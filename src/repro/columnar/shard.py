"""Block-sharded whole-tape execution across a JAX device mesh.

:class:`ShardedTapeBackend` scales the single-device
:class:`~repro.columnar.device.DeviceTapeBackend` past one device's HBM by
partitioning the *block axis* — the axis every array the tape program
touches already leads with — across a 1-D ``("shards",)`` mesh
(:func:`repro.launch.mesh.make_shard_mesh`):

* columns upload as ``f32[N, 32, W]`` bit-major blocks with block rows
  ``[s*B, (s+1)*B)`` pinned to shard ``s`` (``B = nblocks / shards``; the
  power-of-two bucket is padded up to at least one block per shard),
* bitmaps / popcounts shard the same way,
* zone-verdict mask rows ``i32[M, nblocks]`` shard along their *trailing*
  (block) axis, so each shard receives exactly its blocks' verdicts as
  runtime inputs — pruning still never retraces across appends.

The compiled program is the **same** op loop the single-device backend
jits (:func:`repro.columnar.device._tape_forward`), wrapped in
``jax.shard_map``: every shard runs the whole tape over its block slice
(the forward has no cross-block ops, so per-shard results are exact), then
ONE collective — ``all_gather`` for the result bitmap, ``psum`` for the
counter vectors — produces replicated outputs.  The inherited
:meth:`~repro.columnar.device.DeviceTapeBackend.run_tapes` then makes its
usual single bundled ``device_get``: the one-sync contract survives
sharding as one *collective* sync per batch of tapes (``host_syncs == 1``),
and a lockstep batch keeps one bundled collective sync via the inherited
:meth:`materialize`.

Appends stay shard-local: :meth:`refresh` re-uploads only the dirty tail
blocks (the block-epoch contract, unchanged in shape), and
``delta_upload_shards`` counts how many shards the tail actually touched —
a small append lands on ONE shard, the other shards' columns are not
re-uploaded.  Per-shard ``lax.cond`` zone skipping is safe: the forward
contains no collectives, so shards may diverge on the skip branch and
rejoin at the gather.

Sessions and the streaming/serving stack compose unchanged — this class
is just another ``SetBackend``; select it with
``ExecConfig(engine="tape", shards=S)`` (or an explicit ``mesh=``), which
:func:`repro.columnar.executor.resolve_backend` routes here.  Pallas
kernels are not supported under ``shard_map`` (the jnp reference kernels
are what XLA partitions), and multi-device CPU runs must set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before the first
jax import — see ``tests/test_shard.py`` for the subprocess pattern.
"""
from __future__ import annotations

import numpy as np

from ..runtime import faults as _faults
from .config import ConfigError
from .device import (_TAPE_PROGRAM_CAP, _TAPE_PROGRAMS, DeviceTapeBackend,
                     _tape_forward)
from .ingest import dirty_tail
from .table import Table


class ShardedTapeBackend(DeviceTapeBackend):
    """Multi-device tape executor: block-sharded columns, one collective
    sync per batch of tapes.

    Parameters mirror :class:`DeviceTapeBackend` plus:

    shards:  shard count (power of two); builds a fresh 1-D mesh over the
             first ``shards`` devices when ``mesh`` is not given
    mesh:    an existing 1-D mesh with a ``"shards"`` axis to place onto
             (``shards`` then defaults to its size)
    """

    def __init__(self, table: Table, block: int = 8192,
                 kernels: str = "jax", zone_prune: bool = True,
                 shards: int = 1, mesh=None):
        if kernels != "jax":
            raise ConfigError(
                f"kernels={kernels!r}: pallas kernels are not supported "
                "under shard_map — sharded execution partitions the jnp "
                "reference kernels")
        if mesh is None:
            from ..launch.mesh import make_shard_mesh
            mesh = make_shard_mesh(shards)
        if "shards" not in mesh.axis_names:
            raise ConfigError(
                f"mesh axes {mesh.axis_names} lack the 'shards' axis "
                "(build one with launch.mesh.make_shard_mesh)")
        size = int(np.prod(mesh.devices.shape))
        if shards > 1 and size != shards:
            raise ConfigError(f"mesh has {size} devices but "
                              f"shards={shards}")
        if size & (size - 1):
            raise ConfigError(f"shard count must be a power of two, "
                              f"got {size}")
        self.mesh = mesh
        self.shards = size
        super().__init__(table, block=block, kernels="jax",
                         zone_prune=zone_prune)
        # at least one block per shard: pad the power-of-two bucket up
        # (padding blocks carry zero bitmaps / NONE verdicts either way)
        if self.nblocks < self.shards:
            self.nblocks = self.shards
            self._padded = self.nblocks * block
        # shards the appended dirty tail landed on (cumulative, the
        # shard-local delta re-upload metric benches gate on)
        self.delta_upload_shards = 0

    # -- placement -------------------------------------------------------------
    def _sharding(self, kind: str):
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = {"col": P("shards", None, None),
                "bits": P("shards", None),
                "pops": P("shards"),
                "zmask": P(None, "shards")}[kind]
        return NamedSharding(self.mesh, spec)

    def _place(self, arr, kind: str):
        import jax
        return jax.device_put(arr, self._sharding(kind))

    # -- shard-aware delta re-upload -------------------------------------------
    def refresh(self) -> int:
        """Grow after a pure append, shard-locally: only the dirty tail
        blocks upload, and they land on (usually one) owning shard — the
        other shards' device-resident columns are untouched.  The bucket
        may grow, in which case the surviving prefix resharding is
        device-to-device traffic, never a host re-upload."""
        import jax
        import jax.numpy as jnp
        _faults.trip("device.upload", backend=self)
        if self._zones:
            self._zones.clear()
        n_new = self.table.n_records
        if n_new == self.n:
            return 0
        dirty = self.n // self.block
        self.n = n_new
        real_new = self.table.n_blocks(self.block)
        nb = 1
        while nb < max(real_new, self.shards):
            nb *= 2
        self.nblocks = nb
        self._padded = self.nblocks * self.block
        self._full = self._empty = None
        # shard-local accounting: under the (new) block partition B =
        # nblocks / shards, the appended tail [dirty, real_new) intersects
        # exactly these shards' block ranges
        bps = self.nblocks // self.shards
        self.delta_upload_shards += (real_new - 1) // bps - dirty // bps + 1
        up = 0
        for name, col in list(self._jcols.items()):
            if col is False:
                continue               # non-numeric: still host-resident
            raw = self.table.column_data(name)
            tail = dirty_tail(raw, dirty, self.nblocks, self.block)
            up += tail.nbytes
            tail = jnp.asarray(
                tail.reshape(self.nblocks - dirty, self.wpb, 32)
                .transpose(0, 2, 1))
            col = jnp.concatenate([col[:dirty], tail]) if dirty else tail
            self._jcols[name] = jax.device_put(col, self._sharding("col"))
        self.uploaded_bytes += up
        return up

    # -- the shard_map-wrapped tape program ------------------------------------
    def _tape_program(self, tape, meta, skip: bool = False):
        """Same cache, same forward, one wrapper: the single-device op
        loop runs per shard over its block slice inside ``shard_map``, and
        the outputs reduce with one ``all_gather``/``psum`` collective to
        replicated arrays — so the inherited ``run_tapes`` / ``materialize``
        bundling (and their ``host_syncs == 1`` accounting) apply verbatim.
        Appends never retrace here either: the zone masks stay runtime
        inputs, and the cache key only adds the mesh identity."""
        import jax
        from jax.sharding import PartitionSpec as P
        prune = self._zones is not None
        key = (tape.key, self.pallas, self.interpret, prune, skip,
               "shards", self.shards,
               tuple(int(d.id) for d in self.mesh.devices.flat))
        prog = _TAPE_PROGRAMS.get(key)
        if prog is not None:
            _TAPE_PROGRAMS.move_to_end(key)
            return prog
        ops = tape.ops
        result = tape.result
        n_slots = tape.n_slots
        pallas, interpret = self.pallas, self.interpret
        mesh = self.mesh

        def shard_body(cols, values, lmasks, zmasks, full_bits, full_pops):
            res, rec, blk, prn, outs = _tape_forward(
                ops, meta, result, n_slots, prune, skip, pallas, interpret,
                cols, values, lmasks, zmasks, full_bits, full_pops)
            # the ONE collective of the query: result block rows gather
            # back to the full bitmap, counter partial sums tree-reduce
            res = jax.lax.all_gather(res, "shards", axis=0, tiled=True)
            rec = jax.lax.psum(rec, "shards")
            blk = jax.lax.psum(blk, "shards")
            prn = jax.lax.psum(prn, "shards")
            outs = jax.lax.psum(outs, "shards")
            return res, rec, blk, prn, outs

        def program(cols, values, lmasks, zmasks, full_bits, full_pops):
            import jax.numpy as jnp
            if zmasks is None:      # pruning disabled: dummy, never read
                zmasks = jnp.zeros((0, 1), dtype=jnp.int32)
                zspec = P()
            else:
                zspec = P(None, "shards")
            return jax.shard_map(
                shard_body, mesh=mesh,
                in_specs=(tuple(P("shards", None, None) for _ in cols),
                          P(), P(), zspec, P("shards", None), P("shards")),
                out_specs=(P(), P(), P(), P(), P()),
                check_vma=False,
            )(cols, values, lmasks, zmasks, full_bits, full_pops)

        prog = jax.jit(program)
        _TAPE_PROGRAMS[key] = prog
        if len(_TAPE_PROGRAMS) > _TAPE_PROGRAM_CAP:
            _TAPE_PROGRAMS.popitem(last=False)
        return prog
