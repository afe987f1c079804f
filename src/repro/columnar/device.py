"""Device-resident plan execution: compiled tapes + a device SetBackend.

``DeviceTapeBackend`` keeps every record bitmap a plan touches *on the
device* and talks to the host once per batch of queries.  It plays two
roles:

1. **Whole-tape executor** — :meth:`run_tapes` takes a batch of
   :class:`~repro.core.tape.PlanTape` objects and runs each as ONE jitted
   device program: a functional slot file of ``u32[N, W]`` bitmaps (plus
   per-block popcounts for kernel-side dead-block skipping), ATOM ops
   lowered to the fused compare∧bitmap kernel, CHAIN ops to
   ``fused_chain_scan``, SETOPs to ``bitmap_setop`` — then a single
   ``device_get`` fetches every result bitmap together with the per-step
   cost counters.  This is the
   dispatch-count-O(1), host-sync-count-1 path ``run_query(engine="tape")``
   (:meth:`run_tape`, which compiles a program on the call) and the
   sessions' batches use; a session's tape whose program is not compiled
   yet runs op by op while :data:`_COMPILER` compiles it off the batch.

2. **Device-resident SetBackend** — the generic
   :class:`~repro.core.sets.SetBackend` interface over device sets
   (``_DevSet`` = bitmap + per-block popcounts, both ``jnp`` arrays), so the
   *multi-query lockstep executor* runs BestD bookkeeping and fused
   multi-bitmap atom kernels entirely on device: one dispatch per fused
   step, no transfers until the batch's single final
   :meth:`materialize` call.

Design note — zone-verdict masks as runtime inputs
--------------------------------------------------
Pruning reaches the compiled program as *data*: per costed op the backend
combines its atoms' per-block zone verdicts (f32-rounded, matching kernel
arithmetic) into an ``i32[n_blocks]`` NONE/ALL/MAYBE row and feeds the
stacked rows to the jitted program as an ordinary argument — appends that
move the verdicts never retrace.  MAYBE blocks evaluate (masked popcounts
drive the Pallas kernels' dead-block skip), ALL blocks pass source bits
through, NONE blocks zero.  When the mask data shows an op decided on
every block, the backend switches to the program's ``lax.cond`` "skip"
flavor (at most two flavors per tape) whose evaluations short-circuit at
runtime — fully decided ops and everything downstream of emptied sets skip
their scans.  ``records_evaluated`` stays the pre-prune paper metric;
live non-MAYBE blocks land in ``blocks_pruned``.  See
``docs/architecture.md`` ("zone-mask-as-runtime-input").  Fragmented
string predicates stay device-resident the same way: ``codes_expression``
emits ``code IN (...)`` membership atoms bound to packed ``u32[U]`` hit
bitmasks and lowered to ``kernels.dict_lookup``.

Design note — slot allocation and the one-sync-per-query contract
-----------------------------------------------------------------
The tape compiler emits SSA ops and then linear-scan-allocates them onto a
minimal physical slot set, so a tape's working set is a dense
``u32[S, N, W]`` slot file whose ``S`` is typically far below the op count
(BestD's Delta bookkeeping is mostly dead-code-eliminated and its repeated
set ops merged; survivors reuse recycled slots).  During execution nothing
leaves the device: popcounts ride
along as ``i32[N]`` vectors (feeding the Pallas kernels' scalar-prefetch
dead-block skip), per-step record/block counts accumulate into device
vectors, and the final transfer bundles ``(result bitmap, counters)`` into
one ``device_get`` — exactly one host sync per query, or per batch of tapes
(:meth:`DeviceTapeBackend.run_tapes`).  String predicates
over dictionary-encodable columns do NOT relax the contract: the planner
entry points rewrite them into numeric comparisons over the columns' int32
dictionary codes (``columnar.table.rewrite_string_atoms``), which this
backend uploads and executes like any other numeric column — a mixed
numeric/string plan is one device program, one sync, ``host_fallbacks ==
0``.  The contract is relaxed only by genuine **host fallbacks**: opaque
atoms no code-space rewrite exists for (UDFs, fragmented dictionary hit
sets, unrewritten non-numeric columns) round-trip their source slot through
the host gather path, each adding one sync and incrementing
``host_fallbacks``, with semantics matching the oracle backend bit-for-bit.
Tape size limits remain open (slots are allocated eagerly: a pathological
plan with thousands of live intermediate sets would want spilling, which
the compiler does not yet do).

Shapes are **bucketed**: the block count is padded up to a power of two, so
one compiled program serves every table whose padded shape matches — e.g.
the request router's per-call metadata tables of drifting row counts hit
the jit cache instead of retracing per size.  Padded blocks carry zero
bitmaps (their popcounts are 0, so kernels skip them) and zero column
values (masked by the zero bitmaps), keeping results exact.
"""
from __future__ import annotations

import atexit
import contextlib
import functools
import queue
import threading
import time
import traceback
import warnings
import weakref
from collections import OrderedDict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ..core.feedback import group_selectivity
from ..core.predicate import (Atom, ZONE_ALL, ZONE_MAYBE, ZONE_NONE,
                              atom_key, decode_column)
from ..core.sets import SetBackend, Stats
from ..runtime import faults as _faults
from ..core.tape import (ATOM, CHAIN, CMP_OPCODE, EMPTY, FULL, IN_OPCODE,
                         OP_AND, OP_ANDNOT, OP_OR, PlanTape, SETOP,
                         device_atom, lookup_atom, op_observation_meta)
from .bitmap import (WORD, bitmap_full, extend_bitmap, live_block_count,
                     n_words, next_pow2, pack_bits, popcount, unpack_bits)
from .executor import _ZonePruner
from .ingest import dirty_tail
from .table import Table

_CMP_OPCODE = CMP_OPCODE


class _DevSet(NamedTuple):
    """A device-resident record set: packed bitmap + per-block popcounts."""

    bits: "object"        # u32[N, W]
    pops: "object"        # i32[N]


# ---------------------------------------------------------------------------
# Device primitives (raw impls shared by the whole-tape program and the
# jitted per-op wrappers)
# ---------------------------------------------------------------------------

def _setop_bits(a, b, setop: int):
    """The pure-jnp set op on packed bitmaps, without popcounts."""
    import jax.numpy as jnp
    if setop == OP_AND:
        return a & b
    if setop == OP_OR:
        return a | b
    if setop == OP_ANDNOT:
        return a & jnp.bitwise_not(b)
    raise ValueError(f"bad setop {setop}")  # pragma: no cover


def _setop_impl(a, b, setop: int, pallas: bool, interpret: bool):
    if pallas:
        from ..kernels.bitmap_ops import bitmap_setop
        out, pops = bitmap_setop(a, b, setop, interpret=interpret)
        return out, pops[:, 0]
    from ..kernels import ref
    out = _setop_bits(a, b, setop)
    return out, ref.popcount_ref(out)


def _atom_ref_bitmajor(col_bm, bits, value, opcode: int):
    """Pure-jnp ATOM on bit-major columns: col_bm f32[N, 32, W],
    bits u32[N, W] -> u32[N, W]."""
    import jax.numpy as jnp
    from ..kernels import ref
    bitpos = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    in_set = ((bits[:, None, :] >> bitpos) & jnp.uint32(1)).astype(jnp.bool_)
    keep = ref.compare(col_bm, value, opcode) & in_set
    return (keep.astype(jnp.uint32) << bitpos).sum(axis=1, dtype=jnp.uint32)


def _zone_apply_multi(eval_fn, bits, pops, zone, skip: bool):
    """Blend a masked evaluation with its per-block zone verdicts.

    ``bits`` is ``u32[Q, N, W]`` and ``pops`` ``i32[Q, N]`` (the lockstep
    stacking; single-set callers go through :func:`_zone_apply`); ``zone``
    is one shared ``i32[N]`` vector of NONE/ALL/MAYBE verdicts — verdicts
    depend on the atom and the zone map, not on the record set — arriving
    as *runtime data* (never a trace constant: appends that move the
    verdicts must not retrace the program).  MAYBE blocks take the
    evaluation's bits, ALL blocks pass the source bits through unchanged,
    NONE blocks produce zeros; the masked popcounts feed the Pallas
    kernels' scalar-prefetch skip, which elides non-MAYBE blocks on
    hardware.

    ``skip`` (a *static* program flavor, not data) additionally puts the
    evaluation under a ``lax.cond`` on "any live MAYBE block": ops fully
    decided by their zone maps — and every op downstream of an emptied
    set — then skip the column scan at runtime.  The cond is not free on
    CPU (XLA materializes the branch operands, ~a column copy per op), so
    the backend requests this flavor only when the masks actually decide
    some op outright; the cond-free flavor keeps the unpruned program's
    fused graph verbatim and adds only the per-block blend.
    """
    import jax
    import jax.numpy as jnp
    from ..kernels import ref
    maybe = zone == ZONE_MAYBE
    ep = jnp.where(maybe[None], pops, 0)

    def _eval(_):
        out = eval_fn(ep)
        return out, ref.popcount_ref(out)

    if skip:
        out0, p0 = jax.lax.cond(
            ep.sum() > 0, _eval,
            lambda _: (jnp.zeros_like(bits), jnp.zeros_like(pops)), None)
    else:
        out0, p0 = _eval(None)
    allm = zone == ZONE_ALL
    out = jnp.where(allm[None, :, None], bits,
                    jnp.where(maybe[None, :, None], out0, 0))
    p = jnp.where(allm[None], pops, jnp.where(maybe[None], p0, 0))
    return out, p


def _zone_apply(eval_fn, bits, pops, zone, skip: bool):
    """Single-set (``u32[N, W]``) view of :func:`_zone_apply_multi` — one
    implementation of the verdict-blend/skip semantics serves both the
    whole-tape program and the lockstep stacking."""
    out, p = _zone_apply_multi(
        lambda ep: eval_fn(ep[0])[None], bits[None], pops[None], zone, skip)
    return out[0], p[0]


def _atom_impl(col_bm, bits, pops, value, opcode: int, pallas: bool,
               interpret: bool, zone=None, skip: bool = False):
    import jax.numpy as jnp
    from ..kernels import ref

    def _eval(ep):
        if pallas:
            from ..kernels.predicate_scan import predicate_scan
            val = jnp.asarray(value, dtype=jnp.float32).reshape(1)
            return predicate_scan(col_bm, bits,
                                  pops if ep is None else ep, val, opcode,
                                  interpret=interpret)
        return _atom_ref_bitmajor(col_bm, bits, value, opcode)

    if zone is None:
        out = _eval(None)
        return out, ref.popcount_ref(out)
    return _zone_apply(_eval, bits, pops, zone, skip)


def _lookup_impl(col_bm, bits, pops, mask_words, pallas: bool,
                 interpret: bool, zone=None, skip: bool = False):
    """Dictionary-membership ATOM: col_bm f32[N, 32, W] int codes tested
    against the packed u32[U] hit bitmask (kernels.dict_lookup)."""
    import jax.numpy as jnp
    from ..kernels import ref

    def _eval(ep):
        if pallas:
            from ..kernels.dict_lookup import dict_lookup_scan
            return dict_lookup_scan(col_bm, bits,
                                    pops if ep is None else ep, mask_words,
                                    interpret=interpret)
        bitpos = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
        in_set = ((bits[:, None, :] >> bitpos)
                  & jnp.uint32(1)).astype(jnp.bool_)
        hit = ref.code_hits(col_bm.astype(jnp.int32), mask_words)
        return ((hit & in_set).astype(jnp.uint32) << bitpos).sum(
            axis=1, dtype=jnp.uint32)

    if zone is None:
        out = _eval(None)
        return out, ref.popcount_ref(out)
    return _zone_apply(_eval, bits, pops, zone, skip)


def _chain_impl(cols_bm, bits, pops, values, opcodes: tuple, conj: bool,
                pallas: bool, interpret: bool, zone=None,
                skip: bool = False):
    """cols_bm f32[N, K, 32, W]; bits u32[N, W]; values f32[K]."""
    import jax.numpy as jnp
    from ..kernels import ref

    def _eval(ep):
        if pallas:
            from ..kernels.fused_chain import fused_chain_scan
            return fused_chain_scan(cols_bm, bits,
                                    pops if ep is None else ep,
                                    jnp.asarray(values, dtype=jnp.float32),
                                    opcodes, conj=conj, interpret=interpret)
        acc = None
        for k, op in enumerate(opcodes):
            cmp = ref.compare(cols_bm[:, k], values[k], op)
            acc = cmp if acc is None else (acc & cmp if conj else acc | cmp)
        bitpos = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
        in_set = ((bits[:, None, :] >> bitpos)
                  & jnp.uint32(1)).astype(jnp.bool_)
        return ((acc & in_set).astype(jnp.uint32) << bitpos).sum(
            axis=1, dtype=jnp.uint32)

    if zone is None:
        out = _eval(None)
        return out, ref.popcount_ref(out)
    return _zone_apply(_eval, bits, pops, zone, skip)


def _multi_atom_impl(col_bm, bits, pops, value, opcode: int, pallas: bool,
                     interpret: bool, zone=None, skip: bool = False):
    """col_bm f32[N, 32, W]; bits u32[Q, N, W]; pops i32[Q, N]."""
    import jax.numpy as jnp
    from ..kernels import ref
    q, n, w = bits.shape

    def _eval(ep):
        if pallas:
            from ..kernels.predicate_scan import predicate_scan_multi
            val = jnp.asarray(value, dtype=jnp.float32).reshape(1)
            p = (pops if ep is None else ep).reshape(-1)
            return predicate_scan_multi(col_bm, bits.reshape(q * n, w),
                                        p, val, opcode,
                                        interpret=interpret).reshape(q, n, w)
        bitpos = jnp.arange(32, dtype=jnp.uint32)[None, None, :, None]
        in_set = ((bits[:, :, None, :] >> bitpos)
                  & jnp.uint32(1)).astype(jnp.bool_)
        keep = ref.compare(col_bm, value, opcode)[None] & in_set
        return (keep.astype(jnp.uint32) << bitpos).sum(axis=2,
                                                       dtype=jnp.uint32)

    if zone is None:
        out = _eval(None)
        return out, ref.popcount_ref(out)
    return _zone_apply_multi(_eval, bits, pops, zone, skip)


def _lookup_multi_impl(col_bm, bits, pops, mask_words, pallas: bool,
                       interpret: bool, zone=None, skip: bool = False):
    """Q-stacked dictionary-membership lookup (one code-column copy)."""
    import jax.numpy as jnp
    from ..kernels import ref
    q, n, w = bits.shape

    def _eval(ep):
        if pallas:
            from ..kernels.dict_lookup import dict_lookup_scan_multi
            p = (pops if ep is None else ep).reshape(-1)
            return dict_lookup_scan_multi(
                col_bm, bits.reshape(q * n, w), p, mask_words,
                interpret=interpret).reshape(q, n, w)
        bitpos = jnp.arange(32, dtype=jnp.uint32)[None, None, :, None]
        in_set = ((bits[:, :, None, :] >> bitpos)
                  & jnp.uint32(1)).astype(jnp.bool_)
        hit = ref.code_hits(col_bm.astype(jnp.int32), mask_words)
        keep = hit[None] & in_set
        return (keep.astype(jnp.uint32) << bitpos).sum(axis=2,
                                                       dtype=jnp.uint32)

    if zone is None:
        out = _eval(None)
        return out, ref.popcount_ref(out)
    return _zone_apply_multi(_eval, bits, pops, zone, skip)


def _inter_multi_impl(a, bits):
    """One set AND-ed against Q stacked sets in ONE dispatch: a u32[N, W],
    bits u32[Q, N, W] -> (u32[Q, N, W], i32[Q, N])."""
    from ..kernels import ref
    out = bits & a[None]
    return out, ref.popcount_ref(out)


def _union_impl(bits, pops):
    """Union-reduce Q stacked device sets in ONE dispatch (the union is
    only needed for fallback detection + cost accounting)."""
    from ..kernels import ref
    out = bits[0]
    for j in range(1, bits.shape[0]):
        out = out | bits[j]
    return out, ref.popcount_ref(out)


def _jit(fn, static):
    import jax
    return functools.partial(jax.jit, static_argnames=static)(fn)


@functools.lru_cache(maxsize=None)
def _jitted_prims():
    """Per-op jitted wrappers (built lazily so importing this module does
    not pull in jax)."""
    return {
        "setop": _jit(_setop_impl, ("setop", "pallas", "interpret")),
        "atom": _jit(_atom_impl, ("opcode", "pallas", "interpret",
                                  "skip")),
        "lookup": _jit(_lookup_impl, ("pallas", "interpret", "skip")),
        "chain": _jit(_chain_impl, ("opcodes", "conj", "pallas",
                                    "interpret", "skip")),
        "multi": _jit(_multi_atom_impl, ("opcode", "pallas", "interpret",
                                         "skip")),
        "lookup_multi": _jit(_lookup_multi_impl, ("pallas", "interpret",
                                                  "skip")),
        "union": _jit(_union_impl, ()),
        "inter_multi": _jit(_inter_multi_impl, ()),
    }


# Whole-tape compiled programs, shared across backends/tables: keyed by
# (tape structural key, kernel flavor, interpret) — jax.jit then caches per
# concrete (bucketed) shape underneath.  LRU-bounded so a long-lived server
# seeing evolving query shapes cannot grow it without bound.
_TAPE_PROGRAMS: "OrderedDict[tuple, object]" = OrderedDict()
_TAPE_PROGRAM_CAP = 256


class _BackgroundCompiler:
    """Compiles whole-tape programs on one daemon thread.

    Tiered execution of a session's batches
    (:meth:`DeviceTapeBackend.run_tapes`): a tape whose program is not
    compiled yet for its argument shapes runs op by op over the shared
    per-op prims, and its program is queued here (once per program and
    shapes), so no batch waits seconds on the compiler for a plan it has
    not met.  A job holds the arguments' abstract values, never the
    arrays, and ``prog.lower(*avals).compile()`` fills the executable
    cache a call of ``prog`` reads, so the tape's next run is one dispatch
    with no trace and no compile.  Each compile runs in a ``tape.compile``
    span of the tracer the batch passed, so the compile listener of
    :mod:`~repro.columnar.trace` records its ``jax.compile`` there.  A
    compile that fails leaves the tape on the op-by-op path for good.
    """

    def __init__(self):
        self._lock = threading.Lock()
        # program -> {argument signature: compiled}; weak, so an evicted
        # program takes its entries with it
        self._done: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
        self._queued: set = set()
        self._jobs: "queue.Queue" = queue.Queue()
        self._thread: Optional[threading.Thread] = None
        #: tracebacks of the compiles that failed
        self.failures: List[str] = []

    @staticmethod
    def _avals(args):
        """What a call's executable is keyed by: each array's shape, dtype,
        weak type and, when it is committed to devices, its sharding."""
        import jax
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, weak_type=x.weak_type,
                sharding=x.sharding if x.committed else None), args)

    @staticmethod
    def _signature(avals) -> tuple:
        import jax
        leaves, tree = jax.tree_util.tree_flatten(avals)
        return tree, tuple((a.shape, a.dtype, a.weak_type, a.sharding)
                           for a in leaves)

    def ready(self, prog, args, tracer=None) -> bool:
        """True when a call ``prog(*args)`` runs compiled code; otherwise
        queues the compile (if it is not queued already, in a span of
        ``tracer``) and returns False."""
        avals = self._avals(args)
        sig = self._signature(avals)
        with self._lock:
            done = self._done.get(prog, {})
            if sig in done:
                return done[sig]
            if (id(prog), sig) not in self._queued:
                self._queued.add((id(prog), sig))
                self._jobs.put((prog, avals, sig, tracer))
                if self._thread is None:
                    self._thread = threading.Thread(
                        target=self._work, name="tape-compile", daemon=True)
                    self._thread.start()
                    atexit.register(self._stop)
        return False

    def wait(self, timeout: float) -> bool:
        """Block until no compile is queued or running; False if
        ``timeout`` seconds pass first."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                if not self._queued:
                    return True
            time.sleep(0.01)
        return False

    def _work(self) -> None:
        while True:
            job = self._jobs.get()
            if job is None:
                return
            prog, avals, sig, tracer = job
            span = (tracer.span("tape.compile") if tracer is not None
                    else contextlib.nullcontext())
            try:
                with span:
                    prog.lower(*avals).compile()
                ok = True
            except Exception:       # the op-by-op path keeps serving
                ok = False
                self.failures.append(traceback.format_exc())
                warnings.warn("whole-tape program failed to compile; the "
                              "tape keeps running op by op:\n"
                              + self.failures[-1], RuntimeWarning)
            with self._lock:
                self._queued.discard((id(prog), sig))
                self._done.setdefault(prog, {})[sig] = ok
            del job, prog, avals, tracer

    def _stop(self) -> None:
        """At exit: drop queued compiles and let the one in flight end
        before the runtime tears down."""
        with self._lock:
            while not self._jobs.empty():
                self._jobs.get_nowait()
            self._jobs.put(None)
        self._thread.join(timeout=300)


_COMPILER = _BackgroundCompiler()


def _tape_forward(ops, meta, result, n_slots, prune, skip, pallas, interpret,
                  cols, values, lmasks, zmasks, full_bits, full_pops):
    """The whole-tape op loop, as a pure function of device arrays.

    This is the body :meth:`DeviceTapeBackend._tape_program` jits, factored
    out so :class:`~repro.columnar.shard.ShardedTapeBackend` can wrap the
    *same* forward in ``jax.shard_map``: every array argument is block-major
    on its leading (or, for zmasks, trailing) axis, so a shard running this
    over its block slice computes exactly its rows of the result and its
    partial sums of the counters — the sharded program reduces them with
    one ``all_gather``/``psum`` collective and the single-sync contract
    survives sharding unchanged.

    Returns ``(bits[result], rec, blk, prn, out)`` — result bitmap plus the
    per-costed-op record / touched-block / pruned-block / realized-output
    counter vectors that ride the one bundled transfer.
    """
    import jax.numpy as jnp
    from ..kernels import ref
    bits: List[object] = [None] * n_slots
    pops: List[object] = [None] * n_slots
    recs, blks, prns, outs = [], [], [], []
    mi = 0
    for oi, op in enumerate(ops):
        if op.kind == FULL:
            b, p = full_bits, full_pops
        elif op.kind == EMPTY:
            b = jnp.zeros_like(full_bits)
            p = jnp.zeros_like(full_pops)
        elif op.kind == SETOP and not pallas:
            # the popcounts of a set op's result are only read if an atom
            # op takes it as its source: count them there, on demand
            b, p = _setop_bits(bits[op.a], bits[op.b], op.setop), None
        elif op.kind == SETOP:
            b, p = _setop_impl(bits[op.a], bits[op.b], op.setop,
                               pallas, interpret)
        else:
            cixs, vixs, opcodes = meta[oi]
            sb, sp = bits[op.a], pops[op.a]
            if sp is None:
                sp = ref.popcount_ref(sb)
            # records_evaluated stays the PRE-prune popcount (the
            # paper metric describes the plan, not the pruning);
            # blocks split into touched (live MAYBE) and pruned
            recs.append(sp.sum())
            zone = zmasks[mi] if prune else None
            mi += 1
            if zone is None:
                blks.append((sp > 0).sum())
                prns.append(jnp.int32(0))
            else:
                live = sp > 0
                maybe = zone == ZONE_MAYBE
                blks.append((live & maybe).sum())
                prns.append((live & ~maybe).sum())
            if opcodes[0] == IN_OPCODE:
                b, p = _lookup_impl(cols[cixs[0]], sb, sp,
                                    lmasks[vixs[0]], pallas,
                                    interpret, zone=zone,
                                    skip=skip)
            elif op.kind == ATOM:
                b, p = _atom_impl(cols[cixs[0]], sb, sp,
                                  values[vixs[0]], opcodes[0],
                                  pallas, interpret, zone=zone,
                                  skip=skip)
            else:
                stack = jnp.stack([cols[c] for c in cixs], axis=1)
                vals = jnp.stack([values[v] for v in vixs])
                b, p = _chain_impl(stack, sb, sp, vals, opcodes,
                                   op.conj, pallas, interpret,
                                   zone=zone, skip=skip)
            # realized output popcount — already computed for the
            # dead-block skip, so surfacing it is free: the Q-Error
            # feedback loop's ground truth rides the existing sync
            outs.append(p.sum())
        bits[op.dst] = b
        pops[op.dst] = p
    rec = (jnp.stack(recs) if recs
           else jnp.zeros((0,), dtype=jnp.int32))
    blk = (jnp.stack(blks) if blks
           else jnp.zeros((0,), dtype=jnp.int32))
    prn = (jnp.stack(prns) if prns
           else jnp.zeros((0,), dtype=jnp.int32))
    out = (jnp.stack(outs) if outs
           else jnp.zeros((0,), dtype=jnp.int32))
    return bits[result], rec, blk, prn, out

#: eager bookkeeping ops of one zone-pruned compare atom through
#: ``DeviceTapeBackend.apply_atom``: 8 cost counters, 1 verdict upload,
#: 2 feedback popcounts
ZONED_ATOM_BOOKKEEPING = 11

#: bound on a backend's undrained observation log — sessions drain it every
#: batch; standalone benchmark loops must not grow it without bound
_OP_LOG_CAP = 4096


class DeviceTapeBackend(SetBackend):
    """Device-resident executor: whole-plan tapes + a device SetBackend.

    Parameters
    ----------
    table:     the columnar table (numeric columns are uploaded once, as
               bit-major f32 blocks, and cached for the backend's lifetime)
    block:     records per block (multiple of 32; the padded block count is
               bucketed to a power of two for jit-cache sharing)
    kernels:   "jax" = pure-jnp ops fused by XLA; "pallas" = the Pallas
               kernels (interpret mode off-TPU, see
               :func:`repro.kernels.ops.interpret_mode`)

    Dispatch counters (lifetime, listed in
    :data:`~repro.columnar.trace.BACKEND_COUNTERS`).  A *launch* is one
    call into JAX that issues device work: a jitted program, or one eager
    ``jnp`` operation or transfer (which may itself run more than one XLA
    executable).

    ``kernel_launches`` / ``kernel_host_s``
        the jitted atom, lookup, multi, lookup_multi and chain prims and
        whole-tape programs; the seconds include binding the atom's column
        and lookup mask
    ``setop_launches`` / ``setop_host_s``
        the setop, inter_multi and union prims and the OR of
        :meth:`extend_set`; ``kernel_launches + setop_launches ==
        device_dispatches``
    ``bookkeeping_launches`` / ``bookkeeping_host_s``
        every other eager device op: the cost counters of
        :meth:`_account`, the feedback popcounts of :meth:`_fb_queue`, the
        stack and unstack of the multi paths, zone-verdict and lookup-mask
        uploads, ``full``/``empty`` set uploads, and :meth:`materialize`'s
        counter stacks.  A zone-pruned compare atom
        through :meth:`apply_atom` issues :data:`ZONED_ATOM_BOOKKEEPING`.
    ``zone_host_s``
        host seconds in :meth:`_zone_mask`

    Host seconds accrue only in the calls the executors make while
    dispatching (``apply_atom*``, the set ops, ``inter_multi``,
    ``run_tapes``), at one ``perf_counter`` pair per timed stretch, and the
    four never overlap; launches count wherever they happen (the OR of
    :meth:`extend_set` during upload, :meth:`materialize` during sync).
    No counter adds a sync.
    """

    def __init__(self, table: Table, block: int = 8192,
                 kernels: str = "jax", zone_prune: bool = True):
        if block % WORD:
            raise ValueError("block must be a multiple of 32")
        if kernels not in ("jax", "pallas"):
            raise ValueError(f"unknown kernels {kernels!r}")
        from ..kernels.ops import interpret_mode
        self.table = table
        self.n = table.n_records
        self.block = block
        self.kernels = kernels
        self.pallas = kernels == "pallas"
        self.interpret = interpret_mode()
        self.wpb = block // WORD
        self.nblocks = next_pow2((self.n + block - 1) // block)
        self._padded = self.nblocks * block
        self.stats = Stats()
        self.blocks_touched = 0.0
        self.records_touched = 0.0
        self.blocks_pruned = 0.0      # blocks decided by zone maps alone
        self.host_syncs = 0
        self.host_fallbacks = 0
        self.device_dispatches = 0
        # tapes run_tapes ran op by op while their program compiled
        self.cold_tapes = 0
        self.uploaded_bytes = 0       # host->device column traffic
        # the dispatch split (see the class docstring)
        self.kernel_launches = 0
        self.kernel_host_s = 0.0
        self.setop_launches = 0
        self.setop_host_s = 0.0
        self.bookkeeping_launches = 0
        self.bookkeeping_host_s = 0.0
        self.zone_host_s = 0.0
        self.last_tape: Optional[PlanTape] = None
        self._jcols: Dict[str, "object"] = {}
        self._full: Optional[_DevSet] = None
        self._empty: Optional[_DevSet] = None
        # zone-verdict pruner (f32: the kernels compare in float32, so the
        # verdicts must round the same way — the JaxBlockBackend precedent)
        self._zones = (_ZonePruner(table, block, f32=True)
                       if zone_prune else None)
        # device-side pending cost counters, flushed by materialize()
        self._pend_records: List[object] = []
        self._pend_k: List[int] = []
        self._pend_weights: List[float] = []
        self._pend_blocks: List[object] = []
        self._pend_pruned: List[object] = []
        # realized-selectivity observations (the Q-Error feedback channel):
        # op_log holds host-resolved (atom_keys, est, src, out) tuples;
        # device-resident src/out popcount scalars queue in _fb_* and ride
        # the SAME bundled transfer materialize() already makes — feedback
        # adds zero host syncs and zero kernel dispatches
        self.op_log: List[Tuple] = []
        self._fb_meta: List[Tuple[Tuple, float]] = []
        self._fb_src: List[object] = []
        self._fb_out: List[object] = []

    # -- conversions -----------------------------------------------------------
    def _place(self, arr, kind: str):
        """Host array -> device array, the single placement point every
        upload funnels through.  ``kind`` names the layout: ``col``
        (f32[N, 32, W] bit-major column blocks), ``bits`` (u32[N, W] packed
        bitmap), ``pops`` (i32[N]), ``zmask`` (i32[M, N] verdict rows).
        The base backend places on the default device;
        :class:`~repro.columnar.shard.ShardedTapeBackend` overrides this to
        pin each kind's block axis to the 1-D shard mesh."""
        import jax.numpy as jnp
        return jnp.asarray(arr)

    def _lap(self, counter: str, since: float) -> float:
        """Add the host seconds since ``since`` to the ``counter``
        attribute; returns now, the start of the next timed stretch."""
        now = time.perf_counter()
        setattr(self, counter, getattr(self, counter) + now - since)
        return now

    def _eager(self, fn, *args, **kwargs):
        """One eager bookkeeping op on the dispatch path, counted and
        timed (``jnp.asarray`` of a verdict row or lookup mask, the stack
        of a multi path)."""
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        self.bookkeeping_launches += 1
        self._lap("bookkeeping_host_s", t0)
        return out

    def _unstack(self, bits, pops, q: int) -> List[_DevSet]:
        """Split stacked ``[q, ...]`` results into q device sets (two
        eager slices per set, counted and timed as bookkeeping)."""
        t0 = time.perf_counter()
        out = [_DevSet(bits[j], pops[j]) for j in range(q)]
        self.bookkeeping_launches += 2 * q
        self._lap("bookkeeping_host_s", t0)
        return out

    def _col_bitmajor(self, name: str):
        """Column as bit-major f32[N, 32, W] device blocks (None if the
        column is not numeric).  Resolves derived dictionary-code columns
        through ``Table.column_data``, so rewritten string atoms upload the
        int32 codes and run the same fused comparison kernels."""
        col = self._jcols.get(name)
        if col is None:
            raw = self.table.column_data(name)
            if not np.issubdtype(raw.dtype, np.number):
                self._jcols[name] = False
                return None
            arr = np.zeros(self._padded, dtype=np.float32)
            arr[: self.n] = raw.astype(np.float32)
            self.uploaded_bytes += arr.nbytes
            col = self._place(arr.reshape(self.nblocks, self.wpb, 32)
                              .transpose(0, 2, 1), "col")
            self._jcols[name] = col
        elif col is False:
            return None
        return col

    def _lookup_mask(self, atom: Atom) -> Optional[np.ndarray]:
        """Packed ``u32[U]`` hit bitmask for a dictionary-membership atom:
        bit ``c`` set iff code ``c`` is in the atom's value set.  ``U`` is
        the dictionary's word count padded to a power of two, so modest
        dictionary growth under appends keeps the kernel shape (and the
        jitted program) stable.  None when the atom's column is not a
        dictionary-code column of this table."""
        base = decode_column(atom.column)
        if base is None or base not in self.table.columns:
            return None
        dc = self.table.dict_column(base)
        if dc is None:
            return None
        nbits = WORD * next_pow2(n_words(max(dc.n, 1)))
        hits = np.zeros(nbits, dtype=bool)
        idx = np.asarray([int(v) for v in atom.value], dtype=np.int64)
        idx = idx[(idx >= 0) & (idx < dc.n)]
        hits[idx] = True
        return pack_bits(hits)

    def _zone_mask(self, atoms: Sequence[Atom], conj: bool = True,
                   exact: bool = False) -> Optional[np.ndarray]:
        """Combined ``i32[nblocks]`` NONE/ALL/MAYBE verdicts for one
        ATOM/CHAIN op's atom group, or None when nothing prunes (no zone
        maps, every block MAYBE, or a stale map mid-append).  CHAIN groups
        combine per-atom verdicts with the group's own connective: under
        AND a single NONE decides the block and ALL needs every atom ALL;
        under OR dually.  Power-of-two padding blocks get NONE — they
        carry zero bitmaps either way.  ``exact=True`` skips the f32
        rounding of the verdicts — required by the host-gather fallback,
        which evaluates in float64 (the JaxBlockBackend precedent)."""
        if self._zones is None:
            return None
        t0 = time.perf_counter()
        try:
            real = (self.n + self.block - 1) // self.block
            out = None
            any_verdict = False
            for a in atoms:
                v = self._zones.verdicts(a, exact=exact)
                if v is None:
                    v = np.full(real, ZONE_MAYBE, dtype=np.int8)
                elif len(v) != real:
                    return None   # zone map describes a different snapshot
                else:
                    any_verdict = True
                if out is None:
                    out = v.astype(np.int32)
                    continue
                if conj:
                    none = (out == ZONE_NONE) | (v == ZONE_NONE)
                    alls = (out == ZONE_ALL) & (v == ZONE_ALL)
                else:
                    alls = (out == ZONE_ALL) | (v == ZONE_ALL)
                    none = (out == ZONE_NONE) & (v == ZONE_NONE)
                out = np.full(real, ZONE_MAYBE, dtype=np.int32)
                out[alls] = ZONE_ALL
                out[none] = ZONE_NONE
            if not any_verdict or (out == ZONE_MAYBE).all():
                return None
            pad = np.full(self.nblocks, ZONE_NONE, dtype=np.int32)
            pad[:real] = out
            return pad
        finally:
            self._lap("zone_host_s", t0)

    def refresh(self) -> int:
        """Grow the backend after a pure table *append*: device-resident
        columns keep every block below the append boundary and upload only
        the dirty tail (the power-of-two block-count bucket may grow, in
        which case the new padding blocks ride along as zeros).  Caller
        must have proven the append via :meth:`Table.delta_since`.  Returns
        the bytes uploaded."""
        import jax.numpy as jnp
        _faults.trip("device.upload", backend=self)
        if self._zones:
            self._zones.clear()
        n_new = self.table.n_records
        if n_new == self.n:
            return 0
        dirty = self.n // self.block
        self.n = n_new
        self.nblocks = next_pow2((n_new + self.block - 1) // self.block)
        self._padded = self.nblocks * self.block
        self._full = self._empty = None
        up = 0
        for name, col in list(self._jcols.items()):
            if col is False:
                continue               # non-numeric: still host-resident
            raw = self.table.column_data(name)
            tail = dirty_tail(raw, dirty, self.nblocks, self.block)
            up += tail.nbytes
            tail = self._place(
                tail.reshape(self.nblocks - dirty, self.wpb, 32)
                .transpose(0, 2, 1), "col")
            self._jcols[name] = (jnp.concatenate([col[:dirty], tail])
                                 if dirty else tail)
        self.uploaded_bytes += up
        return up

    def extend_set(self, s: _DevSet, old_n: int, delta_hits) -> _DevSet:
        """Splice the appended rows' hit mask into a cached device set (the
        streaming delta path): the old bitmap's blocks stay on device and
        only the delta words upload — one OR dispatch, no host sync."""
        import jax.numpy as jnp
        from ..kernels import ref
        delta_hits = np.asarray(delta_hits, dtype=bool)
        flat = extend_bitmap(np.zeros(n_words(old_n), dtype=np.uint32),
                             old_n, delta_hits, old_n + len(delta_hits))
        words = np.zeros(self.nblocks * self.wpb, dtype=np.uint32)
        words[: len(flat)] = flat
        bits = s.bits
        if bits.shape[0] < self.nblocks:
            bits = jnp.pad(bits, ((0, self.nblocks - bits.shape[0]), (0, 0)))
            self.bookkeeping_launches += 1
        self.device_dispatches += 1
        self.setop_launches += 1
        bits = bits | self._place(words.reshape(self.nblocks, self.wpb),
                                  "bits")
        self.bookkeeping_launches += 2      # the upload, the popcount
        return _DevSet(bits, ref.popcount_ref(bits))

    def _from_flat(self, words: np.ndarray) -> _DevSet:
        """Host flat packed words -> device blocked set."""
        from ..kernels import ref
        t0 = time.perf_counter()
        padded = np.zeros(self.nblocks * self.wpb, dtype=np.uint32)
        padded[: n_words(self.n)] = words
        bits = self._place(padded.reshape(self.nblocks, self.wpb), "bits")
        out = _DevSet(bits, ref.popcount_ref(bits))
        self.bookkeeping_launches += 2      # the upload, the popcount
        self._lap("bookkeeping_host_s", t0)
        return out

    def _cut(self, bits) -> np.ndarray:
        """Fetched bucket-padded ``u32[N, W]`` bits -> host flat packed
        words of the real length.  The cut is made on the host, so no
        device op takes its shape from the row count (an append inside the
        block bucket compiles nothing)."""
        return np.asarray(bits).reshape(-1)[: n_words(self.n)]

    def _pull_flat(self, d: _DevSet) -> np.ndarray:
        """One host sync: fetch a device set as host flat packed words."""
        import jax
        self.host_syncs += 1
        return self._cut(jax.device_get(d.bits))

    # -- SetBackend ------------------------------------------------------------
    def full(self) -> _DevSet:
        if self._full is None:
            self._full = self._from_flat(bitmap_full(self.n))
        return self._full

    def empty(self) -> _DevSet:
        if self._empty is None:
            t0 = time.perf_counter()
            bits = self._place(np.zeros((self.nblocks, self.wpb),
                                        dtype=np.uint32), "bits")
            pops = self._place(np.zeros((self.nblocks,),
                                        dtype=np.int32), "pops")
            self._empty = _DevSet(bits, pops)
            self.bookkeeping_launches += 2
            self._lap("bookkeeping_host_s", t0)
        return self._empty

    def _setop(self, a: _DevSet, b: _DevSet, code: int) -> _DevSet:
        t0 = time.perf_counter()
        self.stats.setops += 1
        self.device_dispatches += 1
        self.setop_launches += 1
        out, pops = _jitted_prims()["setop"](a.bits, b.bits, setop=code,
                                             pallas=self.pallas,
                                             interpret=self.interpret)
        self._lap("setop_host_s", t0)
        return _DevSet(out, pops)

    def inter(self, a, b):
        return self._setop(a, b, OP_AND)

    def union(self, a, b):
        return self._setop(a, b, OP_OR)

    def diff(self, a, b):
        return self._setop(a, b, OP_ANDNOT)

    def count(self, d: _DevSet) -> float:
        import jax
        self.host_syncs += 1
        return float(jax.device_get(d.pops.sum()))

    def inter_multi(self, a: _DevSet, ds: Sequence[_DevSet]
                    ) -> List[_DevSet]:
        """Q cached-atom intersections in ONE stacked dispatch (the
        lockstep executor's atom-cache hit path: per-query setops would
        otherwise cost a dispatch each)."""
        if len(ds) == 1:
            return [self.inter(a, ds[0])]
        import jax.numpy as jnp
        bits = self._eager(jnp.stack, [d.bits for d in ds])
        t0 = time.perf_counter()
        self.stats.setops += len(ds)
        self.device_dispatches += 1
        self.setop_launches += 1
        out, pops = _jitted_prims()["inter_multi"](a.bits, bits)
        self._lap("setop_host_s", t0)
        return self._unstack(out, pops, len(ds))

    def _account(self, atoms: Sequence[Atom], pops, device: bool = True,
                 zone: Optional[np.ndarray] = None):
        """Queue device-side cost counters for one costed application of
        ``atoms`` (K > 1 for a fused chain: every chain atom evaluates on
        all of src's live blocks, so counts scale by K — the fused trade of
        +evaluations for -passes stays visible in the paper metrics).

        ``device=False`` (host fallback) still counts records_evaluated —
        count(D) is engine-independent — but leaves blocks/records_touched
        to the fallback's own gather accounting.  ``zone`` (the op's
        NONE/ALL/MAYBE verdicts) splits the live blocks into touched
        (MAYBE: the kernel pays for them) and pruned (decided by the zone
        map alone); ``records_evaluated`` stays the *pre-prune* count — the
        paper metric measures the plan, not the storage-level pruning, so
        plan-quality comparisons are unaffected (the JaxBlockBackend
        precedent).

        Eager device ops (bookkeeping launches): 3 on the host path, 4
        unpruned, 8 zone-pruned.
        """
        import jax.numpy as jnp
        t0 = time.perf_counter()
        self.stats.atom_applications += len(atoms)
        self._pend_records.append(pops.sum())
        self._pend_k.append(len(atoms))
        self._pend_weights.append(sum(a.cost_factor for a in atoms))
        if not device:
            self._pend_blocks.append(jnp.int32(0))
            self._pend_pruned.append(jnp.int32(0))
            launches = 3
        elif zone is None:
            self._pend_blocks.append((pops > 0).sum())
            self._pend_pruned.append(jnp.int32(0))
            launches = 4
        else:
            maybe = jnp.asarray(zone == ZONE_MAYBE)
            live = pops > 0
            self._pend_blocks.append((live & maybe).sum())
            self._pend_pruned.append((live & ~maybe).sum())
            launches = 8
        self.bookkeeping_launches += launches
        self._lap("bookkeeping_host_s", t0)

    # -- realized-selectivity feedback (rides the existing syncs) --------------
    def _log_op(self, keys: Tuple, est: float, src: int, out: int) -> None:
        """Record one host-resolved observation ``(atom_keys, estimated
        fraction, source popcount, output popcount)``.  Sessions drain the
        log every batch (:meth:`drain_op_log`); it is capped so undrained
        standalone loops stay bounded."""
        self.op_log.append((keys, float(est), int(src), int(out)))
        if len(self.op_log) > _OP_LOG_CAP:
            del self.op_log[: len(self.op_log) - _OP_LOG_CAP]

    def _fb_queue(self, atoms: Sequence[Atom], conj: bool, src_pops,
                  out_pops) -> None:
        """Queue one observation from the source and output per-block
        popcounts (``i32[N]``, or stacked ``i32[Q, N]``): their totals stay
        device scalars (or ``i32[Q]`` vectors) and ride the bundled
        transfer :meth:`materialize` already makes — no extra sync.  Two
        eager device ops (bookkeeping launches)."""
        t0 = time.perf_counter()
        est = group_selectivity([a.selectivity for a in atoms], conj)
        self._fb_meta.append((tuple(atom_key(a) for a in atoms), est))
        self._fb_src.append(src_pops.sum(axis=-1))
        self._fb_out.append(out_pops.sum(axis=-1))
        self.bookkeeping_launches += 2
        self._lap("bookkeeping_host_s", t0)

    def drain_op_log(self) -> List[Tuple]:
        """Pop accumulated ``(keys, est, src, out)`` observations."""
        out = self.op_log
        self.op_log = []
        return out

    def _host_gather(self, grp: Sequence[Atom], conj: bool,
                     sw: np.ndarray) -> Tuple[np.ndarray, int, int]:
        """Zone-pruned gather-evaluate-scatter for a host-fallback atom
        group: exact float64 zone verdicts (combined under the group's
        connective) restrict the gather to MAYBE blocks — NONE blocks
        contribute nothing, ALL blocks pass their source bits through
        without touching the records (the ``JaxBlockBackend._eval_blocked``
        fallback precedent).  Returns ``(packed result words, source
        popcount, output popcount)``."""
        wpb = self.wpb
        u2 = np.zeros((self.nblocks, wpb), dtype=np.uint32)
        u2.reshape(-1)[: n_words(self.n)] = sw
        src_count = int(popcount(sw))
        verd = self._zone_mask(grp, conj=conj, exact=True)
        all_rows = None
        all_bits = None
        if verd is not None:
            live = (u2 != 0).any(axis=1)
            maybe = verd == ZONE_MAYBE
            self.blocks_pruned += float((live & ~maybe).sum())
            all_rows = verd == ZONE_ALL
            all_bits = u2[all_rows].copy()
            u2[~maybe] = 0
        uw = u2.reshape(-1)[: n_words(self.n)]
        mask = unpack_bits(uw, self.n)
        idx = np.nonzero(mask)[0]
        acc = None
        for a in grp:
            hits = self.table.eval_atom(a, idx)
            acc = hits if acc is None else (
                (acc & hits) if conj else (acc | hits))
        out = np.zeros(self.n, dtype=bool)
        if len(idx):
            out[idx[acc]] = True
        words = pack_bits(out)
        if all_bits is not None and all_bits.size:
            o2 = np.zeros((self.nblocks, wpb), dtype=np.uint32)
            o2.reshape(-1)[: n_words(self.n)] = words
            o2[all_rows] |= all_bits
            words = o2.reshape(-1)[: n_words(self.n)].copy()
        # gather cost: post-prune records, block-granular touch count
        self.records_touched += len(idx) * len(grp)
        self.blocks_touched += live_block_count(uw, self.nblocks, wpb)
        return words, src_count, int(popcount(words))

    def _apply_host(self, atom: Atom, ds: Sequence[_DevSet],
                    union: _DevSet) -> List[_DevSet]:
        """Host-gather fallback for atoms a device kernel cannot run."""
        self.host_fallbacks += 1
        uw = self._pull_flat(union)
        words, src_count, out_count = self._host_gather([atom], True, uw)
        self._log_op((atom_key(atom),), atom.selectivity, src_count,
                     out_count)
        sat = self._from_flat(words)
        return [self._setop(sat, d, OP_AND) for d in ds]

    def _bind_atom(self, atom: Atom):
        """(column blocks, lookup mask or None) for a device-executable
        atom; (None, None) when the atom needs the host fallback."""
        if lookup_atom(atom):
            mask = self._lookup_mask(atom)
            if mask is None:
                return None, None
            return self._col_bitmajor(atom.column), mask
        if device_atom(atom):
            return self._col_bitmajor(atom.column), None
        return None, None

    def apply_atom(self, atom: Atom, d: _DevSet) -> _DevSet:
        """One atom on one device set: one kernel launch and, for a
        zone-pruned compare atom, :data:`ZONED_ATOM_BOOKKEEPING` eager
        bookkeeping ops (the cost counters, the verdict upload, the two
        feedback popcounts); a lookup atom adds its mask upload."""
        import jax.numpy as jnp
        t0 = time.perf_counter()
        col, lmask = self._bind_atom(atom)
        self._lap("kernel_host_s", t0)
        zone = self._zone_mask([atom]) if col is not None else None
        self._account([atom], d.pops, device=col is not None, zone=zone)
        if col is None:
            return self._apply_host(atom, [d], d)[0]
        zj = None if zone is None else self._eager(jnp.asarray, zone)
        lj = None if lmask is None else self._eager(jnp.asarray, lmask)
        skip = zone is not None and not (zone == ZONE_MAYBE).any()
        t0 = time.perf_counter()
        self.device_dispatches += 1
        self.kernel_launches += 1
        if lj is not None:
            out, pops = _jitted_prims()["lookup"](col, d.bits, d.pops, lj,
                                                  zone=zj, skip=skip,
                                                  pallas=self.pallas,
                                                  interpret=self.interpret)
        else:
            out, pops = _jitted_prims()["atom"](col, d.bits, d.pops,
                                                float(atom.value), zone=zj,
                                                skip=skip,
                                                opcode=_CMP_OPCODE[atom.op],
                                                pallas=self.pallas,
                                                interpret=self.interpret)
        self._lap("kernel_host_s", t0)
        self._fb_queue([atom], True, d.pops, pops)
        return _DevSet(out, pops)

    def apply_atom_multi(self, atom: Atom, ds: Sequence[_DevSet]
                         ) -> List[_DevSet]:
        """Q device record sets against one atom in one fused kernel."""
        if len(ds) == 1:
            return [self.apply_atom(atom, ds[0])]
        import jax.numpy as jnp
        bits = self._eager(jnp.stack, [d.bits for d in ds])
        pops = self._eager(jnp.stack, [d.pops for d in ds])
        # one reduce dispatch (not Q-1 setops): the union only feeds the
        # fallback path and cost accounting, mirroring the block engines'
        # uncounted host union
        t0 = time.perf_counter()
        self.device_dispatches += 1
        self.setop_launches += 1
        ubits, upops = _jitted_prims()["union"](bits, pops)
        union = _DevSet(ubits, upops)
        t0 = self._lap("setop_host_s", t0)
        col, lmask = self._bind_atom(atom)
        self._lap("kernel_host_s", t0)
        zone = self._zone_mask([atom]) if col is not None else None
        self._account([atom], union.pops, device=col is not None, zone=zone)
        if col is None:
            return self._apply_host(atom, ds, union)
        zj = None if zone is None else self._eager(jnp.asarray, zone)
        lj = None if lmask is None else self._eager(jnp.asarray, lmask)
        skip = zone is not None and not (zone == ZONE_MAYBE).any()
        t0 = time.perf_counter()
        self.device_dispatches += 1
        self.kernel_launches += 1
        if lj is not None:
            out, opops = _jitted_prims()["lookup_multi"](
                col, bits, pops, lj, zone=zj, skip=skip,
                pallas=self.pallas, interpret=self.interpret)
        else:
            out, opops = _jitted_prims()["multi"](col, bits, pops,
                                                  float(atom.value), zone=zj,
                                                  skip=skip,
                                                  opcode=_CMP_OPCODE[atom.op],
                                                  pallas=self.pallas,
                                                  interpret=self.interpret)
        self._lap("kernel_host_s", t0)
        self._fb_queue([atom], True, pops, opops)
        return self._unstack(out, opops, len(ds))

    # -- the single end-of-query (or end-of-batch) host sync -------------------
    def materialize(self, sets: Sequence[_DevSet]) -> List[np.ndarray]:
        """Fetch result bitmaps AND flush pending cost counters in one
        bundled transfer — the query/batch's single host sync."""
        return self._materialize(sets)[0]

    def _materialize(self, sets: Sequence[_DevSet], extra=None):
        """:meth:`materialize`, with ``extra`` (any pytree of device
        arrays) riding the same transfer; returns ``(bitmaps, fetched
        extra)``."""
        import jax
        import jax.numpy as jnp
        _faults.trip("device.dispatch", backend=self, where="materialize")
        if self._pend_records:
            rec = jnp.stack(self._pend_records)
            blk = jnp.stack(self._pend_blocks)
            prn = jnp.stack(self._pend_pruned)
        else:
            rec = jnp.zeros((0,), dtype=jnp.int32)
            blk = jnp.zeros((0,), dtype=jnp.int32)
            prn = jnp.zeros((0,), dtype=jnp.int32)
        # the three counter vectors
        self.bookkeeping_launches += 3
        self.host_syncs += 1
        bits, rec, blk, prn, fsrc, fout, extra = jax.device_get(
            ([d.bits for d in sets], rec, blk, prn, self._fb_src,
             self._fb_out, extra))
        rec = np.asarray(rec, dtype=np.float64)
        blk = np.asarray(blk, dtype=np.float64)
        ks = np.asarray(self._pend_k, dtype=np.float64)
        self.stats.records_evaluated += float((rec * ks).sum())
        self.stats.weighted_cost += float(
            (rec * np.asarray(self._pend_weights)).sum())
        self.blocks_touched += float((blk * ks).sum())
        self.records_touched += float((blk * ks).sum() * self.block)
        self.blocks_pruned += float(
            (np.asarray(prn, dtype=np.float64) * ks).sum())
        self._pend_records, self._pend_weights = [], []
        self._pend_k, self._pend_blocks, self._pend_pruned = [], [], []
        # resolve the queued realized-selectivity observations (stacked
        # i32[Q] entries expand to one observation per lockstep query)
        for (keys, est), s, o in zip(self._fb_meta, fsrc, fout):
            s = np.asarray(s).reshape(-1)
            o = np.asarray(o).reshape(-1)
            for sj, oj in zip(s, o):
                self._log_op(keys, est, int(sj), int(oj))
        self._fb_meta, self._fb_src, self._fb_out = [], [], []
        return [self._cut(b) for b in bits], extra

    def _host_atom_group(self, op, src: _DevSet) -> _DevSet:
        """Host fallback for a tape ATOM/CHAIN op: zone-pruned gather of
        src's records, evaluate the group's atoms on them, combine (∧/∨),
        scatter (see :meth:`_host_gather`)."""
        atoms = self.last_tape.tree.atoms
        grp = [atoms[a] for a in op.aids]
        self.host_fallbacks += 1
        self._account(grp, src.pops, device=False)
        sw = self._pull_flat(src)
        words, src_count, out_count = self._host_gather(grp, op.conj, sw)
        est = group_selectivity([a.selectivity for a in grp], op.conj)
        self._log_op(tuple(atom_key(a) for a in grp), est, src_count,
                     out_count)
        return self._from_flat(words)

    # -- whole-tape execution --------------------------------------------------
    def _tape_bindings(self, tape: PlanTape):
        """Column arrays, value vector, lookup bitmasks and per-op metadata.

        Returns (cols, values, lmasks, meta, device_ok) where meta[i] is
        (col_indices, value_indices, opcodes) for op i (empty for SETOPs)
        and device_ok[i] says the op can run on device.  A dictionary-
        membership ATOM op carries opcode :data:`IN_OPCODE` and its value
        index points into ``lmasks`` (stacked packed hit bitmasks, padded
        to a common word count) instead of ``values``.
        """
        atoms = tape.tree.atoms
        col_ix: Dict[str, int] = {}
        cols: List[object] = []
        values: List[float] = []
        lmask_rows: List[np.ndarray] = []
        meta: List[Tuple[tuple, tuple, tuple]] = []
        device_ok: List[bool] = []
        for op in tape.ops:
            if op.kind not in (ATOM, CHAIN):
                meta.append(((), (), ()))
                device_ok.append(True)
                continue
            if len(op.aids) == 1 and lookup_atom(atoms[op.aids[0]]):
                a = atoms[op.aids[0]]
                col = self._col_bitmajor(a.column)
                mask = self._lookup_mask(a)
                if col is None or mask is None:
                    meta.append(((), (), ()))
                    device_ok.append(False)
                    continue
                if a.column not in col_ix:
                    col_ix[a.column] = len(cols)
                    cols.append(col)
                meta.append(((col_ix[a.column],), (len(lmask_rows),),
                             (IN_OPCODE,)))
                lmask_rows.append(mask)
                device_ok.append(True)
                continue
            ok = all(device_atom(atoms[a]) for a in op.aids)
            bound = []
            if ok:
                for a in op.aids:
                    c = self._col_bitmajor(atoms[a].column)
                    if c is None:
                        ok = False
                        break
                    bound.append(atoms[a].column)
            if not ok:
                meta.append(((), (), ()))
                device_ok.append(False)
                continue
            cixs, vixs, opcodes = [], [], []
            for a, name in zip(op.aids, bound):
                if name not in col_ix:
                    col_ix[name] = len(cols)
                    cols.append(self._col_bitmajor(name))
                cixs.append(col_ix[name])
                vixs.append(len(values))
                values.append(float(atoms[a].value))
                opcodes.append(_CMP_OPCODE[atoms[a].op])
            meta.append((tuple(cixs), tuple(vixs), tuple(opcodes)))
            device_ok.append(True)
        if lmask_rows:
            u = max(len(m) for m in lmask_rows)
            lmasks = np.zeros((len(lmask_rows), u), dtype=np.uint32)
            for j, m in enumerate(lmask_rows):
                lmasks[j, : len(m)] = m
        else:
            lmasks = np.zeros((0, 1), dtype=np.uint32)
        return cols, values, lmasks, meta, device_ok

    def _tape_zone_masks(self, tape: PlanTape):
        """Stacked per-op zone-verdict rows ``i32[M, nblocks]`` for the M
        costed (ATOM/CHAIN) ops of ``tape``, or None with pruning disabled.

        These are *runtime inputs* to the compiled program: M and the row
        shape are fixed by the tape structure and the block bucket, while
        the verdict VALUES are data — appends that extend the zone maps, or
        cache-hit tapes with drifted constants, feed new rows through the
        same jitted program without retracing.  Ops nothing prunes get an
        all-MAYBE row (the blend then reduces to the unpruned evaluation).

        Returns ``(zmasks, any_decided)`` — ``any_decided`` says some op's
        mask has no MAYBE block at all, which selects the lax.cond "skip"
        flavor of the program (see :func:`_zone_apply`); with pruning
        disabled returns ``(None, False)``.
        """
        if self._zones is None:
            return None, False
        atoms = tape.tree.atoms
        rows = []
        any_decided = False
        for op in tape.costed_ops():
            z = self._zone_mask([atoms[a] for a in op.aids], conj=op.conj)
            if z is None:
                z = np.full(self.nblocks, ZONE_MAYBE, np.int32)
            elif not (z == ZONE_MAYBE).any():
                any_decided = True
            rows.append(z)
        if not rows:
            return self._eager(self._place, np.zeros((0, self.nblocks),
                                                     dtype=np.int32),
                               "zmask"), False
        return self._eager(self._place, np.stack(rows).astype(np.int32),
                           "zmask"), any_decided

    def _tape_program(self, tape: PlanTape, meta, skip: bool = False):
        """Build (or fetch) the jitted whole-tape program for ``tape``.

        The pruning *mechanism* (whether a zone-mask input exists, and
        whether evaluations sit under the lax.cond runtime skip) is a
        static part of the program — it changes the traced graph — but the
        masks themselves are runtime arrays: a program compiled once serves
        every zone-map state of every key-equal tape.  At most two flavors
        per tape exist (skip on/off), chosen host-side from the mask data;
        appends never retrace either.
        """
        import jax
        prune = self._zones is not None
        key = (tape.key, self.pallas, self.interpret, prune, skip)
        prog = _TAPE_PROGRAMS.get(key)
        if prog is not None:
            _TAPE_PROGRAMS.move_to_end(key)
            return prog
        ops = tape.ops
        result = tape.result
        n_slots = tape.n_slots
        pallas, interpret = self.pallas, self.interpret

        def program(cols, values, lmasks, zmasks, full_bits, full_pops):
            return _tape_forward(ops, meta, result, n_slots, prune, skip,
                                 pallas, interpret, cols, values, lmasks,
                                 zmasks, full_bits, full_pops)

        prog = jax.jit(program)
        _TAPE_PROGRAMS[key] = prog
        if len(_TAPE_PROGRAMS) > _TAPE_PROGRAM_CAP:
            _TAPE_PROGRAMS.popitem(last=False)
        return prog

    def run_tape(self, tape: PlanTape) -> np.ndarray:
        """Execute one compiled tape; returns the host packed result
        bitmap.  Its program compiles on the call if it must, so an
        all-device tape is one dispatch and one host sync
        (``run_query``)."""
        return self._run_tapes([tape], tiered=False, tracer=None)[0]

    def run_tapes(self, tapes: Sequence[PlanTape], tracer=None
                  ) -> List[np.ndarray]:
        """Execute a session's batch of compiled tapes; returns their host
        packed result bitmaps, in order.

        Every all-device tape — dictionary-rewritten string atoms included
        — whose program is compiled is launched as ONE jitted program
        first; then ONE host sync (``device_get``) brings back every
        result with its counter vectors, so a batch of tapes pays one
        sync, not one per tape.  Results come back bucket-padded and are
        cut to the row count on the host, so no device op takes its shape
        from the row count.

        Tiered: a tape whose program is not compiled yet for its shapes
        runs op by op over device slots while :data:`_COMPILER` compiles
        the program on its own thread (in a ``tape.compile`` span of
        ``tracer``), so no batch waits on the compiler and the tape's next
        run is one dispatch; :attr:`cold_tapes` counts these runs.  Tapes
        with host-fallback ops (opaque UDF atoms, unrewritten non-numeric
        columns) run op by op too, syncing at each fallback.  An op-by-op
        run's result and queued counters ride the same bundled transfer.
        The bundled sync is in no dispatch counter's host seconds.
        """
        return self._run_tapes(tapes, tiered=True, tracer=tracer)

    def _run_tapes(self, tapes: Sequence[PlanTape], tiered: bool, tracer
                   ) -> List[np.ndarray]:
        import jax
        out: List[Optional[np.ndarray]] = [None] * len(tapes)
        progs = []       # (index, tape, program outputs)
        sets = []        # (index, device set of an op-by-op run)
        for i, tape in enumerate(tapes):
            _faults.trip("device.dispatch", backend=self, where="run_tape")
            dev = self._launch_tape(tape, tiered, tracer)
            if isinstance(dev, _DevSet):
                sets.append((i, dev))
            else:
                progs.append((i, tape, dev))
        fetched = []
        if sets:
            bitmaps, fetched = self._materialize(
                [d for _, d in sets], [dev for _, _, dev in progs])
            for (i, _), b in zip(sets, bitmaps):
                out[i] = b
        elif progs:
            self.host_syncs += 1
            fetched = jax.device_get([dev for _, _, dev in progs])
        for (i, tape, _), (res, rec, blk, prn, outs) in zip(progs, fetched):
            self._account_tape(tape, rec, blk, prn, outs)
            out[i] = self._cut(res)
        return out

    def _launch_tape(self, tape: PlanTape, tiered: bool, tracer):
        """Bind and launch a tape: an all-device tape's compiled program
        returns its device outputs ``(result bits, rec, blk, prn, outs)``;
        an op-by-op run (host-fallback ops, or a ``tiered`` tape whose
        program is still compiling) returns its result set.  Nothing
        syncs here but a host fallback."""
        import jax.numpy as jnp
        self.last_tape = tape
        t0 = time.perf_counter()
        cols, values, lmasks, meta, device_ok = self._tape_bindings(tape)
        self._lap("kernel_host_s", t0)
        if not all(device_ok):
            return self._run_tape_ops(tape, lmasks, meta, device_ok)
        full = self.full()
        zmasks, any_decided = self._tape_zone_masks(tape)
        vj = self._eager(jnp.asarray, values, dtype=jnp.float32)
        lj = self._eager(jnp.asarray, lmasks)
        prog = self._tape_program(tape, tuple(meta), skip=any_decided)
        args = (tuple(cols), vj, lj, zmasks, full.bits, full.pops)
        if tiered and not _COMPILER.ready(prog, args, tracer):
            self.cold_tapes += 1
            return self._run_tape_ops(tape, lmasks, meta, device_ok)
        self.stats.atom_applications += sum(len(op.aids)
                                            for op in tape.costed_ops())
        self.stats.setops += sum(1 for op in tape.ops if op.kind == SETOP)
        t0 = time.perf_counter()
        self.device_dispatches += 1
        self.kernel_launches += 1
        dev = prog(*args)
        self._lap("kernel_host_s", t0)
        return dev

    def _account_tape(self, tape: PlanTape, rec, blk, prn, outs) -> None:
        """Fold one tape's fetched per-costed-op counters into the cost
        stats and the feedback log.  A K-atom CHAIN evaluates K atoms on
        all of src's live blocks: counts scale by K, matching the fused
        +evaluations trade."""
        atoms = tape.tree.atoms
        costed = tape.costed_ops()
        ks = np.asarray([len(op.aids) for op in costed], dtype=np.float64)
        rec = np.asarray(rec, dtype=np.float64)
        weights = np.asarray([sum(atoms[a].cost_factor for a in op.aids)
                              for op in costed])
        self.stats.records_evaluated += float((rec * ks).sum())
        self.stats.weighted_cost += float((rec * weights).sum())
        blk_total = float((np.asarray(blk, dtype=np.float64) * ks).sum())
        self.blocks_touched += blk_total
        self.records_touched += blk_total * self.block
        self.blocks_pruned += float(
            (np.asarray(prn, dtype=np.float64) * ks).sum())
        # per-op realized selectivities rode the same device_get:
        # (keys, est) metadata is tape-order aligned with rec/outs
        for (opm, keys, est), s, o in zip(op_observation_meta(tape),
                                          rec, np.asarray(outs)):
            self._log_op(keys, est, int(s), int(o))

    def _run_tape_ops(self, tape: PlanTape, lmasks, meta, device_ok
                      ) -> _DevSet:
        """Op-by-op tape execution over device slots, host fallbacks
        interleaved: the jitted per-op prims the lockstep executor uses,
        with their cost counters queued for the next bundled transfer.
        Returns the result set, unsynced."""
        import jax.numpy as jnp
        prims = _jitted_prims()
        slots: List[Optional[_DevSet]] = [None] * tape.n_slots
        atoms = tape.tree.atoms
        for oi, op in enumerate(tape.ops):
            if op.kind == FULL:
                s = self.full()
            elif op.kind == EMPTY:
                s = self.empty()
            elif op.kind == SETOP:
                s = self._setop(slots[op.a], slots[op.b], op.setop)
            else:
                src = slots[op.a]
                cixs, vixs, opcodes = meta[oi]
                if not device_ok[oi]:
                    s = self._host_atom_group(op, src)
                else:
                    grp = [atoms[a] for a in op.aids]
                    zone = self._zone_mask(grp, conj=op.conj)
                    self._account(grp, src.pops, zone=zone)
                    zj = (None if zone is None
                          else self._eager(jnp.asarray, zone))
                    skip = (zone is not None
                            and not (zone == ZONE_MAYBE).any())
                    t0 = time.perf_counter()
                    cols = [self._col_bitmajor(atoms[a].column)
                            for a in op.aids]
                    self._lap("kernel_host_s", t0)
                    if opcodes[0] == IN_OPCODE:
                        args = (cols[0], src.bits, src.pops,
                                self._eager(jnp.asarray, lmasks[vixs[0]]))
                    elif op.kind == ATOM:
                        args = (cols[0], src.bits, src.pops,
                                float(atoms[op.aids[0]].value))
                    else:
                        args = (self._eager(jnp.stack, cols, axis=1),
                                src.bits, src.pops,
                                self._eager(jnp.asarray,
                                            [float(atoms[a].value)
                                             for a in op.aids],
                                            dtype=jnp.float32))
                    t0 = time.perf_counter()
                    self.device_dispatches += 1
                    self.kernel_launches += 1
                    if opcodes[0] == IN_OPCODE:
                        out, pops = prims["lookup"](
                            *args, zone=zj, skip=skip, pallas=self.pallas,
                            interpret=self.interpret)
                    elif op.kind == ATOM:
                        out, pops = prims["atom"](
                            *args, zone=zj, skip=skip, opcode=opcodes[0],
                            pallas=self.pallas, interpret=self.interpret)
                    else:
                        out, pops = prims["chain"](
                            *args, zone=zj, skip=skip, opcodes=opcodes,
                            conj=op.conj, pallas=self.pallas,
                            interpret=self.interpret)
                    self._lap("kernel_host_s", t0)
                    self._fb_queue(grp, op.conj, src.pops, pops)
                    s = _DevSet(out, pops)
            slots[op.dst] = s
        return slots[tape.result]
