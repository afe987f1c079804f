"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e.

Interpret mode (``tests/test_kernels.py``) checks results but none of
Mosaic's rules: block tiling, supported reductions, scalar memory.  Here
the TPU compiler that ships with ``libtpu`` compiles each kernel for a
*described* ``v5e:2x2`` topology (no chip needed) at the deployment's
widths — 1024 blocks of 8192 rows (W = 256 words), 16 stacked queries for
the ``*_multi`` kernels, and 256 stacked queries, whose popcounts overflow
the scalar memory one call may use — plus one whole-tape program of a
16-atom query with the Pallas kernels inside.  Each compiled program must
hold a ``tpu_custom_call``, i.e. the kernel was lowered, not interpreted.
The sharded whole-tape program (``ExecConfig(shards=4)``) compiles against
a mesh of the topology's four chips.

The topology is described inside a module-scoped fixture, never at import
(only one process may load libtpu at a time),
and the persistent compilation cache is off around these compiles: an
executable for a described chip cannot be read back without one.
"""
import os

import numpy as np
import pytest

N, W, Q, U = 1024, 256, 16, 2


@pytest.fixture(scope="module")
def v5e_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _compile_text(fn, *shapes):
    import jax
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding, shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


KERNELS = ("predicate_scan", "predicate_scan_multi", "fused_chain_scan",
           "dict_lookup_scan", "dict_lookup_scan_multi", "bitmap_setop")


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name):
    import jax.numpy as jnp
    from repro.kernels.bitmap_ops import ANDNOT, bitmap_setop
    from repro.kernels.dict_lookup import (dict_lookup_scan,
                                           dict_lookup_scan_multi)
    from repro.kernels.fused_chain import fused_chain_scan
    from repro.kernels.predicate_scan import (predicate_scan,
                                              predicate_scan_multi)

    def s(shape, dtype):
        return _spec(one_chip, shape, dtype)

    col = s((N, 32, W), jnp.float32)
    bits, pops = s((N, W), jnp.uint32), s((N,), jnp.int32)
    qbits, qpops = s((Q * N, W), jnp.uint32), s((Q * N,), jnp.int32)
    val, mask = s((1,), jnp.float32), s((U,), jnp.uint32)
    cases = {
        "predicate_scan": (
            lambda c, b, p, v: predicate_scan(c, b, p, v, 0),
            (col, bits, pops, val)),
        "predicate_scan_multi": (
            lambda c, b, p, v: predicate_scan_multi(c, b, p, v, 3),
            (col, qbits, qpops, val)),
        "fused_chain_scan": (
            lambda c, b, p, v: fused_chain_scan(c, b, p, v, (0, 3, 4),
                                                conj=False),
            (s((N, 3, 32, W), jnp.float32), bits, pops,
             s((3,), jnp.float32))),
        "dict_lookup_scan": (dict_lookup_scan, (col, bits, pops, mask)),
        "dict_lookup_scan_multi": (dict_lookup_scan_multi,
                                   (col, qbits, qpops, mask)),
        "bitmap_setop": (lambda a, b: bitmap_setop(a, b, ANDNOT),
                         (bits, bits)),
    }
    fn, shapes = cases[name]
    assert "tpu_custom_call" in _compile_text(fn, *shapes)


@pytest.mark.parametrize("name", ("predicate_scan_multi",
                                  "dict_lookup_scan_multi"))
def test_multi_kernel_past_smem_budget_compiles_for_v5e(one_chip, name):
    """256 queries x 1024 blocks: 1 MiB of popcounts, more than the SMEM
    one kernel call can prefetch.  The stack runs as one kernel call per
    ``query_chunks`` range, each of which fits."""
    import jax.numpy as jnp
    from repro.kernels.dict_lookup import dict_lookup_scan_multi
    from repro.kernels.predicate_scan import (predicate_scan_multi,
                                              query_chunks)
    q = 256
    fn, last, reserved = {
        "predicate_scan_multi": (
            lambda c, b, p, v: predicate_scan_multi(c, b, p, v, 3),
            _spec(one_chip, (1,), jnp.float32), 0),
        "dict_lookup_scan_multi": (
            dict_lookup_scan_multi, _spec(one_chip, (U,), jnp.uint32), U),
    }[name]
    text = _compile_text(
        fn, _spec(one_chip, (N, 32, W), jnp.float32),
        _spec(one_chip, (q * N, W), jnp.uint32),
        _spec(one_chip, (q * N,), jnp.int32), last)
    chunks = query_chunks(q * N, N, reserved)
    assert len(chunks) > 1
    assert text.count("tpu_custom_call") >= len(chunks)


def _deepfish_tape(table):
    """A 16-atom deepfish tape over ``table``.  A tape and its bindings'
    metadata do not depend on the table's size, so a small table serves a
    compile at the deployment's block count."""
    from repro.columnar import random_tree
    from repro.core import deepfish
    from repro.core.cost import PerAtomCostModel
    from repro.core.tape import compile_tape

    tree = random_tree(table, 16, 3, np.random.default_rng(5))
    plan = deepfish(tree, PerAtomCostModel(),
                    total_records=table.n_records)
    return compile_tape(plan)


def test_whole_tape_program_compiles_for_v5e(one_chip, forest):
    """A 16-atom deepfish tape with ``pallas=True`` at 1024 blocks: the
    program ``run_query(engine="tape-pallas")`` dispatches on the chip."""
    import jax.numpy as jnp
    from repro.columnar import DeviceTapeBackend
    from repro.columnar.device import _tape_forward

    tape = _deepfish_tape(forest)
    be = DeviceTapeBackend(forest, kernels="pallas")
    cols, values, lmasks, meta, ok = be._tape_bindings(tape)
    assert all(ok)
    n_zone = len(tape.costed_ops())

    def program(cols, values, lmasks, zmasks, full_bits, full_pops):
        return _tape_forward(tape.ops, tuple(meta), tape.result,
                             tape.n_slots, True, False, True, False, cols,
                             values, lmasks, zmasks, full_bits, full_pops)

    def s(shape, dtype):
        return _spec(one_chip, shape, dtype)

    text = _compile_text(
        program,
        tuple(s((N, 32, W), jnp.float32) for _ in cols),
        s((len(values),), jnp.float32), s(lmasks.shape, jnp.uint32),
        s((n_zone, N), jnp.int32), s((N, W), jnp.uint32),
        s((N,), jnp.int32))
    assert "tpu_custom_call" in text


def test_sharded_tape_program_compiles_for_v5e_2x2(v5e_2x2, forest):
    """The ``ExecConfig(shards=4)`` program: the same 16-atom tape under
    ``shard_map`` over a ``("shards",)`` mesh of the topology's four chips,
    1024 blocks split 256 per chip, with its one result all-gather."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.columnar import DeviceTapeBackend
    from repro.columnar.shard import ShardedTapeBackend
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("shards",), devices=v5e_2x2.devices[:4])
    tape = _deepfish_tape(forest)
    # bindings come from a backend on this process's devices; the sharded
    # one only builds the program, which uploads nothing
    cols, values, lmasks, meta, ok = (
        DeviceTapeBackend(forest)._tape_bindings(tape))
    assert all(ok)
    be = ShardedTapeBackend(forest, mesh=mesh)
    assert be.shards == 4 and be._zones is not None
    prog = be._tape_program(tape, tuple(meta))

    def s(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    text = prog.lower(
        tuple(s((N, 32, W), jnp.float32, P("shards", None, None))
              for _ in cols),
        s((len(values),), jnp.float32, P()),
        s(lmasks.shape, jnp.uint32, P()),
        s((len(tape.costed_ops()), N), jnp.int32, P(None, "shards")),
        s((N, W), jnp.uint32, P("shards", None)),
        s((N,), jnp.int32, P("shards"))).compile().as_text()
    assert "all-gather" in text
