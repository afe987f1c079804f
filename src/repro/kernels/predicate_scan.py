"""Pallas TPU kernel: fused masked predicate application over column blocks.

This is the paper's hot loop — "apply predicate atom P to record set D" —
adapted to the TPU memory hierarchy (DESIGN §3):

* the column is blocked into ``B = 32 * W`` records; each grid step loads one
  block as a (32, W) f32 tile into VMEM (bit-position major, so the packed
  bitmap broadcast is a lane-aligned shift, no transposes in-kernel);
* the current record set D_i rides along as one (1, W) packed uint32 row:
  ``u32[N, W]`` bitmaps are viewed as ``u32[N, 1, W]`` at the pallas_call
  boundary (a metadata-only reshape), because Mosaic requires a block's
  last two dimensions to be (8, 128)-divisible or whole;
* per-block popcounts of D_i are scalar-prefetched; ``pl.when`` skips the
  load/compute of dead blocks entirely — the TPU-native replacement for the
  paper's per-record short-circuit (cost becomes #live-blocks × B, exactly
  the BlockCostModel);
* compare ∧ mask ∧ repack happen in registers; only W packed words per block
  return to HBM.

Validated against ``ref.predicate_blocks_ref`` in interpret mode (tests
sweep shapes, opcodes and dtypes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref


def _predicate_kernel(pop_ref, val_ref, col_ref, bits_ref, out_ref, *,
                      opcode: int):
    i = pl.program_id(0)

    @pl.when(pop_ref[i] > 0)
    def _live():
        col = col_ref[0]                    # (32, W) f32 — bit-major layout
        bits = bits_ref[...]                # (1, W) u32 packed D_i
        w = col.shape[1]
        bitpos = jax.lax.broadcasted_iota(jnp.uint32, (32, w), 0)
        in_set = ((bits >> bitpos) & jnp.uint32(1)).astype(jnp.bool_)
        cmp = ref.compare(col, val_ref[0], opcode)
        out_ref[...] = ref.pack_bitmajor(jnp.logical_and(cmp, in_set))

    @pl.when(pop_ref[i] == 0)
    def _dead():
        out_ref[...] = jnp.zeros_like(out_ref)


def row_spec(w: int, index_map) -> pl.BlockSpec:
    """One packed bitmap row per grid step, over a ``u32[N, 1, W]`` view
    of a ``u32[N, W]`` bitmap; the kernel sees a (1, W) ref."""
    return pl.BlockSpec((None, 1, w), index_map)


#: Scalar-prefetch budget, in 32-bit words, of one ``*_multi`` kernel call.
#: Its ``Q * N`` popcounts (and a lookup's mask words) live in SMEM, 1 MiB
#: on v5e and shared with Mosaic's own scalars: 64 queries x 1024 blocks
#: compile for v5e, 256 x 1024 run out of SMEM.
MAX_PREFETCH_WORDS = 1 << 16


def query_chunks(qn: int, n: int, reserved: int = 0) -> list:
    """``[lo, hi)`` ranges splitting ``qn`` query-major stacked rows of
    ``n`` blocks into whole queries whose popcounts, plus ``reserved``
    other prefetched words, fit :data:`MAX_PREFETCH_WORDS` (at least one
    query per range)."""
    step = max(1, (MAX_PREFETCH_WORDS - reserved) // n) * n
    return [(lo, min(lo + step, qn)) for lo in range(0, qn, step)]


def predicate_scan(col_bitmajor: jnp.ndarray, bits: jnp.ndarray,
                   pops: jnp.ndarray, value: jnp.ndarray, opcode: int,
                   interpret: bool = False) -> jnp.ndarray:
    """col_bitmajor: f32[N, 32, W]; bits: u32[N, W]; pops: i32[N];
    value: f32[1]  ->  u32[N, W] packed (D ∧ P)."""
    n, _, w = col_bitmajor.shape
    kernel = functools.partial(_predicate_kernel, opcode=opcode)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, 32, w), lambda i, pop, val: (i, 0, 0)),
            row_spec(w, lambda i, pop, val: (i, 0, 0)),
        ],
        out_specs=row_spec(w, lambda i, pop, val: (i, 0, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, w), jnp.uint32),
        interpret=interpret,
    )(pops, value, col_bitmajor, bits.reshape(n, 1, w)).reshape(n, w)


def predicate_scan_multi(col_bitmajor: jnp.ndarray, bits: jnp.ndarray,
                         pops: jnp.ndarray, value: jnp.ndarray, opcode: int,
                         interpret: bool = False) -> jnp.ndarray:
    """Multi-bitmap variant: Q stacked record sets share one column copy.

    col_bitmajor: f32[N, 32, W];  bits: u32[Q*N, W] (query-major stacking);
    pops: i32[Q*N]  ->  u32[Q*N, W].  One pallas_call over a (Q*N,) grid:
    grid step ``k`` loads column block ``k % N`` (the index map re-reads the
    same column tile for every query) against bitmap row ``k``, so a group
    of queries needing the same atom costs one kernel invocation, with dead
    (query, block) pairs still skipped via the prefetched popcounts.
    A stack whose popcounts overflow the SMEM budget runs as one call per
    :func:`query_chunks` range.
    """
    qn, w = bits.shape
    n = col_bitmajor.shape[0]
    chunks = query_chunks(qn, n)
    if len(chunks) > 1:
        return jnp.concatenate([
            predicate_scan_multi(col_bitmajor, bits[lo:hi], pops[lo:hi],
                                 value, opcode, interpret=interpret)
            for lo, hi in chunks])
    kernel = functools.partial(_predicate_kernel, opcode=opcode)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(qn,),
        in_specs=[
            pl.BlockSpec((1, 32, w), lambda k, pop, val: (k % n, 0, 0)),
            row_spec(w, lambda k, pop, val: (k, 0, 0)),
        ],
        out_specs=row_spec(w, lambda k, pop, val: (k, 0, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((qn, 1, w), jnp.uint32),
        interpret=interpret,
    )(pops, value, col_bitmajor, bits.reshape(qn, 1, w)).reshape(qn, w)
