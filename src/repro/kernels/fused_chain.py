"""Pallas TPU kernel: fused multi-atom predicate chain over column blocks.

The §Perf P2 engine iteration measured that evaluating an AND/OR group of
cheap comparisons in ONE pass (single bitmap round-trip, no re-gather)
trades +evaluations for -passes.  On TPU the trade is better than on CPU:
all K columns of a block are resident in VMEM together and the combine
happens in registers — K atoms cost one HBM round-trip instead of K.

cols: f32[N, K, 32, W] (bit-major like predicate_scan); bits: u32[N, W];
values: f32[K]; opcodes/conj static.  Dead blocks skip via scalar-prefetch
popcounts (pl.when).  Validated against ref.fused_chain_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref
from .predicate_scan import row_spec


def _chain_kernel(pop_ref, val_ref, cols_ref, bits_ref, out_ref, *,
                  opcodes, conj: bool):
    i = pl.program_id(0)

    @pl.when(pop_ref[i] > 0)
    def _live():
        bits = bits_ref[...]                 # (1, W)
        w = bits.shape[1]
        bitpos = jax.lax.broadcasted_iota(jnp.uint32, (32, w), 0)
        in_set = ((bits >> bitpos) & jnp.uint32(1)).astype(jnp.bool_)
        acc = None
        for k, op in enumerate(opcodes):
            col = cols_ref[0, k]             # (32, W)
            cmp = ref.compare(col, val_ref[k], op)
            acc = cmp if acc is None else (
                jnp.logical_and(acc, cmp) if conj
                else jnp.logical_or(acc, cmp))
        out_ref[...] = ref.pack_bitmajor(jnp.logical_and(acc, in_set))

    @pl.when(pop_ref[i] == 0)
    def _dead():
        out_ref[...] = jnp.zeros_like(out_ref)


def fused_chain_scan(cols_bitmajor: jnp.ndarray, bits: jnp.ndarray,
                     pops: jnp.ndarray, values: jnp.ndarray,
                     opcodes, conj: bool = True,
                     interpret: bool = False) -> jnp.ndarray:
    """cols_bitmajor: f32[N, K, 32, W]; bits: u32[N, W]; pops: i32[N];
    values: f32[K] -> u32[N, W]."""
    n, k, _, w = cols_bitmajor.shape
    assert len(opcodes) == k
    kernel = functools.partial(_chain_kernel, opcodes=tuple(opcodes),
                               conj=conj)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, k, 32, w), lambda i, pop, val: (i, 0, 0, 0)),
            row_spec(w, lambda i, pop, val: (i, 0, 0)),
        ],
        out_specs=row_spec(w, lambda i, pop, val: (i, 0, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, w), jnp.uint32),
        interpret=interpret,
    )(pops, values, cols_bitmajor, bits.reshape(n, 1, w)).reshape(n, w)
