"""Pallas TPU kernel: fused dictionary-membership lookup over code blocks.

The dictionary rewrite (``core.predicate.codes_expression``) turns most
string predicates into a handful of numeric comparisons over int32
dictionary codes — but a hit set fragmented into many runs (regex-shaped
LIKE, scattered IN, arbitrary masks) has no compact comparison form.  This
kernel closes that gap on device: the hit set uploads as a packed
``u32[U]`` bitmask over code space (bit ``c`` set iff dictionary value
``c`` satisfies the predicate), each record's code is read from the same
bit-major f32 column blocks every other kernel uses, and membership is one
bit test — so EVERY non-UDF string predicate executes inside the one-sync
whole-tape program.

Bit-test without a vector gather: TPU VMEM gathers with per-element
indices are the wrong shape for a tiny mask, so the kernel iterates the
``U`` mask words (static, typically 1-2 for real vocabularies — the mask
is scalar-prefetched into SMEM) and selects the word each code addresses
with a lane-aligned compare.  Cost is O(U) vector ops per block, dead
blocks skip via the prefetched popcounts exactly like ``predicate_scan``.

Validated against ``ref.dict_lookup_ref`` in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref
from .predicate_scan import query_chunks, row_spec


def _lookup_kernel(pop_ref, mask_ref, col_ref, bits_ref, out_ref, *,
                   n_mask_words: int):
    i = pl.program_id(0)

    @pl.when(pop_ref[i] > 0)
    def _live():
        col = col_ref[0]                    # (32, W) f32 codes — bit-major
        bits = bits_ref[...]                # (1, W) u32 packed D_i
        w = col.shape[1]
        bitpos = jax.lax.broadcasted_iota(jnp.uint32, (32, w), 0)
        in_set = ((bits >> bitpos) & jnp.uint32(1)).astype(jnp.bool_)
        codes = col.astype(jnp.int32)
        word_ix = codes >> 5
        code_bit = (codes & 31).astype(jnp.uint32)
        hit = jnp.zeros(col.shape, dtype=jnp.bool_)
        for u in range(n_mask_words):
            word = mask_ref[u]              # scalar u32 from SMEM
            sel = word_ix == u
            b = ((word >> code_bit) & jnp.uint32(1)).astype(jnp.bool_)
            hit = jnp.logical_or(hit, jnp.logical_and(sel, b))
        out_ref[...] = ref.pack_bitmajor(jnp.logical_and(hit, in_set))

    @pl.when(pop_ref[i] == 0)
    def _dead():
        out_ref[...] = jnp.zeros_like(out_ref)


def dict_lookup_scan(col_bitmajor: jnp.ndarray, bits: jnp.ndarray,
                     pops: jnp.ndarray, mask_words: jnp.ndarray,
                     interpret: bool = False) -> jnp.ndarray:
    """col_bitmajor: f32[N, 32, W] int codes; bits: u32[N, W]; pops: i32[N];
    mask_words: u32[U] packed code hit set  ->  u32[N, W] packed (D ∧ P).

    Codes at or past ``32 * U`` are misses (the mask bounds code space)."""
    n, _, w = col_bitmajor.shape
    u = mask_words.shape[0]
    kernel = functools.partial(_lookup_kernel, n_mask_words=u)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, 32, w), lambda i, pop, mask: (i, 0, 0)),
            row_spec(w, lambda i, pop, mask: (i, 0, 0)),
        ],
        out_specs=row_spec(w, lambda i, pop, mask: (i, 0, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, w), jnp.uint32),
        interpret=interpret,
    )(pops, mask_words, col_bitmajor, bits.reshape(n, 1, w)).reshape(n, w)


def dict_lookup_scan_multi(col_bitmajor: jnp.ndarray, bits: jnp.ndarray,
                           pops: jnp.ndarray, mask_words: jnp.ndarray,
                           interpret: bool = False) -> jnp.ndarray:
    """Multi-bitmap variant: Q stacked record sets share one code column.

    col_bitmajor: f32[N, 32, W];  bits: u32[Q*N, W] (query-major stacking);
    pops: i32[Q*N];  mask_words: u32[U]  ->  u32[Q*N, W].  Same index-map
    trick as ``predicate_scan_multi``: grid step ``k`` re-reads column
    block ``k % N`` against bitmap row ``k``, and the same SMEM split
    into :func:`~repro.kernels.predicate_scan.query_chunks` ranges."""
    qn, w = bits.shape
    n = col_bitmajor.shape[0]
    u = mask_words.shape[0]
    chunks = query_chunks(qn, n, reserved=u)
    if len(chunks) > 1:
        return jnp.concatenate([
            dict_lookup_scan_multi(col_bitmajor, bits[lo:hi], pops[lo:hi],
                                   mask_words, interpret=interpret)
            for lo, hi in chunks])
    kernel = functools.partial(_lookup_kernel, n_mask_words=u)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(qn,),
        in_specs=[
            pl.BlockSpec((1, 32, w), lambda k, pop, mask: (k % n, 0, 0)),
            row_spec(w, lambda k, pop, mask: (k, 0, 0)),
        ],
        out_specs=row_spec(w, lambda k, pop, mask: (k, 0, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((qn, 1, w), jnp.uint32),
        interpret=interpret,
    )(pops, mask_words, col_bitmajor, bits.reshape(qn, 1, w)).reshape(qn, w)
