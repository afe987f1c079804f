"""Pure-jnp oracles for the Pallas kernels.

Layout convention (shared with columnar.bitmap / numpy packbits
``bitorder="little"``): record ``r`` of a block lives in word ``r // 32``,
bit ``r % 32``.  All functions are shape-polymorphic over a leading batch
(blocks) axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# opcode table shared with the executors and the Pallas kernels
LT, LE, GT, GE, EQ, NE = range(6)


def unpack_u32(words: jnp.ndarray) -> jnp.ndarray:
    """uint32[..., W] -> bool[..., W*32] (record-major)."""
    bitpos = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., :, None] >> bitpos) & jnp.uint32(1)
    return bits.reshape(*words.shape[:-1], -1).astype(bool)


def pack_u32(mask: jnp.ndarray) -> jnp.ndarray:
    """bool[..., B] -> uint32[..., B//32]."""
    b = mask.shape[-1]
    assert b % 32 == 0, "block must be a multiple of 32 records"
    m = mask.reshape(*mask.shape[:-1], b // 32, 32).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return (m * weights).sum(axis=-1, dtype=jnp.uint32)


def pack_bitmajor(keep: jnp.ndarray) -> jnp.ndarray:
    """bool[32, W] bit-major mask -> u32[1, W] packed words, inside a
    Pallas kernel.  Mosaic has no unsigned reductions, so the sum runs in
    int32 and is bitcast back: the 32 shifted bits of a word are disjoint,
    so their sum is their OR (bit 31 lands exactly on the sign bit)."""
    bitpos = jax.lax.broadcasted_iota(jnp.int32, keep.shape, 0)
    words = (keep.astype(jnp.int32) << bitpos).sum(axis=0, keepdims=True)
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


def compare(col: jnp.ndarray, value, opcode: int) -> jnp.ndarray:
    if opcode == LT:
        return col < value
    if opcode == LE:
        return col <= value
    if opcode == GT:
        return col > value
    if opcode == GE:
        return col >= value
    if opcode == EQ:
        return col == value
    if opcode == NE:
        return col != value
    raise ValueError(f"bad opcode {opcode}")


def predicate_blocks_ref(col: jnp.ndarray, bits_in: jnp.ndarray, value,
                         opcode: int) -> jnp.ndarray:
    """Fused (col OP value) ∧ bits_in over blocked columns.

    col:     f32[N, B]   column values, one row per block
    bits_in: u32[N, W]   packed record bitmap (W = B // 32)
    returns  u32[N, W]   packed (D ∧ P) bitmap
    """
    keep = compare(col, value, opcode) & unpack_u32(bits_in)
    return pack_u32(keep)


def predicate_blocks_multi_ref(col: jnp.ndarray, bits_in: jnp.ndarray, value,
                               opcode: int) -> jnp.ndarray:
    """Multi-bitmap variant of :func:`predicate_blocks_ref`: the comparison
    is computed once per block and masked against Q stacked record sets.

    col:     f32[N, B]      column values, one row per block
    bits_in: u32[Q, N, W]   Q packed record bitmaps (W = B // 32)
    returns  u32[Q, N, W]   packed (D_q ∧ P) bitmaps
    """
    keep = compare(col, value, opcode)[None] & unpack_u32(bits_in)
    return pack_u32(keep)


def code_hits(codes: jnp.ndarray, mask_words: jnp.ndarray) -> jnp.ndarray:
    """Membership of integer ``codes`` (any shape) in a packed hit set.

    ``mask_words`` is u32[U] with bit ``c`` set iff dictionary value ``c``
    satisfies the predicate; codes outside [0, 32*U) are misses.  The one
    definition of the packed-bitmask test — the device backend's jnp
    fallbacks call it too, so it cannot diverge from this oracle (the
    Pallas kernel necessarily re-expresses it as a mask-word loop and is
    tested against this).
    """
    u = mask_words.shape[0]
    word = mask_words[jnp.clip(codes >> 5, 0, u - 1)]
    hit = ((word >> (codes & 31).astype(jnp.uint32))
           & jnp.uint32(1)).astype(bool)
    return hit & (codes >= 0) & (codes < 32 * u)


def dict_lookup_ref(col: jnp.ndarray, bits_in: jnp.ndarray,
                    mask_words: jnp.ndarray) -> jnp.ndarray:
    """Fused dictionary-membership test ∧ bits_in over blocked code columns.

    col:        f32[N, B]   int dictionary codes stored as f32 blocks
    bits_in:    u32[N, W]   packed record bitmap (W = B // 32)
    mask_words: u32[U]      packed hit set over code space
    returns     u32[N, W]   packed (D ∧ P) bitmap
    """
    hit = code_hits(col.astype(jnp.int32), mask_words)
    return pack_u32(hit & unpack_u32(bits_in))


def bitmap_and_ref(a, b):
    return a & b


def bitmap_or_ref(a, b):
    return a | b


def bitmap_andnot_ref(a, b):
    return a & ~b


def popcount_ref(words: jnp.ndarray) -> jnp.ndarray:
    """uint32[...] -> int32 total popcount over the last axis."""
    return unpack_u32(words).sum(axis=-1, dtype=jnp.int32)


def fused_chain_ref(cols: jnp.ndarray, bits_in: jnp.ndarray,
                    values: jnp.ndarray, opcodes, conj: bool = True) -> jnp.ndarray:
    """Multi-atom chain fused on the same record blocks (AND or OR combine).

    cols:    f32[K, N, B]  K columns, blocked
    bits_in: u32[N, W]
    values:  f32[K]
    opcodes: static tuple of K opcodes
    """
    acc = None
    for k, op in enumerate(opcodes):
        c = compare(cols[k], values[k], op)
        acc = c if acc is None else (acc & c if conj else acc | c)
    return pack_u32(acc & unpack_u32(bits_in))
