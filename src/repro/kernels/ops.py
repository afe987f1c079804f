"""Jit'd public wrappers around the Pallas kernels.

``predicate_blocks`` matches the signature of ``ref.predicate_blocks_ref``
(record-major column blocks) and handles the bit-major relayout + popcount
prefetch on the host side of the pallas_call; XLA fuses the relayout into
the surrounding graph.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .bitmap_ops import AND, ANDNOT, OR, bitmap_setop
from .dict_lookup import dict_lookup_scan, dict_lookup_scan_multi
from .fused_chain import fused_chain_scan
from .predicate_scan import predicate_scan, predicate_scan_multi


def interpret_mode() -> bool:
    """Whether the Pallas kernels run in interpret mode: exactly when the
    default JAX backend is not a TPU.  The one rule every backend uses, so
    a TPU run never silently falls into the interpreter."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("opcode", "interpret"))
def predicate_blocks(col: jnp.ndarray, bits: jnp.ndarray, value,
                     opcode: int, interpret: bool = False) -> jnp.ndarray:
    """Fused (col OP value) ∧ bits over blocked columns via the Pallas kernel.

    col:  f32[N, B] record-major blocks;  bits: u32[N, W], W = B // 32.
    """
    n, b = col.shape
    w = b // 32
    # record-major (N, B) -> bit-major (N, 32, W): record r = w*32 + b
    col_bm = col.reshape(n, w, 32).transpose(0, 2, 1)
    pops = ref.popcount_ref(bits)                    # i32[N]
    val = jnp.asarray([value], dtype=col.dtype)
    return predicate_scan(col_bm, bits, pops.astype(jnp.int32), val, opcode,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("opcode", "interpret"))
def predicate_blocks_multi(col: jnp.ndarray, bits: jnp.ndarray, value,
                           opcode: int, interpret: bool = False) -> jnp.ndarray:
    """Multi-bitmap ``predicate_blocks``: Q queries' live-block bitmaps
    stacked into one fused kernel invocation against a single column copy.

    col:  f32[N, B] record-major blocks;  bits: u32[Q, N, W], W = B // 32.
    """
    n, b = col.shape
    q = bits.shape[0]
    w = b // 32
    col_bm = col.reshape(n, w, 32).transpose(0, 2, 1)
    bits_flat = bits.reshape(q * n, w)
    pops = ref.popcount_ref(bits_flat).astype(jnp.int32)   # i32[Q*N]
    val = jnp.asarray([value], dtype=col.dtype)
    out = predicate_scan_multi(col_bm, bits_flat, pops, val, opcode,
                               interpret=interpret)
    return out.reshape(q, n, w)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dict_lookup_blocks(col: jnp.ndarray, bits: jnp.ndarray,
                       mask_words: jnp.ndarray,
                       interpret: bool = False) -> jnp.ndarray:
    """Fused dictionary-membership lookup ∧ bits via the Pallas kernel.

    col:  f32[N, B] record-major code blocks;  bits: u32[N, W], W = B//32;
    mask_words: u32[U] packed hit set over code space.
    """
    n, b = col.shape
    w = b // 32
    col_bm = col.reshape(n, w, 32).transpose(0, 2, 1)
    pops = ref.popcount_ref(bits).astype(jnp.int32)
    return dict_lookup_scan(col_bm, bits, pops, mask_words,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("opcode", "interpret"))
def bitmap_op(a: jnp.ndarray, b: jnp.ndarray, opcode: int,
              interpret: bool = False):
    """Fused set op + per-row popcount. a, b: u32[N, W]."""
    out, pops = bitmap_setop(a, b, opcode, interpret=interpret)
    return out, pops[:, 0]


@functools.partial(jax.jit, static_argnames=("opcodes", "conj", "interpret"))
def fused_chain_blocks(cols: jnp.ndarray, bits: jnp.ndarray, values,
                       opcodes, conj: bool = True,
                       interpret: bool = False) -> jnp.ndarray:
    """Fused K-atom chain via the Pallas kernel.

    cols: f32[K, N, B] record-major; bits: u32[N, W]; values: f32[K].
    """
    k, n, b = cols.shape
    w = b // 32
    cols_bm = cols.reshape(k, n, w, 32).transpose(1, 0, 3, 2)  # (N,K,32,W)
    pops = ref.popcount_ref(bits).astype(jnp.int32)
    vals = jnp.asarray(values, dtype=cols.dtype)
    return fused_chain_scan(cols_bm, bits, pops, vals, tuple(opcodes),
                            conj=conj, interpret=interpret)
