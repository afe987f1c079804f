"""Pallas TPU kernel: fused packed-bitmap set ops + popcount.

The paper's set operations (∩ ∪ \\) are "fast bit flipping operations" on
packed bitmaps; on TPU they are uint32 lane ops on the VPU.  This kernel
fuses the set op with the popcount the executor needs next (for block
skipping / cost accounting), so the result bitmap is read once instead of
twice.  One grid step per block row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .predicate_scan import row_spec

AND, OR, ANDNOT = range(3)


def _bitmap_kernel(a_ref, b_ref, out_ref, pop_ref, *, opcode: int):
    a = a_ref[...]                       # (1, W) u32
    b = b_ref[...]
    if opcode == AND:
        r = a & b
    elif opcode == OR:
        r = a | b
    elif opcode == ANDNOT:
        r = a & ~b
    else:
        raise ValueError(f"bad opcode {opcode}")
    out_ref[...] = r
    w = r.shape[1]
    bitpos = jax.lax.broadcasted_iota(jnp.uint32, (32, w), 0)
    ones = ((r >> bitpos) & jnp.uint32(1)).astype(jnp.int32)
    pop_ref[...] = ones.sum(dtype=jnp.int32).reshape(1, 1)


def bitmap_setop(a: jnp.ndarray, b: jnp.ndarray, opcode: int,
                 interpret: bool = False):
    """a, b: u32[N, W] -> (u32[N, W] result, i32[N, 1] per-row popcounts)."""
    n, w = a.shape
    kernel = functools.partial(_bitmap_kernel, opcode=opcode)
    out, pops = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            row_spec(w, lambda i: (i, 0, 0)),
            row_spec(w, lambda i: (i, 0, 0)),
        ],
        out_specs=[
            row_spec(w, lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1, w), jnp.uint32),
            jax.ShapeDtypeStruct((n, 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(a.reshape(n, 1, w), b.reshape(n, 1, w))
    return out.reshape(n, w), pops.reshape(n, 1)
