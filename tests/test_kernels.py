"""Pallas kernel sweeps vs the pure-jnp ref oracles (interpret mode)."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.columnar.bitmap import pack_bits, popcount, unpack_bits
from repro.kernels import ops as kops
from repro.kernels import ref as kref

SHAPES = [(1, 256), (3, 1024), (4, 8192), (7, 2048)]   # (blocks, block_size)
OPS = list(range(6))


@pytest.mark.parametrize("n,b", SHAPES)
@pytest.mark.parametrize("opcode", OPS)
def test_predicate_kernel_matches_ref(n, b, opcode):
    rng = np.random.default_rng(opcode * 100 + n)
    col = rng.normal(size=(n, b)).astype(np.float32)
    bits = rng.integers(0, 2 ** 32, size=(n, b // 32), dtype=np.uint32)
    if n > 1:
        bits[1] = 0                       # dead block exercises pl.when skip
    value = float(rng.normal())
    got = np.asarray(kops.predicate_blocks(jnp.asarray(col),
                                           jnp.asarray(bits), value, opcode,
                                           interpret=True))
    want = np.asarray(kref.predicate_blocks_ref(jnp.asarray(col),
                                                jnp.asarray(bits), value,
                                                opcode))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_predicate_kernel_dtypes(dtype):
    rng = np.random.default_rng(0)
    col = (rng.normal(size=(2, 512)) * 100).astype(dtype)
    bits = rng.integers(0, 2 ** 32, size=(2, 16), dtype=np.uint32)
    got = np.asarray(kops.predicate_blocks(
        jnp.asarray(col.astype(np.float32)), jnp.asarray(bits), 3.0, 0,
        interpret=True))
    want = np.asarray(kref.predicate_blocks_ref(
        jnp.asarray(col.astype(np.float32)), jnp.asarray(bits), 3.0, 0))
    np.testing.assert_array_equal(got, want)


def test_predicate_kernel_matches_numpy_oracle():
    """Kernel vs the *numpy* column-store oracle end to end."""
    rng = np.random.default_rng(1)
    n, b = 4, 2048
    col = rng.normal(size=(n * b,)).astype(np.float32)
    mask = rng.random(n * b) < 0.6
    bits = pack_bits(mask).reshape(n, b // 32)
    got = np.asarray(kops.predicate_blocks(
        jnp.asarray(col.reshape(n, b)), jnp.asarray(bits), 0.25, 0,
        interpret=True))
    want_mask = (col < 0.25) & mask
    np.testing.assert_array_equal(unpack_bits(got.reshape(-1), n * b),
                                  want_mask)


@pytest.mark.parametrize("n,w", [(1, 8), (5, 64), (3, 256)])
@pytest.mark.parametrize("opcode", [0, 1, 2])
def test_bitmap_kernel_matches_ref(n, w, opcode):
    rng = np.random.default_rng(opcode + n)
    a = rng.integers(0, 2 ** 32, size=(n, w), dtype=np.uint32)
    b = rng.integers(0, 2 ** 32, size=(n, w), dtype=np.uint32)
    out, pops = kops.bitmap_op(jnp.asarray(a), jnp.asarray(b), opcode,
                               interpret=True)
    ref_fn = [kref.bitmap_and_ref, kref.bitmap_or_ref,
              kref.bitmap_andnot_ref][opcode]
    want = np.asarray(ref_fn(a, b))
    np.testing.assert_array_equal(np.asarray(out), want)
    np.testing.assert_array_equal(
        np.asarray(pops), np.asarray(kref.popcount_ref(jnp.asarray(want))))


def test_pack_unpack_roundtrip_jnp_vs_numpy():
    rng = np.random.default_rng(2)
    mask = rng.random(4096) < 0.37
    np_words = pack_bits(mask)
    j_words = np.asarray(kref.pack_u32(jnp.asarray(mask)))
    np.testing.assert_array_equal(np_words, j_words)
    back = np.asarray(kref.unpack_u32(jnp.asarray(np_words)))
    np.testing.assert_array_equal(back[:4096], mask)
    assert popcount(np_words) == mask.sum()


def test_fused_chain_ref():
    rng = np.random.default_rng(3)
    k, n, b = 3, 2, 512
    cols = rng.normal(size=(k, n, b)).astype(np.float32)
    bits = rng.integers(0, 2 ** 32, size=(n, b // 32), dtype=np.uint32)
    vals = rng.normal(size=(k,)).astype(np.float32)
    got = np.asarray(kref.fused_chain_ref(jnp.asarray(cols),
                                          jnp.asarray(bits),
                                          jnp.asarray(vals), (0, 2, 0),
                                          conj=True))
    m = (cols[0] < vals[0]) & (cols[1] > vals[1]) & (cols[2] < vals[2])
    want = np.asarray(kref.pack_u32(jnp.asarray(
        m & np.asarray(kref.unpack_u32(jnp.asarray(bits))))))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,w", [(2, 16), (4, 64)])
@pytest.mark.parametrize("opcode", [0, 1, 2])
def test_bitmap_setop_kernel_direct(n, w, opcode):
    """bitmap_setop itself (not the jitted wrapper): result + fused pops."""
    from repro.kernels.bitmap_ops import bitmap_setop
    rng = np.random.default_rng(10 * n + opcode)
    a = rng.integers(0, 2 ** 32, size=(n, w), dtype=np.uint32)
    b = rng.integers(0, 2 ** 32, size=(n, w), dtype=np.uint32)
    out, pops = bitmap_setop(jnp.asarray(a), jnp.asarray(b), opcode,
                             interpret=True)
    ref_fn = [kref.bitmap_and_ref, kref.bitmap_or_ref,
              kref.bitmap_andnot_ref][opcode]
    want = np.asarray(ref_fn(a, b))
    np.testing.assert_array_equal(np.asarray(out), want)
    assert pops.shape == (n, 1)
    want_pops = [popcount(row) for row in want]
    np.testing.assert_array_equal(np.asarray(pops)[:, 0], want_pops)


@pytest.mark.parametrize("n,w,k", [(2, 16, 2), (3, 8, 4)])
@pytest.mark.parametrize("conj", [True, False])
def test_fused_chain_scan_kernel_direct(n, w, k, conj):
    """fused_chain_scan itself, pre-layouted bit-major inputs + prefetch
    pops (incl. a dead block exercising the pl.when skip)."""
    from repro.kernels.fused_chain import fused_chain_scan
    rng = np.random.default_rng(n * 7 + k)
    cols_bm = rng.normal(size=(n, k, 32, w)).astype(np.float32)
    bits = rng.integers(0, 2 ** 32, size=(n, w), dtype=np.uint32)
    if n > 1:
        bits[-1] = 0
    pops = np.asarray(kref.popcount_ref(jnp.asarray(bits)), dtype=np.int32)
    vals = rng.normal(size=(k,)).astype(np.float32)
    opcodes = tuple(int(rng.integers(0, 6)) for _ in range(k))
    got = np.asarray(fused_chain_scan(
        jnp.asarray(cols_bm), jnp.asarray(bits), jnp.asarray(pops),
        jnp.asarray(vals), opcodes, conj=conj, interpret=True))
    # oracle on the same bit-major layout
    acc = None
    for i, op in enumerate(opcodes):
        cmp = np.asarray(kref.compare(jnp.asarray(cols_bm[:, i]),
                                      vals[i], op))
        acc = cmp if acc is None else (acc & cmp if conj else acc | cmp)
    bitpos = np.arange(32, dtype=np.uint32)[None, :, None]
    in_set = ((bits[:, None, :] >> bitpos) & 1).astype(bool)
    want = ((acc & in_set).astype(np.uint32) << bitpos).sum(
        axis=1, dtype=np.uint32)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,b,k", [(2, 512, 2), (3, 1024, 3), (1, 256, 4)])
@pytest.mark.parametrize("conj", [True, False])
def test_fused_chain_kernel_matches_ref(n, b, k, conj):
    rng = np.random.default_rng(n * 10 + k)
    cols = rng.normal(size=(k, n, b)).astype(np.float32)
    bits = rng.integers(0, 2 ** 32, size=(n, b // 32), dtype=np.uint32)
    if n > 1:
        bits[0] = 0                      # dead block path
    vals = rng.normal(size=(k,)).astype(np.float32)
    opcodes = tuple(int(rng.integers(0, 6)) for _ in range(k))
    got = np.asarray(kops.fused_chain_blocks(
        jnp.asarray(cols), jnp.asarray(bits), vals, opcodes, conj=conj,
        interpret=True))
    want = np.asarray(kref.fused_chain_ref(
        jnp.asarray(cols), jnp.asarray(bits), jnp.asarray(vals), opcodes,
        conj=conj))
    want = np.asarray(want)
    # dead blocks: kernel writes zeros; ref keeps mask-AND (also zeros)
    np.testing.assert_array_equal(got, want)


def _pack_mask(hits):
    """bool[|dict|] -> packed u32[ceil/32] code hit bitmask (the canonical
    packing — masks must follow the same convention as record bitmaps)."""
    return pack_bits(np.asarray(hits, dtype=bool))


@pytest.mark.parametrize("n,b,dict_n", [(1, 256, 7), (3, 1024, 37),
                                        (4, 2048, 64), (2, 512, 200)])
def test_dict_lookup_kernel_matches_ref(n, b, dict_n):
    rng = np.random.default_rng(n * 100 + dict_n)
    col = rng.integers(0, dict_n, size=(n, b)).astype(np.float32)
    bits = rng.integers(0, 2 ** 32, size=(n, b // 32), dtype=np.uint32)
    if n > 1:
        bits[1] = 0                       # dead block exercises pl.when skip
    mask = _pack_mask(rng.random(dict_n) < 0.4)
    got = np.asarray(kops.dict_lookup_blocks(
        jnp.asarray(col), jnp.asarray(bits), jnp.asarray(mask),
        interpret=True))
    want = np.asarray(kref.dict_lookup_ref(
        jnp.asarray(col), jnp.asarray(bits), jnp.asarray(mask)))
    np.testing.assert_array_equal(got, want)


def test_dict_lookup_matches_numpy_oracle():
    """Kernel + ref vs a direct numpy membership test, end to end."""
    rng = np.random.default_rng(5)
    n, b, dict_n = 3, 1024, 23
    codes = rng.integers(0, dict_n, size=n * b)
    live = rng.random(n * b) < 0.7
    hits = rng.random(dict_n) < 0.5
    bits = pack_bits(live).reshape(n, b // 32)
    mask = _pack_mask(hits)
    for fn in (kops.dict_lookup_blocks, kref.dict_lookup_ref):
        kwargs = {"interpret": True} if fn is kops.dict_lookup_blocks else {}
        got = np.asarray(fn(jnp.asarray(codes.reshape(n, b).astype(np.float32)),
                            jnp.asarray(bits), jnp.asarray(mask), **kwargs))
        np.testing.assert_array_equal(
            unpack_bits(got.reshape(-1), n * b), hits[codes] & live)


def test_dict_lookup_multi_matches_single():
    """Q stacked record sets against one code column == Q single calls."""
    from repro.kernels.dict_lookup import (dict_lookup_scan,
                                           dict_lookup_scan_multi)
    rng = np.random.default_rng(9)
    q, n, b, dict_n = 3, 2, 512, 12
    w = b // 32
    col = rng.integers(0, dict_n, size=(n, b)).astype(np.float32)
    col_bm = jnp.asarray(col.reshape(n, w, 32).transpose(0, 2, 1))
    bits = rng.integers(0, 2 ** 32, size=(q, n, w), dtype=np.uint32)
    mask = jnp.asarray(_pack_mask(rng.random(dict_n) < 0.3))
    pops = kref.popcount_ref(jnp.asarray(bits.reshape(q * n, w)))
    multi = np.asarray(dict_lookup_scan_multi(
        col_bm, jnp.asarray(bits.reshape(q * n, w)),
        pops.astype(jnp.int32), mask, interpret=True)).reshape(q, n, w)
    for j in range(q):
        single = np.asarray(dict_lookup_scan(
            col_bm, jnp.asarray(bits[j]),
            kref.popcount_ref(jnp.asarray(bits[j])).astype(jnp.int32),
            mask, interpret=True))
        np.testing.assert_array_equal(multi[j], single)


@pytest.mark.parametrize("kernel", ["predicate", "dict_lookup"])
def test_multi_kernels_split_stacks_past_prefetch_budget(kernel, monkeypatch):
    """A stack whose popcounts overflow the SMEM budget runs in whole-query
    chunks and still equals one single-query call per query."""
    from repro.kernels.dict_lookup import (dict_lookup_scan,
                                           dict_lookup_scan_multi)
    # the module, not the function ``repro.kernels`` re-exports by its name
    ps = importlib.import_module("repro.kernels.predicate_scan")
    rng = np.random.default_rng(11)
    q, n, b, dict_n = 5, 3, 256, 40
    w = b // 32
    col = rng.integers(0, dict_n, size=(n, b)).astype(np.float32)
    col_bm = jnp.asarray(col.reshape(n, w, 32).transpose(0, 2, 1))
    bits = rng.integers(0, 2 ** 32, size=(q, n, w), dtype=np.uint32)
    bits[2, 1] = 0
    mask = jnp.asarray(_pack_mask(rng.random(dict_n) < 0.3))
    val = jnp.asarray([17.0], dtype=jnp.float32)
    if kernel == "predicate":
        def multi(bb, pp):
            return ps.predicate_scan_multi(col_bm, bb, pp, val, 0,
                                           interpret=True)

        def single(bb, pp):
            return ps.predicate_scan(col_bm, bb, pp, val, 0, interpret=True)
    else:
        def multi(bb, pp):
            return dict_lookup_scan_multi(col_bm, bb, pp, mask,
                                          interpret=True)

        def single(bb, pp):
            return dict_lookup_scan(col_bm, bb, pp, mask, interpret=True)
    # room for two queries' popcounts (and the lookup's 2 mask words)
    monkeypatch.setattr(ps, "MAX_PREFETCH_WORDS", 2 * n + 2)
    assert ps.query_chunks(q * n, n) == [(0, 6), (6, 12), (12, 15)]
    flat = jnp.asarray(bits.reshape(q * n, w))
    got = np.asarray(multi(flat, kref.popcount_ref(flat).astype(jnp.int32)))
    for j in range(q):
        one = jnp.asarray(bits[j])
        want = np.asarray(single(one,
                                 kref.popcount_ref(one).astype(jnp.int32)))
        np.testing.assert_array_equal(got[j * n:(j + 1) * n], want)
