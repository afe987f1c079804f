"""Tape compiler + device-resident executor.

Covers: CHAIN/SETOP lowering (the fused kernels are reachable from
``run_query``), DCE/slot allocation, the one-sync-per-query contract,
bucketed shape reuse of compiled programs, host fallbacks for non-numeric
columns (with consistent cost accounting across block engines), and the
cross-batch atom-result cache with table-version invalidation.
"""
import numpy as np
import pytest

from repro.columnar import (DeviceTapeBackend, QuerySession, Table,
                            make_forest_table, pack_bits, random_tree,
                            run_query)
from repro.columnar.device import _TAPE_PROGRAMS
from repro.core import (And, Atom, Or, PerAtomCostModel, compile_tape,
                        deepfish, normalize, shallowfish)
from repro.core.tape import ATOM, CHAIN, SETOP


def _conj_group_tree(forest):
    """(a ∧ b ∧ c) ∨ (d ∧ e): two all-atom conjunction groups."""
    def atom(col, g):
        return Atom(col, "lt", forest.value_at_selectivity(col, g),
                    selectivity=g)
    return normalize(Or([
        And([atom("elevation_0", 0.4), atom("slope_0", 0.5),
             atom("aspect_0", 0.6)]),
        And([atom("h_dist_road_0", 0.3), atom("hillshade_9am_0", 0.7)]),
    ]))


def oracle_mask(table, node):
    if isinstance(node, Atom):
        return table.eval_atom(node, None)
    combine = np.logical_and if isinstance(node, And) else np.logical_or
    out = None
    for c in node.children:
        m = oracle_mask(table, c)
        out = m if out is None else combine(out, m)
    return out


# -- compiler ----------------------------------------------------------------

def test_tape_contains_chain_and_setop_for_conjunction_groups(forest):
    tree = _conj_group_tree(forest)
    plan = shallowfish(tree, PerAtomCostModel(),
                       total_records=forest.n_records)
    tape = compile_tape(plan)
    kinds = [op.kind for op in tape.ops]
    assert CHAIN in kinds, "conjunction groups must lower to CHAIN ops"
    assert SETOP in kinds
    chains = [op for op in tape.ops if op.kind == CHAIN]
    assert sorted(len(op.aids) for op in chains) == [2, 3]
    assert all(op.conj for op in chains)


def test_chain_fusion_is_bit_identical(forest):
    tree = _conj_group_tree(forest)
    plan = deepfish(tree, PerAtomCostModel(), total_records=forest.n_records)
    fused = DeviceTapeBackend(forest, block=2048).run_tape(
        compile_tape(plan, chain=True))
    plain = DeviceTapeBackend(forest, block=2048).run_tape(
        compile_tape(plan, chain=False))
    np.testing.assert_array_equal(fused, plain)


def test_chain_order_does_not_split_the_program_key(forest):
    """Conjunctions that differ only in their atoms' selectivities plan in
    different orders, yet lower to one CHAIN in (column, op) order: one
    structural key, so one compiled program."""
    cols = ["elevation_0", "slope_0", "aspect_0", "h_dist_road_0"]

    def conj(sels):
        return normalize(And([
            Atom(c, "lt", forest.value_at_selectivity(c, g), selectivity=g)
            for c, g in zip(cols, sels)]))

    tapes = []
    for sels in ((0.2, 0.4, 0.6, 0.8), (0.8, 0.6, 0.4, 0.2)):
        tree = conj(sels)
        plan = deepfish(tree, PerAtomCostModel(),
                        total_records=forest.n_records)
        tapes.append(compile_tape(plan))
        (chain,) = [op for op in tapes[-1].ops if op.kind == CHAIN]
        assert [tree.atoms[a].column for a in chain.aids] == sorted(cols)
        got = DeviceTapeBackend(forest, block=2048).run_tape(tapes[-1])
        np.testing.assert_array_equal(
            got, pack_bits(oracle_mask(forest, tree.root)))
    assert tapes[0].key == tapes[1].key


def test_cse_merges_repeated_set_ops():
    from repro.core.tape import (EMPTY, FULL, OP_AND, OP_ANDNOT, OP_OR,
                                 TapeOp, _cse)
    ops = [TapeOp(FULL, 0), TapeOp(EMPTY, 7),
           TapeOp(ATOM, 1, a=0, aids=(0,)), TapeOp(ATOM, 2, a=0, aids=(1,)),
           TapeOp(SETOP, 3, a=1, b=2, setop=OP_OR),
           TapeOp(SETOP, 4, a=2, b=1, setop=OP_OR),    # = s3 (commutes)
           TapeOp(SETOP, 5, a=3, b=4, setop=OP_AND),   # s3 ∧ s3 = s3
           TapeOp(SETOP, 6, a=5, b=0, setop=OP_AND),   # s3 ∧ full = s3
           TapeOp(ATOM, 8, a=6, aids=(2,)),            # reads s3 now
           TapeOp(SETOP, 9, a=8, b=7, setop=OP_ANDNOT),  # s8 \ empty
           TapeOp(SETOP, 10, a=7, b=9, setop=OP_OR),   # empty ∨ s8 = s8
           TapeOp(SETOP, 11, a=10, b=2, setop=OP_ANDNOT)]
    kept, result = _cse(ops, 11)
    assert result == 11
    assert [op.dst for op in kept] == [0, 7, 1, 2, 3, 8, 11]
    assert kept[5] == TapeOp(ATOM, 8, a=3, aids=(2,))
    assert kept[6] == TapeOp(SETOP, 11, a=8, b=2, setop=OP_ANDNOT)


def test_cse_tapes_match_the_oracle_on_deep_trees(forest):
    rng = np.random.default_rng(12)
    be = DeviceTapeBackend(forest, block=2048)
    for _ in range(4):
        tree = random_tree(forest, 10, 4, rng)
        plan = deepfish(tree, PerAtomCostModel(),
                        total_records=forest.n_records)
        tape = compile_tape(plan)
        sets = [(op.setop, op.a, op.b) for op in tape.ops
                if op.kind == SETOP]
        assert all(a != b for _, a, b in sets)   # no x ∧ x, x ∨ x left
        np.testing.assert_array_equal(
            be.run_tape(tape), pack_bits(oracle_mask(forest, tree.root)))


def test_slot_allocation_recycles(forest):
    rng = np.random.default_rng(5)
    tree = random_tree(forest, 8, 3, rng)
    plan = deepfish(tree, PerAtomCostModel(), total_records=forest.n_records)
    tape = compile_tape(plan)
    n_dsts = len({op.dst for op in tape.ops})
    assert tape.n_slots == n_dsts
    assert tape.n_slots < len(tape.ops), "linear scan should recycle slots"
    assert tape.result < tape.n_slots


# -- device execution --------------------------------------------------------

def test_run_query_tape_reaches_fused_kernels_one_sync(forest):
    tree = _conj_group_tree(forest)
    res, plan, be = run_query(tree, forest, planner="shallowfish",
                              engine="tape")
    want = pack_bits(oracle_mask(forest, tree.root))
    np.testing.assert_array_equal(res, want)
    # the fused chain + setop kernels are live on the execution path
    assert any(op.kind == CHAIN for op in be.last_tape.ops)
    assert any(op.kind == SETOP for op in be.last_tape.ops)
    # one device dispatch, one host sync for the whole query
    assert be.device_dispatches == 1
    assert be.host_syncs == 1
    assert be.host_fallbacks == 0
    # a K-atom CHAIN counts as K applications (the fused trade stays
    # visible in the paper metrics)
    assert be.stats.atom_applications == sum(
        len(op.aids) for op in be.last_tape.ops if op.kind in (ATOM, CHAIN))
    assert be.stats.records_evaluated > 0
    assert be.blocks_touched > 0


def test_tape_pallas_engine_matches_jax_tape(forest):
    rng = np.random.default_rng(2)
    tree = random_tree(forest, 5, 3, rng)
    r1, _, _ = run_query(tree, forest, planner="deepfish", engine="tape")
    r2, _, b2 = run_query(tree, forest, planner="deepfish",
                          engine="tape-pallas")
    np.testing.assert_array_equal(r1, r2)
    assert b2.host_syncs == 1


def test_tape_program_cache_shared_across_key_equal_queries(forest):
    rng = np.random.default_rng(7)
    tree = random_tree(forest, 6, 3, rng)
    plan = deepfish(tree, PerAtomCostModel(), total_records=forest.n_records)
    be = DeviceTapeBackend(forest, block=2048)
    be.run_tape(compile_tape(plan))
    n_progs = len(_TAPE_PROGRAMS)
    # identical structure (same plan) must not compile a second program
    be.run_tape(compile_tape(plan))
    assert len(_TAPE_PROGRAMS) == n_progs


def test_backend_reuse_across_queries(forest):
    rng = np.random.default_rng(8)
    be = DeviceTapeBackend(forest, block=2048)
    for seed in range(2):
        tree = random_tree(forest, 5, 3, np.random.default_rng(seed))
        res, _, _ = run_query(tree, forest, planner="deepfish",
                              engine="tape", backend=be)
        want = pack_bits(oracle_mask(forest, tree.root))
        np.testing.assert_array_equal(res, want)
    assert be.host_syncs == 2           # still one per query


# -- dictionary-encoded strings: the one-sync contract on mixed plans --------

@pytest.fixture(scope="module")
def string_table():
    rng = np.random.default_rng(0)
    n = 4000
    return Table({
        "x": rng.normal(size=n).astype(np.float32),
        "y": rng.normal(size=n).astype(np.float32),
        "city": rng.choice(np.array(["oslo", "bergen", "tromso"]), n),
    })


def _mixed_tree():
    """Mixed numeric/string plan: dict-rewritable, no opaque atoms."""
    return normalize(And([
        Atom("x", "lt", 0.5, selectivity=0.7),
        Or([Atom("city", "eq", "oslo", selectivity=0.3),
            Atom("y", "gt", 0.0, selectivity=0.5)]),
    ]))


def _udf_tree():
    """Plan with a genuinely opaque atom: keeps the host fallback path."""
    udf = Atom("y", "udf", fn=lambda v: np.abs(v) < 0.7, selectivity=0.5)
    return normalize(And([
        Atom("x", "lt", 0.5, selectivity=0.7),
        Or([udf, Atom("y", "gt", 1.0, selectivity=0.15)]),
    ]))


def test_tape_engine_dict_strings_zero_fallbacks_one_sync(string_table):
    """The acceptance criterion: a mixed numeric+string (dict-encodable)
    plan executes as ONE device program — one dispatch, one host sync,
    host_fallbacks == 0 — bit-identical to the numpy oracle."""
    tree = _mixed_tree()
    res, _, be = run_query(tree, string_table, planner="deepfish",
                           engine="tape")
    want = pack_bits(oracle_mask(string_table, tree.root))
    np.testing.assert_array_equal(res, want)
    assert be.host_fallbacks == 0
    assert be.host_syncs == 1
    assert be.device_dispatches == 1


def test_tape_engine_fragmented_strings_zero_fallbacks_one_sync():
    """PR 5 acceptance: string atoms whose dictionary hit set fragments
    past MAX_CODE_RUNS (contains-LIKE, scattered IN) compile into the ONE
    device program via the dict-lookup kernel — no host fallback, one
    dispatch, one sync, bit-identical to the numpy oracle."""
    rng = np.random.default_rng(4)
    n = 6000
    vocab = np.array(["aspen", "birch", "cedar", "fir", "hemlock",
                      "juniper", "larch", "maple", "oak", "pine",
                      "spruce", "willow"])
    table = Table({
        "x": rng.normal(size=n).astype(np.float32),
        "species": rng.choice(vocab, n),
    })
    # 'contains e' fragments into 5 runs / 5 gaps; the IN set into 6 runs
    tree = normalize(And([
        Atom("x", "lt", 0.5, selectivity=0.7),
        Or([Atom("species", "like", "%e%", selectivity=0.5),
            Atom("species", "in", ("aspen", "cedar", "hemlock", "maple",
                                   "pine", "willow"), selectivity=0.5)]),
    ]))
    for engine in ("tape", "tape-pallas"):
        res, _, be = run_query(tree, table, planner="deepfish",
                               engine=engine)
        want = pack_bits(oracle_mask(table, tree.root))
        np.testing.assert_array_equal(res, want, err_msg=engine)
        assert be.host_fallbacks == 0, engine
        assert be.host_syncs == 1, engine
        assert be.device_dispatches == 1, engine


def test_fragmented_string_atoms_share_atom_key_across_queries():
    """Two queries with the same fragmented string atom dedupe in code
    space (the membership atom's key is (codes-col, 'in', codes))."""
    rng = np.random.default_rng(6)
    n = 3000
    vocab = np.array(["aspen", "birch", "cedar", "fir", "hemlock",
                      "juniper", "larch", "maple", "oak", "pine"])
    table = Table({
        "x": rng.normal(size=n).astype(np.float32),
        "y": rng.normal(size=n).astype(np.float32),
        "species": rng.choice(vocab, n),
    })
    like = lambda: Atom("species", "like", "%e%", selectivity=0.5)  # noqa: E731
    t1 = normalize(And([Atom("x", "lt", 0.5, selectivity=0.6), like()]))
    t2 = normalize(And([Atom("y", "gt", 0.0, selectivity=0.5), like()]))
    session = QuerySession(table, planner="deepfish", engine="numpy")
    r = session.execute([t1, t2])
    assert r.stats.shared_atom_keys >= 1
    for tree, bm in zip((t1, t2), r.bitmaps):
        want = pack_bits(oracle_mask(table, tree.root))
        np.testing.assert_array_equal(bm, want)


def test_tape_engine_unrewritten_strings_still_fall_back(string_table):
    # rewrite_strings=False restores the PR 2 behavior: same bits, one
    # host round-trip per string atom
    tree = _mixed_tree()
    res, _, be = run_query(tree, string_table, planner="deepfish",
                           engine="tape", rewrite_strings=False)
    want = pack_bits(oracle_mask(string_table, tree.root))
    np.testing.assert_array_equal(res, want)
    assert be.host_fallbacks > 0
    assert be.records_touched > 0 and be.blocks_touched > 0


def test_tape_engine_udf_fallback_matches_oracle(string_table):
    tree = _udf_tree()
    res, _, be = run_query(tree, string_table, planner="deepfish",
                           engine="tape")
    want = pack_bits(oracle_mask(string_table, tree.root))
    np.testing.assert_array_equal(res, want)
    assert be.host_fallbacks > 0
    assert be.records_touched > 0 and be.blocks_touched > 0


def test_run_tapes_bundles_programs_and_op_by_op_tapes(string_table):
    """A batch of an opaque-atom tape between two all-device tapes: the
    device tapes run one program each (once compiled), the fallback tape
    op by op, and one bundled sync (after the fallback's own) brings back
    all three."""
    from repro.columnar import rewrite_string_atoms
    from repro.columnar.device import _COMPILER
    trees = [rewrite_string_atoms(t, string_table)
             for t in (_mixed_tree(), _udf_tree(), _conj_tree())]
    tapes = [compile_tape(deepfish(t, PerAtomCostModel(),
                                   total_records=string_table.n_records))
             for t in trees]
    DeviceTapeBackend(string_table, block=1024).run_tapes(tapes)
    assert _COMPILER.wait(300)      # the programs the batch met compile
    be = DeviceTapeBackend(string_table, block=1024)
    device_ok = be._tape_bindings(tapes[1])[4]
    got = be.run_tapes(tapes)
    for g, t in zip(got, trees):
        np.testing.assert_array_equal(
            g, pack_bits(oracle_mask(string_table, t.root)))
    fallbacks = device_ok.count(False)
    assert fallbacks >= 1 and be.host_fallbacks == fallbacks
    assert be.host_syncs == fallbacks + 1
    op_by_op = sum(ok and op.kind in (ATOM, CHAIN, SETOP)
                   for op, ok in zip(tapes[1].ops, device_ok))
    assert be.cold_tapes == 0
    assert be.device_dispatches == 2 + op_by_op
    assert len(be.drain_op_log()) == sum(len(t.costed_ops()) for t in tapes)


def _conj_tree():
    return normalize(And([Atom("x", "gt", -0.5, selectivity=0.7),
                          Atom("y", "lt", 0.9, selectivity=0.8)]))


def test_block_engines_account_fallback_cost_consistently(string_table):
    # regression: the host-fallback path used to skip blocks_touched /
    # records_touched entirely, silently diverging between jax and pallas
    # (UDF atoms are the remaining fallback now that strings dict-rewrite)
    tree = _udf_tree()
    want = pack_bits(oracle_mask(string_table, tree.root))
    touched = {}
    for engine in ("jax", "pallas"):
        res, _, be = run_query(tree, string_table, planner="deepfish",
                               engine=engine)
        np.testing.assert_array_equal(res, want, err_msg=engine)
        assert be.records_touched > 0
        assert be.blocks_touched > 0
        touched[engine] = (be.records_touched, be.blocks_touched)
    assert touched["jax"] == touched["pallas"]


def test_string_atoms_share_across_queries_in_code_space(string_table):
    # the same string atom in two different queries dedupes through
    # atom_key after the code-space rewrite
    t1 = normalize(And([Atom("x", "lt", 0.5, selectivity=0.6),
                        Atom("city", "eq", "oslo", selectivity=0.3)]))
    t2 = normalize(And([Atom("y", "gt", 0.0, selectivity=0.5),
                        Atom("city", "eq", "oslo", selectivity=0.3)]))
    session = QuerySession(string_table, planner="deepfish", engine="numpy")
    r = session.execute([t1, t2])
    assert r.stats.shared_atom_keys >= 1
    for tree, bm in zip((t1, t2), r.bitmaps):
        want = pack_bits(oracle_mask(string_table, tree.root))
        np.testing.assert_array_equal(bm, want)


# -- cross-batch atom cache + invalidation (table.version) -------------------

def test_atom_cache_persists_across_batches_and_invalidates(forest):
    rng = np.random.default_rng(3)
    pool = [random_tree(forest, 5, 3, rng) for _ in range(3)]
    queries = pool + pool               # every atom shared within a batch
    session = QuerySession(forest, planner="deepfish", engine="numpy",
                           batched=False)
    r1 = session.execute(queries)
    p1 = r1.stats.physical_atoms
    r2 = session.execute(queries)
    # second batch: all shared atoms served from the persisted cache
    assert r2.stats.physical_atoms < p1
    for a, b in zip(r1.bitmaps, r2.bitmaps):
        np.testing.assert_array_equal(a, b)

    # a table write must invalidate: flip one column and re-run
    col = pool[0].atoms[0].column
    flipped = forest.columns[col].copy()
    flipped[:] = flipped[::-1]
    forest.set_column(col, flipped)
    try:
        r3 = session.execute(queries)
        assert r3.stats.physical_atoms >= r2.stats.physical_atoms
        for tree, bm in zip(queries, r3.bitmaps):
            want = pack_bits(oracle_mask(forest, tree.root))
            np.testing.assert_array_equal(bm, want)
    finally:                            # forest is session-scoped: restore
        forest.set_column(col, flipped[::-1].copy())


def test_column_rebind_invalidates_session_backend(forest):
    # the pre-existing write idiom `table.columns[name] = arr` (no
    # set_column) must also invalidate the session's cached backend
    rng = np.random.default_rng(12)
    queries = [random_tree(forest, 4, 2, rng)]
    session = QuerySession(forest, planner="deepfish", engine="jax")
    be = session.execute(queries).backend
    col = queries[0].atoms[0].column
    old = forest.columns[col]
    forest.columns[col] = old[::-1].copy()
    try:
        r = session.execute(queries)
        assert r.backend is not be
        want = pack_bits(oracle_mask(forest, queries[0].root))
        np.testing.assert_array_equal(r.bitmaps[0], want)
    finally:
        forest.columns[col] = old
        forest._stats.pop(col, None)


def test_atom_cache_version_invalidation_device_engine(forest_big):
    rng = np.random.default_rng(6)
    queries = [random_tree(forest_big, 4, 2, rng) for _ in range(2)] * 2
    session = QuerySession(forest_big, planner="deepfish", engine="tape",
                           block=4096, batched=True)   # device lockstep
    r1 = session.execute(queries)
    be = r1.backend
    r2 = session.execute(queries)
    assert r2.backend is be             # device backend (columns) reused
    assert be.host_syncs == 2           # one bundled sync per batch
    for tree, bm in zip(queries, r2.bitmaps):
        want = pack_bits(oracle_mask(forest_big, tree.root))
        np.testing.assert_array_equal(bm, want)

    # a table write must rebuild the device backend (stale uploaded
    # columns would otherwise serve wrong bitmaps) and drop the atom cache
    col = queries[0].atoms[0].column
    flipped = forest_big.columns[col].copy()[::-1].copy()
    forest_big.set_column(col, flipped)
    try:
        r3 = session.execute(queries)
        assert r3.backend is not be     # version bump -> fresh backend
        for tree, bm in zip(queries, r3.bitmaps):
            want = pack_bits(oracle_mask(forest_big, tree.root))
            np.testing.assert_array_equal(bm, want)
    finally:                            # forest_big is session-scoped
        forest_big.set_column(col, flipped[::-1].copy())
