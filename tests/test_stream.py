"""Streaming admission layer + delta-reuse accounting + tape-cache reuse."""
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.columnar import (QuerySession, StreamQueryError, StreamSession,
                            Table, Tracer, make_forest_table, random_tree,
                            run_query)
from repro.columnar.device import (_COMPILER, _TAPE_PROGRAMS,
                                   _BackgroundCompiler)
from repro.columnar.trace import COMPILE_EVENT
from repro.core import And, Atom, Or, normalize
from repro.serve import RequestRouter


def _rows_like(table, n, seed):
    src = make_forest_table(n, n_dup=1, seed=seed)
    return {name: src.columns[name] for name in table.columns}


def _oracle(table, tree):
    return run_query(tree, table, planner="deepfish", engine="numpy")[0]


# -- StreamSession ------------------------------------------------------------

def test_stream_submit_drain_matches_oracle():
    t = make_forest_table(6000, n_dup=1, seed=7)
    stream = StreamSession(t, engine="numpy", max_pending=64)
    rng = np.random.default_rng(1)
    queries = [random_tree(t, 4, 2, rng) for _ in range(5)]
    futs = [stream.submit(q) for q in queries]
    assert not any(f.done() for f in futs)
    assert stream.pending == 5
    res = stream.drain()
    assert res.stats.n_queries == 5 and stream.pending == 0
    for f, q in zip(futs, queries):
        assert f.done()
        np.testing.assert_array_equal(f.result(), _oracle(t, q))
    assert stream.stats.batches == 1 and stream.stats.completed == 5


def test_stream_result_triggers_cooperative_drain():
    t = make_forest_table(3000, n_dup=1, seed=7)
    stream = StreamSession(t, engine="numpy", max_pending=64)
    q = random_tree(t, 4, 2, np.random.default_rng(2))
    fut = stream.submit(q)
    np.testing.assert_array_equal(fut.result(), _oracle(t, q))  # no deadlock
    assert stream.stats.batches == 1


def test_stream_auto_drains_at_max_pending():
    t = make_forest_table(3000, n_dup=1, seed=7)
    stream = StreamSession(t, engine="numpy", max_pending=2)
    rng = np.random.default_rng(3)
    a = stream.submit(random_tree(t, 4, 2, rng))
    assert not a.done()
    b = stream.submit(random_tree(t, 4, 2, rng))
    assert a.done() and b.done()                   # admission hit the cap


def test_stream_snapshot_at_drain_sees_interleaved_appends():
    t = make_forest_table(4000, n_dup=1, seed=7)
    stream = StreamSession(t, engine="numpy", max_pending=64)
    rng = np.random.default_rng(4)
    q1 = random_tree(t, 4, 2, rng)
    f1 = stream.submit(q1)
    stream.append(_rows_like(t, 800, seed=11))     # lands before the drain
    q2 = random_tree(t, 4, 2, rng)
    f2 = stream.submit(q2)
    stream.drain()
    assert t.n_records == 4800
    assert f1.n_records == 4800                    # snapshot at drain time
    np.testing.assert_array_equal(f1.result(), _oracle(t, q1))
    np.testing.assert_array_equal(f2.result(), _oracle(t, q2))
    assert stream.stats.appends == 1 and stream.stats.appended_rows == 800


def test_stream_tape_one_bundled_sync_per_drain():
    # the default (per-query compiled tapes) and the lockstep executor
    # alike: one bundled host sync per drain, before and after an append
    for batched in ("auto", True):
        t = make_forest_table(8000, n_dup=1, seed=7)
        stream = StreamSession(t, config=StreamSession.DEFAULT_CONFIG.replace(
            block=4096, batched=batched), max_pending=64)
        rng = np.random.default_rng(5)
        queries = [random_tree(t, 4, 2, rng) for _ in range(4)]
        for q in queries:
            stream.submit(q)
        stream.drain()
        be = stream.session._backend
        assert be.host_syncs == 1                  # one bundled sync
        stream.append(_rows_like(t, 1000, seed=12))
        futs = [stream.submit(q) for q in queries]
        res = stream.drain()
        assert be.host_syncs == 2                  # still one per drain
        for f, q in zip(futs, queries):
            np.testing.assert_array_equal(f.result(), _oracle(t, q))
        # the drain after the append reused the device columns: only the
        # dirty tail re-uploaded
        assert 0 < res.stats.upload_bytes < be.uploaded_bytes
        if batched is True:
            # lockstep keeps atom results across drains: they were
            # spliced, not redone
            assert res.stats.atoms_delta_extended > 0
            assert res.stats.delta_reuse_ratio > 0.5


def test_stream_delta_reuse_on_host_engine():
    t = make_forest_table(6000, n_dup=1, seed=7)
    stream = StreamSession(t, engine="numpy", max_pending=64,
                           share_threshold=1)
    rng = np.random.default_rng(6)
    queries = [random_tree(t, 5, 2, rng) for _ in range(3)] * 2
    for q in queries:
        stream.submit(q)
    stream.drain()
    stream.append(_rows_like(t, 600, seed=13))
    futs = [stream.submit(q) for q in queries]
    res = stream.drain()
    s = res.stats
    assert s.atoms_delta_extended > 0
    assert s.delta_rows_evaluated > 0
    assert s.delta_reuse_ratio == pytest.approx(6000 / 6600)
    for f, q in zip(futs, queries):
        np.testing.assert_array_equal(f.result(), _oracle(t, q))


def test_stream_failure_quarantined_to_own_future():
    # a broken query must fail only itself: drain never raises, the bad
    # future carries its own StreamQueryError (original as __cause__),
    # and batch-mates resolve normally
    t = make_forest_table(1000, n_dup=1, seed=7)
    stream = StreamSession(t, engine="numpy", max_pending=64)
    good = stream.submit(Atom("elevation_0", "lt", 3000.0))
    bad = stream.submit(Atom("no_such_column", "lt", 1.0))
    assert stream.drain() is None      # quarantine drains have no result
    assert good.done() and bad.done()
    from repro.columnar import pack_bits
    np.testing.assert_array_equal(
        good.result(), pack_bits(t.columns["elevation_0"] < 3000.0))
    with pytest.raises(StreamQueryError) as ei:
        bad.result()
    assert isinstance(ei.value.__cause__, KeyError)
    other = stream.submit(Atom("still_missing", "lt", 1.0))
    stream.drain()
    with pytest.raises(StreamQueryError) as ei2:
        other.result()
    assert ei2.value is not ei.value   # never a shared exception object
    assert stream.stats.quarantined_queries == 2
    assert stream.stats.failed == 2


# -- plan-cache tape reuse ----------------------------------------------------

def test_tape_cache_rebind_skips_recompiles():
    t = make_forest_table(8000, n_dup=1, seed=7)
    rng = np.random.default_rng(8)
    queries = [random_tree(t, 5, 3, rng) for _ in range(3)]
    sess = QuerySession(t, planner="deepfish", engine="tape", block=4096)
    r1 = sess.execute(queries)
    assert r1.stats.tape_cache_hits == 0           # cold cache: all compiled
    r2 = sess.execute(queries)
    assert r2.stats.tape_cache_hits == len(queries)  # rebound, not recompiled
    assert r2.stats.plan_cache_hits == len(queries)
    for q, bm in zip(queries, r2.bitmaps):
        np.testing.assert_array_equal(bm, _oracle(t, q))


def test_tape_rebind_across_key_equal_trees():
    """A fresh, structurally identical tree must reuse the cached tape and
    still bind its own comparison values."""
    from repro.core import normalize, tree_copy
    t = make_forest_table(8000, n_dup=1, seed=7)
    tree = random_tree(t, 5, 3, np.random.default_rng(9))
    sess = QuerySession(t, planner="deepfish", engine="tape", block=4096)
    sess.execute([tree])
    clone = normalize(tree_copy(tree.root))
    res = sess.execute([clone])
    assert res.stats.tape_cache_hits == 1
    np.testing.assert_array_equal(res.bitmaps[0], _oracle(t, tree))


# -- persistent (streaming) router -------------------------------------------

def test_persistent_router_routes_per_call_batches():
    rng = np.random.default_rng(0)

    def reqs(n):
        return {"tier": rng.choice(3, n).astype(np.int32),
                "tokens": rng.integers(8, 4096, n).astype(np.int32)}

    rules = [
        (Atom("tier", "eq", 2) | Atom("tokens", "lt", 1024)),
        Atom("tokens", "lt", 1024),
    ]
    router = RequestRouter(rules, persistent=True)
    r1 = reqs(64)
    m1 = router.route(r1)
    assert m1.shape == (2, 64)
    np.testing.assert_array_equal(m1[1], r1["tokens"] < 1024)
    r2 = reqs(48)
    m2 = router.route(r2)                          # appends, returns delta
    assert m2.shape == (2, 48)
    np.testing.assert_array_equal(
        m2[0], (r2["tier"] == 2) | (r2["tokens"] < 1024))
    np.testing.assert_array_equal(m2[1], r2["tokens"] < 1024)
    assert router.table.n_records == 112           # history accumulated
    # per-call cost is delta-shaped: cached atoms spliced, not re-evaluated
    assert router.last_result.stats.atoms_delta_extended > 0


# -- the served path: per-query compiled tapes, one bundled sync per drain ---

BRANDS = [f"Brand#{m}{n}" for m in range(1, 6) for n in range(1, 6)]
CONTAINERS = [f"{a} {b}" for a in ("SM", "MED", "LG", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]


def _lineitem(n, seed):
    """Columns of TPC-H lineitem joined to part that Q6 and Q19 read."""
    rng = np.random.default_rng(seed)
    return {"l_quantity": rng.integers(1, 51, n).astype(np.int32),
            "l_discount": rng.integers(0, 11, n).astype(np.int32),
            "l_shipdate": rng.integers(0, 2500, n).astype(np.int32),
            "p_size": rng.integers(1, 51, n).astype(np.int32),
            "p_brand": rng.choice(BRANDS, n),
            "p_container": rng.choice(CONTAINERS, n),
            "l_shipmode": rng.choice(MODES, n),
            "l_shipinstruct": rng.choice(INSTRUCTIONS, n)}


def _q19(rng):
    """Three arms of brand eq + container IN + quantity and size ranges +
    shipmode IN + shipinstruct eq."""
    arms = []
    for kind, size, lo in (("SM", 5, 1), ("MED", 10, 10), ("LG", 15, 20)):
        qty = int(rng.integers(lo, lo + 11))
        arms.append(And([
            Atom("p_brand", "eq", str(rng.choice(BRANDS))),
            Atom("p_container", "in", tuple(f"{kind} {b}" for b in
                                            ("CASE", "BOX", "PACK", "PKG"))),
            Atom("l_quantity", "ge", qty), Atom("l_quantity", "le", qty + 10),
            Atom("p_size", "ge", 1), Atom("p_size", "le", size),
            Atom("l_shipmode", "in", ("AIR", "REG AIR")),
            Atom("l_shipinstruct", "eq", "DELIVER IN PERSON")]))
    return normalize(Or(arms))


def _q6(rng):
    year = int(rng.integers(0, 5)) * 365
    disc = int(rng.integers(2, 10))
    return normalize(And([Atom("l_shipdate", "ge", year),
                Atom("l_shipdate", "lt", year + 365),
                Atom("l_discount", "ge", disc - 1),
                Atom("l_discount", "le", disc + 1),
                Atom("l_quantity", "lt", int(rng.integers(24, 26)))]))


def _expected(table, q):
    want = _oracle(table, q)
    lw = table.live_words()
    return want if lw is None else want & lw


def _served(table, **kw):
    return StreamSession(table, config=StreamSession.DEFAULT_CONFIG.replace(
        block=1024, **kw), max_pending=64)


def _dispatch_spans(tr):
    return [sp for sp in tr.drain() if sp.name == "batch.dispatch"]


def test_stream_default_drain_runs_one_program_per_query():
    _TAPE_PROGRAMS.clear()          # every plan below meets a cold program
    t = Table(_lineitem(6000, seed=1))
    tr = Tracer()
    stream = _served(t, trace=tr)
    rng = np.random.default_rng(2)
    queries = [_q19(rng), _q6(rng), _q19(rng), _q6(rng)]
    drains = []
    for _ in range(2):
        futs = [stream.submit(q) for q in queries]
        setops = stream.session._backend.setop_launches \
            if stream.session._backend is not None else 0
        res = stream.drain()
        drains.append((res.stats, _dispatch_spans(tr),
                       res.backend.setop_launches - setops))
        for f, q in zip(futs, queries):
            np.testing.assert_array_equal(f.result(), _expected(t, q))
        assert _COMPILER.wait(300)
    (cold, (cold_disp,), cold_setops), (warm, (disp,), setops) = drains
    # a plan met for the first time runs op by op while its program
    # compiles off the drain (a later query of the drain may find it
    # compiled already); one bundled sync per drain either way
    assert cold_disp.attrs["cold"] >= 2           # the first Q19, Q6
    assert _COMPILER.failures == []
    assert cold.device_dispatches > len(queries) and cold_setops > 0
    assert disp.attrs["tapes"] is True and disp.attrs["lockstep"] is False
    assert disp.attrs["cold"] == 0
    assert warm.lockstep_rounds == 0
    assert warm.device_dispatches == len(queries)  # one program per query
    assert setops == 0
    for st in (cold, warm):
        assert st.host_syncs == 1                  # one sync per drain
        assert st.host_fallbacks == 0


def test_background_compile_is_traced_and_the_next_call_compiles_nothing():
    _TAPE_PROGRAMS.clear()
    t = Table(_lineitem(5000, seed=10))
    tr = Tracer()
    stream = _served(t, trace=tr)
    rng = np.random.default_rng(11)
    queries = [_q19(rng), _q6(rng)]
    for q in queries:
        stream.submit(q)
    stream.drain()
    assert _COMPILER.wait(300)
    spans = tr.drain()
    by_seq = {sp.seq: sp for sp in spans}
    tape_compiles = [sp for sp in spans if sp.name == "tape.compile"]
    assert len(tape_compiles) == len(queries)
    assert {sp.thread for sp in tape_compiles} == {"tape-compile"}
    jax_compiles = [sp for sp in spans if sp.name == "jax.compile"
                    and by_seq.get(sp.parent_seq) in tape_compiles]
    assert len(jax_compiles) >= len(queries)
    compiles = []
    counting = threading.Event()

    def listener(event, secs, **kw):
        if event == COMPILE_EVENT and counting.is_set():
            compiles.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listener)
    futs = [stream.submit(q) for q in queries]
    counting.set()
    try:
        res = stream.drain()
    finally:
        counting.clear()
    # the program compiled from the arguments' abstract values is the one
    # the call runs: no trace, no compile on the drain
    assert compiles == []
    assert res.stats.device_dispatches == len(queries)
    for f, q in zip(futs, queries):
        np.testing.assert_array_equal(f.result(), _expected(t, q))


def test_stream_default_tapes_match_lockstep_and_oracle():
    rng = np.random.default_rng(3)
    queries = [_q19(rng) for _ in range(3)] + [_q6(rng) for _ in range(2)]
    extra = _lineitem(700, seed=4)
    dead = np.random.default_rng(5).choice(6700, 300, replace=False)
    got = {}
    for batched in ("auto", True):
        t = Table(_lineitem(6000, seed=1))
        stream = _served(t, batched=batched)
        rounds = []
        for step in ("initial", "append", "delete"):
            if step == "append":
                stream.append(extra)
            elif step == "delete":
                stream.delete(dead)
            futs = [stream.submit(q) for q in queries]
            stream.drain()
            answers = [f.result() for f in futs]
            for a, q in zip(answers, queries):
                np.testing.assert_array_equal(a, _expected(t, q))
            rounds.append(answers)
        got[batched] = rounds
    for tapes, lockstep in zip(got["auto"], got[True]):
        for a, b in zip(tapes, lockstep):
            np.testing.assert_array_equal(a, b)


def test_stream_tape_append_in_bucket_compiles_nothing():
    # 6000 rows in blocks of 1024: bucket 8; appends of 10 rows keep both
    # the bucket and the append boundary's block, so refresh's tail
    # shapes are the warm-up append's
    t = Table(_lineitem(6000, seed=6))
    stream = _served(t)
    rng = np.random.default_rng(7)
    queries = [_q19(rng), _q6(rng), _q19(rng)]
    for step in range(2):
        if step:
            stream.append(_lineitem(10, seed=8))
        for q in queries:
            stream.submit(q)
        stream.drain()
        assert _COMPILER.wait(300)
    compiles = []
    counting = threading.Event()

    def listener(event, secs, **kw):
        if event == COMPILE_EVENT and counting.is_set():
            compiles.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listener)
    stream.append(_lineitem(10, seed=9))
    futs = [stream.submit(q) for q in queries]
    n_programs = len(_TAPE_PROGRAMS)
    counting.set()
    try:
        res = stream.drain()
    finally:
        counting.clear()
    assert t.n_records == 6020 and res.stats.host_syncs == 1
    assert res.stats.device_dispatches == len(queries)
    assert len(_TAPE_PROGRAMS) == n_programs
    assert compiles == []
    for f, q in zip(futs, queries):
        np.testing.assert_array_equal(f.result(), _expected(t, q))


class _FakeProgram:
    """Stands in for a jitted program: counts its lowerings and keeps what
    they were given; ``fail`` makes its compile raise."""

    def __init__(self, fail=False):
        self.fail = fail
        self.lowered = 0
        self.lowered_with = []
        self._lock = threading.Lock()

    def lower(self, *args):
        with self._lock:
            self.lowered += 1
            self.lowered_with.append(args)
        time.sleep(0.005)
        return self

    def compile(self):
        if self.fail:
            raise RuntimeError("planted compile failure")
        return self


@pytest.mark.filterwarnings("ignore:whole-tape program failed")
def test_background_compiler_compiles_each_program_once_under_contention():
    comp = _BackgroundCompiler()
    progs = [_FakeProgram() for _ in range(3)] + [_FakeProgram(fail=True)]
    shapes = [(jax.numpy.zeros(4, np.uint32),),
              (jax.numpy.zeros(8, np.uint32),)]
    jobs = [(p, a) for p in progs for a in shapes]
    calls = []

    def hammer():
        deadline = time.monotonic() + 30
        n = 0
        while time.monotonic() < deadline:
            ready = [comp.ready(p, a) for p, a in jobs]
            n += len(ready)
            if all(ready[:6]) and len(comp.failures) == 2:
                break
        calls.append(n)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=hammer) for _ in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
        comp._stop()
    assert len(calls) == 16
    # one compile per program and shapes, however many drains asked
    assert [p.lowered for p in progs] == [2, 2, 2, 2]
    assert all(comp.ready(p, a) for p, a in jobs[:6])
    # a failed compile stays failed: its tape keeps running op by op
    assert not any(comp.ready(p, a) for p, a in jobs[6:])
    assert progs[3].lowered == 2 and len(comp.failures) == 2
    assert not comp._thread.is_alive()
    # a queued job holds abstract values, never the device arrays
    for p in progs:
        for (aval,) in p.lowered_with:
            assert isinstance(aval, jax.ShapeDtypeStruct)
