"""Observability plane: metrics registry, trace spans, EXPLAIN ANALYZE,
serving endpoints — and the hard contract that none of it perturbs
execution (bit-identical results, identical sync/retrace counts with
telemetry on or off)."""
import json
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from repro.columnar import (ExecConfig, QuerySession, StreamQueryError,
                            StreamSession, Tracer, explain_analyze,
                            make_forest_table, random_tree)
from repro.columnar.drainer import DrainPolicy
from repro.core import Atom
from repro.runtime import faults
from repro.runtime.telemetry import (MetricsRegistry, TelemetryError,
                                     parse_prometheus)


@pytest.fixture(autouse=True)
def _clean_plane():
    faults.fault_plane().clear()
    yield
    faults.fault_plane().clear()


def _trees(table, k, seed=0):
    rng = np.random.default_rng(seed)
    return [random_tree(table, 4, 2, rng) for _ in range(k)]


# -- registry units -----------------------------------------------------------

def test_counter_gauge_label_cells():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests")
    c.inc()
    c.inc(2, lane="bulk")
    c.inc(3, lane="bulk")
    assert c.value() == 1
    assert c.value(lane="bulk") == 5
    g = reg.gauge("depth")
    g.set(7, lane="x")
    g.dec(2, lane="x")
    assert g.value(lane="x") == 5
    # get-or-create returns the same instance; type clash raises
    assert reg.counter("reqs_total") is c
    with pytest.raises(TelemetryError):
        reg.gauge("reqs_total")
    with pytest.raises(TelemetryError):
        c.inc(-1)


def test_histogram_bucket_edges_inclusive_le():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(1.0, 10.0, 100.0))
    # exactly on an edge counts in that bucket (le semantics), above the
    # last bucket lands only in +Inf
    for v in (0.5, 1.0, 10.0, 99.9, 1000.0):
        h.observe(v)
    cell = h.snapshot_cell()
    assert cell["counts"] == [2, 1, 1, 1]    # per-bucket, +Inf tail last
    assert cell["count"] == 5
    assert cell["sum"] == pytest.approx(sum((0.5, 1.0, 10.0, 99.9, 1000.0)))
    with pytest.raises(TelemetryError):
        reg.histogram("bad", buckets=(5.0, 5.0))
    with pytest.raises(TelemetryError):      # bucket mismatch on re-get
        reg.histogram("lat", buckets=(1.0, 2.0))


def test_concurrent_publish_is_exact():
    reg = MetricsRegistry()
    c = reg.counter("n")
    h = reg.histogram("v", buckets=(10.0, 100.0))

    def work():
        for i in range(1000):
            c.inc()
            h.observe(float(i % 150))

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == 8000
    assert h.snapshot_cell()["count"] == 8000


def test_prometheus_render_round_trip():
    reg = MetricsRegistry()
    reg.counter("c_total", "help text").inc(3, engine="tape", shards=2)
    reg.gauge("g").set(1.5)
    reg.histogram("h", buckets=(1.0, 2.0)).observe(1.5)
    text = reg.render_prometheus()
    assert "# HELP c_total help text" in text
    assert "# TYPE c_total counter" in text
    parsed = parse_prometheus(text)
    assert parsed[("c_total", (("engine", "tape"), ("shards", "2")))] == 3
    assert parsed[("g", ())] == 1.5
    # histogram explodes into _bucket/_sum/_count series
    assert parsed[("h_bucket", (("le", "2"),))] == 1
    assert parsed[("h_bucket", (("le", "+Inf"),))] == 1
    assert parsed[("h_count", ())] == 1
    # label values with quotes/newlines survive the escaping
    reg.counter("esc_total").inc(1, q='sp"am\negg\\s')
    rt = parse_prometheus(reg.render_prometheus())
    assert rt[("esc_total", (("q", 'sp"am\negg\\s'),))] == 1


# -- tracer units -------------------------------------------------------------

def test_span_nesting_and_ring_bound():
    tr = Tracer(capacity=8)
    with tr.span("outer", k=1):
        with tr.span("inner"):
            tr.event("mark", x=2)
    spans = tr.drain()
    by_name = {s.name: s for s in spans}
    assert by_name["inner"].depth == 1
    assert by_name["inner"].parent_seq == by_name["outer"].seq
    assert by_name["inner"].events[0][0] == "mark"
    assert by_name["outer"].dur_ms >= by_name["inner"].dur_ms
    for i in range(20):                      # ring stays bounded
        with tr.span(f"s{i}"):
            pass
    assert len(tr) == 8

    off = Tracer(enabled=False)
    with off.span("ignored"):
        off.event("ignored")
    assert len(off) == 0


def test_stats_protocol_uniform():
    """Every stats surface answers as_dict() with scalars only."""
    t = make_forest_table(2000, n_dup=1, seed=7)
    cfg = ExecConfig(planner="deepfish", engine="numpy",
                     telemetry=False, trace=False)
    sess = QuerySession(t, config=cfg)
    res = sess.execute(_trees(t, 3))
    from repro.core.sets import Stats
    surfaces = [res.stats, sess.plan_cache.stats, Stats()]
    if sess.feedback is not None:
        surfaces.append(sess.feedback)
    for obj in surfaces:
        d = obj.as_dict()
        assert d and all(isinstance(v, (int, float)) for v in d.values())
    # the op log drains into the batch every time (never accumulates
    # undrained on the backend between drains)
    res2 = sess.execute(_trees(t, 3, seed=1))
    assert len(res2.stats.op_observations) <= res2.stats.physical_atoms


# -- the zero-perturbation contract -------------------------------------------

@pytest.mark.parametrize("engine", ["numpy", "tape"])
@pytest.mark.parametrize("planner", ["shallowfish", "deepfish"])
def test_bit_identical_and_contract_equal_with_telemetry(engine, planner,
                                                         forest):
    trees = _trees(forest, 4, seed=3)
    off = QuerySession(forest, config=ExecConfig(
        planner=planner, engine=engine, telemetry=False, trace=False))
    reg, tr = MetricsRegistry(), Tracer()
    on = QuerySession(forest, config=ExecConfig(
        planner=planner, engine=engine, telemetry=reg, trace=tr))
    r_off = off.execute(trees)
    r_on = on.execute(trees)
    for a, b in zip(r_off.bitmaps, r_on.bitmaps):
        np.testing.assert_array_equal(a, b)
    # sync/dispatch/retrace contracts byte-equal between the two runs
    for f in ("host_syncs", "device_dispatches", "host_fallbacks",
              "n_queries", "logical_atoms", "physical_atoms",
              "atom_cache_hits", "plan_cache_hits", "lockstep_rounds"):
        assert getattr(r_off.stats, f) == getattr(r_on.stats, f), f
    # and the observed run actually published
    assert reg.counter("repro_batches_total").value(
        engine=engine, planner=planner, shards=1) == 1
    assert any(s.name == "batch.execute" for s in tr.drain())


def test_batch_publishes_qerror_histograms(forest):
    reg = MetricsRegistry()
    cfg = ExecConfig(planner="deepfish", engine="tape", batched=True,
                     telemetry=reg, trace=False)
    sess = QuerySession(forest, config=cfg)
    sess.execute(_trees(forest, 4, seed=2))
    labels = dict(engine="tape", planner="deepfish", shards=1)
    cell = reg.histogram("repro_op_qerror").snapshot_cell(**labels)
    assert cell is not None and cell["count"] > 0
    assert reg.counter("repro_batch_host_syncs_total").value(**labels) >= 1
    assert reg.histogram("repro_batch_wall_ms").snapshot_cell(
        **labels)["count"] == 1


# -- EXPLAIN ANALYZE ----------------------------------------------------------

def test_explain_analyze_mixed_string_numeric(string_forest):
    q = (Atom("cover_0", "eq", "pine")
         | Atom("elevation_0", "lt",
                float(np.median(string_forest.columns["elevation_0"])))) \
        & Atom("slope_0", "ge", 0.0)
    rep = explain_analyze(q, string_forest,
                          config=ExecConfig(planner="deepfish",
                                            engine="tape"))
    assert rep.engine == "tape" and rep.planner
    assert 0 < rep.selected <= rep.n_records == string_forest.n_records
    assert rep.counters["host_syncs"] == 1       # the contract, visible
    assert rep.plan and rep.plan_order
    assert rep.ops and all(o.src >= o.out >= 0 for o in rep.ops)
    assert rep.max_qerror >= 1.0
    text = rep.render()
    for needle in ("EXPLAIN ANALYZE", "host_syncs=1", "q-err", "cover_0"):
        assert needle in text, needle
    # spans captured for just this query, and JSON-serializable
    assert any(s["name"] == "batch.execute" for s in rep.spans)
    json.dumps(rep.as_dict(), default=str)


def test_explain_analyze_borrowed_session_restores_tracer(forest):
    tr = Tracer()
    sess = QuerySession(forest, config=ExecConfig(
        planner="deepfish", engine="numpy", telemetry=False, trace=tr))
    rep = explain_analyze(_trees(forest, 1, seed=9)[0], session=sess)
    assert sess.tracer is tr                 # swapped back
    assert rep.selected >= 0 and rep.spans


# -- streaming observability --------------------------------------------------

def _stream(table, reg, tr, **kw):
    cfg = ExecConfig(planner="deepfish", engine="tape", batched=True,
                     telemetry=reg, trace=tr)
    return StreamSession(table, config=cfg, **kw)


def test_stream_health_explain_and_latency(forest):
    reg, tr = MetricsRegistry(), Tracer()
    ss = _stream(forest, reg, tr, background=True,
                 policy=DrainPolicy(20.0, 2.0))
    futs = [ss.submit(q, lane="interactive" if i % 2 else "bulk")
            for i, q in enumerate(_trees(forest, 4, seed=5))]
    for f in futs:
        f.result(timeout=30)
    # result() implies the report is already retained (no race)
    for f in futs:
        rep = ss.explain(f)
        assert rep is not None and isinstance(rep.query, str)
    h = ss.health()
    assert h["ok"] and h["drainer_alive"] and h["pending"] == 0
    assert h["last_drain_age_s"] is not None
    lat = reg.histogram("repro_query_latency_ms")
    counts = sum((lat.snapshot_cell(lane=ln) or {"count": 0})["count"]
                 for ln in ("interactive", "bulk"))
    assert counts == 4
    assert reg.gauge("repro_stream_batches").value(
        engine="tape", planner="deepfish", shards=1) >= 1
    ss.close()
    assert not ss.health()["ok"]             # closed -> not ok
    spans = tr.drain()
    names = {s.name for s in spans}
    assert {"stream.drain", "batch.execute", "batch.sync"} <= names
    # each request's queue wait is its own stream.queued span, keyed by id
    queued = [s for s in spans if s.name == "stream.queued"]
    assert sorted(s.attrs["id"] for s in queued) == sorted(f.id for f in futs)
    assert all(s.dur_ms >= 0 for s in queued)


def test_explain_retention_bounded(forest):
    reg = MetricsRegistry()
    ss = _stream(forest, reg, None)
    ss.explain_capacity = 3
    futs = [ss.submit(q) for q in _trees(forest, 5, seed=6)]
    ss.drain()
    assert len(ss.explain_ids()) == 3        # oldest two evicted
    assert ss.explain(futs[0]) is None
    assert ss.explain(futs[-1]) is not None
    ss.close()


def test_stream_close_flushes_metrics_json(forest, tmp_path):
    reg = MetricsRegistry()
    ss = _stream(forest, reg, None, cache_dir=str(tmp_path))
    fut = ss.submit(_trees(forest, 1, seed=7)[0])
    fut.result(timeout=30)
    ss.close()
    payload = json.loads((tmp_path / "metrics.json").read_text())
    assert payload["stream"]["batches"] == 1
    assert payload["health"]["closed"] is True
    assert any(k.startswith("repro_") for k in payload["registry"])


# -- fault ladder in the registry ---------------------------------------------

def test_degradation_ladder_assertable_from_registry(forest):
    trees = _trees(forest, 3, seed=8)
    reg = MetricsRegistry()
    ss = _stream(forest, reg, None, max_retries=2)

    def rung(name):
        return reg.counter("repro_degradation_total").value(rung=name)

    with faults.inject("device.dispatch", exc=faults.TransientFault,
                       times=1):
        ss.submit(trees[0]).result(timeout=30)
    assert (rung("retry"), rung("fallback"), rung("quarantine")) == (1, 0, 0)

    with faults.inject("device.dispatch", exc=faults.DeviceFault, times=4):
        ss.submit(trees[1]).result(timeout=30)
    assert rung("fallback") == 1 and rung("quarantine") == 0

    with faults.inject("query.plan", exc=lambda: ValueError("poisoned"),
                       times=4, match=lambda ctx: ctx.get("index") == 0):
        f = ss.submit(trees[2])
        with pytest.raises(StreamQueryError):
            f.result(timeout=30)
    assert rung("quarantine") == 1
    # the fault plane itself reported its trips into the global registry
    from repro.runtime.telemetry import registry as global_registry
    assert global_registry().counter("repro_faults_fired_total").value(
        site="device.dispatch") >= 2
    ss.close()


# -- HTTP endpoints -----------------------------------------------------------

def test_httpd_endpoints(forest):
    from urllib.request import urlopen

    from repro.serve.httpd import ObservabilityServer

    reg = MetricsRegistry()
    ss = _stream(forest, reg, Tracer())
    futs = [ss.submit(q) for q in _trees(forest, 2, seed=4)]
    for f in futs:
        f.result(timeout=30)
    with ObservabilityServer(ss) as srv:
        metrics = urlopen(f"{srv.url}/metrics", timeout=10).read().decode()
        parsed = parse_prometheus(metrics)
        key = ("repro_stream_completed",
               (("engine", "tape"), ("planner", "deepfish"),
                ("shards", "1")))
        assert parsed[key] == 2
        health = json.loads(urlopen(f"{srv.url}/healthz",
                                    timeout=10).read())
        assert health["ok"] is True
        listing = json.loads(urlopen(f"{srv.url}/explain",
                                     timeout=10).read())
        assert set(listing["retained"]) == {f.id for f in futs}
        rep = json.loads(urlopen(f"{srv.url}/explain?id={futs[1].id}",
                                 timeout=10).read())
        assert rep["counters"]["host_syncs"] == 1
        text = urlopen(f"{srv.url}/explain?id={futs[1].id}&format=text",
                       timeout=10).read().decode()
        assert "EXPLAIN ANALYZE" in text
    ss.close()


def test_httpd_healthz_surfaces_durability(tmp_path):
    """A durable session's /healthz carries the WAL block and recovery
    state; /metrics carries the ``repro_wal_*`` gauges after a drain."""
    import numpy as np
    from urllib.request import urlopen

    from repro.columnar import make_forest_table
    from repro.serve.httpd import ObservabilityServer

    table = make_forest_table(4000, n_dup=1, seed=7)  # session-private
    n0 = table.n_records
    data_dir = str(tmp_path / "data")
    reg = MetricsRegistry()
    ss = _stream(table, reg, None, durable=data_dir)
    rows = {n: c[:32].copy() for n, c in table.columns.items()}
    ss.append(rows)
    futs = [ss.submit(q) for q in _trees(table, 2, seed=4)]
    for f in futs:
        f.result(timeout=30)
    with ObservabilityServer(ss) as srv:
        health = json.loads(urlopen(f"{srv.url}/healthz",
                                    timeout=10).read())
        assert health["durable"] is True
        assert health["wal"]["uncommitted"] == 0    # drain group-committed
        assert health["wal"]["committed_seq"] >= 2  # create + append
        assert health["recovery"] == {"recovered": False}
        metrics = urlopen(f"{srv.url}/metrics",
                          timeout=10).read().decode()
        assert "repro_wal" in metrics
        assert "repro_wal_commit_ms" in metrics
    ss.close()

    ss2 = _stream(None, reg, None, durable=data_dir)
    with ObservabilityServer(ss2) as srv:
        health = json.loads(urlopen(f"{srv.url}/healthz",
                                    timeout=10).read())
        rec = health["recovery"]
        assert rec["recovered"] is True
        assert rec["recovery_ms"] > 0
    assert ss2.table.n_records == n0 + 32
    np.testing.assert_array_equal(
        ss2.table.columns["elevation_0"][-32:], rows["elevation_0"])
    ss2.close()


def test_httpd_404_and_bad_id(forest):
    from urllib.error import HTTPError
    from urllib.request import urlopen

    from repro.serve.httpd import ObservabilityServer

    ss = _stream(forest, MetricsRegistry(), None)
    with ObservabilityServer(ss) as srv:
        for path in ("/nope", "/explain?id=abc", "/explain?id=12345"):
            with pytest.raises(HTTPError):
                urlopen(f"{srv.url}{path}", timeout=10)
    ss.close()


# -- sharded subprocess: contracts + explain under shard_map ------------------

SHARD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    from repro.columnar import (ExecConfig, QuerySession, Tracer,
                                explain_analyze, make_forest_table,
                                random_tree, run_query)
    from repro.columnar.device import _TAPE_PROGRAMS
    from repro.core import Atom
    from repro.runtime.telemetry import MetricsRegistry

    t = make_forest_table(20_000, n_dup=1, seed=7, strings=True)
    trees = [random_tree(t, 4, 2, np.random.default_rng(s))
             for s in (1, 2)]
    out = {}

    reg, tr = MetricsRegistry(), Tracer()
    on = QuerySession(t, config=ExecConfig(
        planner="deepfish", engine="tape", batched=True, shards=2,
        telemetry=reg, trace=tr))
    off = QuerySession(t, config=ExecConfig(
        planner="deepfish", engine="tape", batched=True, shards=2,
        telemetry=False, trace=False))
    n0 = len(_TAPE_PROGRAMS)
    r_on, r_off = on.execute(trees), off.execute(trees)
    out["identical"] = all(
        np.array_equal(a, b) for a, b in zip(r_on.bitmaps, r_off.bitmaps))
    out["host_syncs"] = [r_on.stats.host_syncs, r_off.stats.host_syncs]
    out["oracle_ok"] = all(
        np.array_equal(bm, run_query(q, t, config=ExecConfig(
            planner="deepfish"))[0])
        for bm, q in zip(r_on.bitmaps, trees))
    t.append({name: col[:1024] for name, col in t.columns.items()})
    n1 = len(_TAPE_PROGRAMS)
    r2 = on.execute(trees)
    out["programs_compiled_on_append"] = len(_TAPE_PROGRAMS) - n1
    out["spans"] = sorted({s.name for s in tr.drain()})

    med = float(np.median(t.columns["elevation_0"]))
    q = (Atom("cover_0", "eq", "pine")
         | Atom("elevation_0", "lt", med)) & Atom("slope_0", "ge", 0.0)
    rep = explain_analyze(q, t, config=ExecConfig(
        planner="deepfish", engine="tape", shards=2))
    out["explain"] = {"shards": rep.shards, "selected": rep.selected,
                      "host_syncs": rep.counters["host_syncs"],
                      "has_qerr": rep.max_qerror >= 1.0,
                      "rendered": "EXPLAIN ANALYZE" in rep.render()}
    print(json.dumps(out))
""")


def test_sharded_observability_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SHARD_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["identical"] and out["oracle_ok"]
    assert out["host_syncs"] == [1, 1]       # one collective sync, on or off
    assert out["programs_compiled_on_append"] == 0
    assert "batch.sync" in out["spans"]
    assert out["explain"]["shards"] == 2
    assert out["explain"]["host_syncs"] == 1
    assert out["explain"]["has_qerr"] and out["explain"]["rendered"]
