"""The reduction from a profiler trace to busy time, idle gaps, op time and
the scan roofline: on hand-made events, and on a trace recorded on one
TPU v5e (``bench/tests/data/small.xplane.pb``: a one-second window of the
Q19 mix at SF 0.01)."""
import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, tracefile  # noqa: E402

DATA = os.path.join(ROOT, "bench", "tests", "data")


def _trace():
    # window [10 s, 11 s] on perf_counter = [1e9 ns, 2e9 ns] on the trace
    marker = (1e9, 1e9)
    events = {"/device:TPU:0": [
        ("fusion.1", 1.1e9, 0.1e9),       # 10.1-10.2
        ("fusion.2", 1.15e9, 0.1e9),      # 10.15-10.25, overlaps
        ("copy.3", 1.5e9, 0.1e9),         # 10.5-10.6
        ("fusion.1", 1.95e9, 0.2e9),      # 10.95-11.15, clipped
        ("early", 0.5e9, 0.1e9),          # before the window
    ]}
    return tracefile.from_events(marker, events, window_t0=10.0)


def test_busy_is_the_union_within_the_window():
    t = _trace()
    assert t.window == (10.0, 11.0)
    assert t.busy_s() == pytest.approx(0.15 + 0.1 + 0.05)
    assert t.busy_s(10.12, 10.55) == pytest.approx(0.13 + 0.05)
    assert tracefile.merge([(3, 4), (1, 2), (1.5, 2.5)]) == [(1, 2.5), (3, 4)]


def test_top_ops_and_idle_gaps():
    t = _trace()
    ops = tracefile.top_ops(t)
    assert ops[0][0] == "fusion.1"
    assert ops[0][1] == pytest.approx(0.15)
    spans = [("stream.drain", 10.2, 10.5, 1), ("batch.plan", 10.3, 10.4, 3),
             ("batch.dispatch", 10.6, 10.95, 3)]
    gaps = tracefile.idle_gaps(t, spans)
    assert [g[0] for g in gaps] == ["batch.dispatch", "batch.plan",
                                    "outside_spans"]
    assert [g[1] for g in gaps] == pytest.approx([0.35, 0.25, 0.1])


def test_scan_roofline_arithmetic():
    read = harness.load_metric("scan_roofline")
    t = _trace()
    drain = harness.Drain(10.0, 10.3, queries=2,
                          columns=frozenset({"a", "b"}), rows=8_000_000)
    run = harness.Run(seconds=1.0, window=(10.0, 11.0), setup_s=0.0,
                      requests=[], drains=[drain],
                      trace=t, peaks={"hbm_bytes_per_s": 819e9},
                      distinct={"a": 50, "b": 3}.get)
    least_bytes = 8e6 * (math.ceil(math.log2(50)) + 2) / 8 + 2 * 8e6 / 8
    want = 100 * (least_bytes / 819e9) / t.busy_s(10.0, 10.3)
    assert read(run) == pytest.approx(want)
    idle = harness.load_metric("device_idle_pct")(run)
    assert idle == pytest.approx(100 * (1 - 0.3))


def test_recorded_trace():
    path = os.path.join(DATA, "small.xplane.pb")
    with open(os.path.join(DATA, "small.json")) as f:
        meta = json.load(f)
    t = tracefile.load(path, meta["window_t0"])
    assert t.window_s == pytest.approx(meta["window_s"])
    busy = t.busy_s()
    assert 0 < busy <= t.window_s
    assert busy == pytest.approx(meta["busy_s"])
    ops = tracefile.top_ops(t)
    assert 0 < len(ops) <= 10
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    assert sum(s for _, s in ops) <= sum(
        b - a for dev in t.ops.values() for _, a, b in dev) + 1e-12
    gaps = tracefile.idle_gaps(t, [])
    assert gaps and all(name == "outside_spans" for name, _ in gaps)
    assert sum(s for _, s in gaps) <= t.window_s - busy + 1e-9
