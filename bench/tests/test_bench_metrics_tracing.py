"""The readers of the program's served-path spans and dispatch counters,
on hand-made runs: each number from its spans and counters, and nothing
from a program that records neither."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

NEW = ("queue_wait_ms", "lock_wait_ms_per_drain", "append_ms",
       "compile_ms_in_window", "launches_per_query",
       "bookkeeping_ms_per_drain", "executor_self_ms_per_drain")

# window [10 s, 20 s]: two drains in it, one before it
SPANS = [
    ("stream.queued", 9.0, 9.5, 1),         # before the window
    ("stream.lock_wait", 9.5, 9.6, 1),
    ("stream.drain", 9.6, 9.9, 1),
    ("stream.queued", 10.0, 10.05, 1),
    ("stream.queued", 10.0, 10.15, 1),
    ("stream.lock_wait", 10.15, 10.45, 1),
    ("stream.drain", 10.45, 11.0, 1),
    ("batch.dispatch", 10.5, 10.9, 3),
    ("jax.compile", 10.6, 10.62, 4),
    ("stream.append", 12.0, 12.4, 0),
    ("stream.queued", 14.0, 14.1, 1),
    ("stream.lock_wait", 14.1, 14.1, 1),
    ("stream.drain", 14.1, 14.7, 1),
    ("batch.dispatch", 14.2, 14.6, 3),
    ("stream.append", 16.0, 16.2, 0),
    ("jax.compile", 25.0, 25.5, 0),         # after the window
]

COUNTERS = {"completed": 3.0, "device_dispatches": 300.0,
            "kernel_invocations": 0.0,
            "kernel_launches": 200.0, "setop_launches": 100.0,
            "bookkeeping_launches": 900.0,
            "kernel_host_s": 0.3, "setop_host_s": 0.2,
            "bookkeeping_host_s": 0.1, "zone_host_s": 0.05}


def _run(spans=SPANS, counters=COUNTERS):
    return harness.Run(seconds=10.0, window=(10.0, 20.0), setup_s=0.0,
                       requests=[], spans=list(spans),
                       counters=None if counters is None else dict(counters))


def _read(name, run):
    return harness.load_metric(name)(run)


def test_span_readers():
    run = _run()
    assert _read("queue_wait_ms", run) == pytest.approx(
        (50 + 150 + 100) / 3)
    assert _read("lock_wait_ms_per_drain", run) == pytest.approx(300 / 2)
    assert _read("append_ms", run) == pytest.approx((400 + 200) / 2)
    assert _read("compile_ms_in_window", run) == pytest.approx(20)


def test_counter_readers():
    run = _run()
    assert _read("launches_per_query", run) == pytest.approx(1200 / 3)
    # the counters cover the window and the drains that answer its last
    # requests after it closes: three drains from the window's open on
    late = SPANS + [("stream.drain", 20.5, 20.8, 1),
                    ("batch.dispatch", 20.55, 20.75, 3)]
    assert _read("bookkeeping_ms_per_drain", _run(late)) == pytest.approx(
        100 / 3)
    # dispatch spans 0.4 + 0.4 + 0.2 s, less 0.65 s in launches and verdicts
    assert _read("executor_self_ms_per_drain", _run(late)) == pytest.approx(
        (1000 - 650) / 3)


def test_no_compile_in_the_window_reads_zero():
    spans = [s for s in SPANS if s[0] != "jax.compile"]
    assert _read("compile_ms_in_window", _run(spans)) == 0


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_and_counters_reads_nothing(name):
    """The parent commit's program: drains and dispatch spans, but no
    request, lock, append or compile spans and no dispatch split."""
    old = [s for s in SPANS if s[0] in ("stream.drain", "batch.dispatch")]
    counters = {k: v for k, v in COUNTERS.items()
                if not k.endswith(("_launches", "_host_s"))}
    assert _read(name, _run(old, counters)) is None
    assert _read(name, _run([], None)) is None


def test_every_reader_is_in_the_benchmark():
    bench = harness.load_benchmark(ROOT)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert entries[name]["moves"] == "latency_p50_ms"
        assert set(entries[name]["workloads"]) <= {
            c["name"] for c in bench["workloads"]}
