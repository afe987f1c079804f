"""Whole runs of the harness on the CPU at tiny sizes, past its look for a
chip: the reference agrees with the engine on each configuration, and the
comparison reads ``correct`` false for the control and for each fault the
cells can have.

The forest cell (``forest-5.8m`` under ``forest-closed8``) is prepared but
not yet in ``BENCHMARK.json``; its runs here add it to a copy, as a later
change adds it, by entries and no edit of a file."""
import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import control, harness  # noqa: E402

TINY = {"forest-5.8m": {"rows": 20_000, "n_dup": 2},
        "tpch-sf1-lineitem": {"scale_factor": 0.002}}
FAST = {"warmup_requests": 4,
        "check_sample": 16}
SEED = 2**35 + 11
FOREST_CONFIG = {"name": "forest-5.8m", "source": "arXiv:2002.00540",
                 "file": "bench/configs/forest-5.8m.json", "reduced": [],
                 "why": "the paper's deployment"}
FOREST_CELL = {"name": "forest-closed8", "config": "forest-5.8m",
               "traffic": "forest-closed8", "chips": 1,
               "why": "8 closed streams over the forest templates"}


@pytest.fixture(scope="module")
def forest_root(tmp_path_factory):
    """A copy of the benchmark with the forest cell added."""
    root = tmp_path_factory.mktemp("bench_forest")
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = harness.load_benchmark(ROOT)
    bench["configs"].append(FOREST_CONFIG)
    bench["workloads"].append(FOREST_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def root_of(cell, request):
    return (request.getfixturevalue("forest_root")
            if cell == FOREST_CELL["name"] else ROOT)


def tiny_run(cell, seconds=1.0, traffic=None, root=ROOT, **kw):
    config = harness.load_benchmark(root)
    name = harness.cell_of(config, cell)["config"]
    extra = {"refresh": {"period_s": 0.3, "mutations": ["rf1", "rf2"]},
             "warmup_replay": {"q19": 3},
             "warmup_compile_wait_s": 1.0} \
        if cell == "tpch-throughput" else {}
    if cell.endswith("open"):
        extra["rate_qps"] = 8.0
    return harness.run_cell(cell, SEED, seconds, False, t_process=0.0,
                            root=root, config_overrides=TINY[name],
                            traffic_overrides={**FAST, **extra,
                                               **(traffic or {})},
                            log=lambda *a, **k: None, **kw)


@pytest.mark.parametrize("cell", ["forest-closed8", "tpch-throughput"])
def test_reference_agrees_with_engine(cell, request):
    root = root_of(cell, request)
    out = tiny_run(cell, root=root)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    wanted = harness.metrics_of(harness.load_benchmark(root), cell,
                                "end_to_end")
    assert set(out["metrics"]) == {m["name"] for m in wanted}


@pytest.mark.parametrize("cell", ["forest-closed8", "tpch-throughput",
                                  "tpch-q19-open"])
def test_control_is_not_correct(cell, request):
    root = root_of(cell, request)
    name = harness.cell_of(harness.load_benchmark(root), cell)["config"]
    extra = {"refresh": {"period_s": 0.3, "mutations": ["rf1", "rf2"]}} \
        if cell == "tpch-throughput" else {}
    size = TINY[name]
    if cell.endswith("open"):
        extra["rate_qps"] = 8.0
        # Q19 selects about 1 row in 30,000 per arm; the control changes
        # only rows at a rounded quantity bound, which a tiny table lacks
        size = {"scale_factor": 0.05}
    out = control.run_control(cell, SEED, 1.0, root=root,
                              config_overrides=size,
                              traffic_overrides={"check_sample": 16, **extra})
    assert not out["correct"]
    assert out["checks"]["wrong_rows"]["value"] > 0
    exact = control.run_control(cell, SEED, 1.0, root=root,
                                config_overrides=size,
                                traffic_overrides={"check_sample": 16,
                                                   **extra}, dtype=None)
    assert exact["correct"], exact["checks"]


def _flip_first_bit(monkeypatch):
    from repro.columnar import stream
    orig = stream.StreamFuture._resolve

    def altered(self, bitmap, n_records, live_words=None):
        bitmap = np.array(bitmap, copy=True)
        bitmap[0] ^= np.uint32(1)
        orig(self, bitmap, n_records, live_words)
    monkeypatch.setattr(stream.StreamFuture, "_resolve", altered)


def _half_left_out(monkeypatch):
    from repro.columnar import stream
    orig = stream.StreamFuture._resolve

    def half(self, bitmap, n_records, live_words=None):
        if self.id % 2 == 0:
            orig(self, bitmap, n_records, live_words)
    monkeypatch.setattr(stream.StreamFuture, "_resolve", half)


def _device_fault(monkeypatch):
    from repro.columnar import multiquery
    from repro.runtime.faults import DeviceFault
    orig = multiquery.QuerySession.execute

    def faulty(self, queries, *a, **k):
        if getattr(self, "_bench_faulted", False):
            return orig(self, queries, *a, **k)
        self._bench_faulted = True
        raise DeviceFault("planted")
    monkeypatch.setattr(multiquery.QuerySession, "execute", faulty)


def _append_unchanged(monkeypatch):
    from repro.columnar.table import Table
    monkeypatch.setattr(Table, "append", lambda self, rows: self.n_records)


def _delete_dropped(monkeypatch):
    from repro.columnar.table import Table
    monkeypatch.setattr(Table, "delete", lambda self, rows: 0)


@pytest.mark.parametrize("fault,cell,check", [
    (_flip_first_bit, "tpch-q19-open", "wrong_rows"),
    (_half_left_out, "tpch-q19-open", "failed_requests"),
    (_device_fault, "tpch-q19-open", "host_fallbacks"),
    (_append_unchanged, "tpch-throughput", "bad_snapshots"),
    (_delete_dropped, "tpch-throughput", "bad_snapshots"),
])
def test_fault_is_not_correct(monkeypatch, fault, cell, check):
    fault(monkeypatch)
    out = tiny_run(cell, late_s=15.0)
    assert not out["correct"]
    assert out["checks"][check]["value"] > 0, out["checks"]
