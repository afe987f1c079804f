"""The command line off a TPU, and cells, mixes, configurations and metrics
added by adding files alone."""
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

ARGS = ["--workload", "tpch-q19-open", "--seed", str(2**34 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_refuses_in_a_bare_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_new_files_are_found_by_name(tmp_path):
    """A configuration, a mix, a metric and a cell that uses them, added
    to a copy without editing any file the copy already has (apart from
    the new entries in BENCHMARK.json)."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(p, "rb").read() for p in _files(tmp_path / "bench")}
    (tmp_path / "bench/configs/tpch-tiny.json").write_text(json.dumps(
        {"name": "tpch-tiny", "generator": "tpch", "scale_factor": 0.001}))
    (tmp_path / "bench/traffic/q6-closed1.json").write_text(json.dumps(
        {"loop": "closed", "streams": 1, "mix": [{"family": "q6"}],
         "warmup_requests": 2, "check_sample": 4}))
    (tmp_path / "bench/metrics/answered.py").write_text(
        "def read(run):\n    return len(run.answered)\n")
    bench = harness.load_benchmark(ROOT)
    bench["configs"].append({"name": "tpch-tiny", "source": "test",
                             "file": "bench/configs/tpch-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-q6", "config": "tpch-tiny",
                               "traffic": "q6-closed1", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "answered", "unit": "queries",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": ["tiny-q6"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    out = harness.run_cell("tiny-q6", 5, 0.5, False, t_process=0.0,
                           root=str(tmp_path), log=lambda *a, **k: None)
    assert out["correct"], out["checks"]
    assert out["metrics"]["answered"]["value"] == out["attempted"] > 0
    assert {"latency_p50_ms", "setup_s"} <= set(out["metrics"])
    for path, content in before.items():
        assert open(path, "rb").read() == content


def _files(top):
    return [os.path.join(d, f) for d, _, fs in os.walk(top) for f in fs
            if "__pycache__" not in d]
