"""CPU rehearsal of ``chip_smoke.py``: its phases at 20k rows with
interpret-mode kernels, its numpy reference, its refusal to report
success off a TPU, and the compilation-cache directory rule."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402
from repro.columnar import (ExecConfig, make_forest_table,  # noqa: E402
                            run_query)

ROWS = 20_000


def _suite(table):
    return cs.build_suite(table, n_queries=3, seed=0, atoms=(6, 8),
                          depths=(2, 3))


@pytest.fixture(scope="module")
def clock():
    return cs.CompileClock()


@pytest.fixture(scope="module")
def table():
    return make_forest_table(ROWS, n_dup=2, seed=0)


@pytest.fixture(scope="module")
def strings_table():
    return make_forest_table(ROWS, n_dup=1, seed=0, strings=True)


def _run_script(args, env_extra=None, cwd=ROOT, script=SCRIPT):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run([sys.executable, script, *args], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=300)


# -- the reference -------------------------------------------------------------

def test_reference_matches_numpy_engine(table, strings_table):
    """The script's full scan agrees with the repo's numpy oracle engine
    on the random suite and on the fragmented string queries."""
    cfg = ExecConfig(planner="deepfish")
    for t, trees in ((table, _suite(table)),
                     (strings_table, cs.string_queries(strings_table))):
        for tree in trees:
            want = run_query(tree, t, config=cfg)[0]
            assert np.array_equal(cs.reference_bitmap(tree, t.columns),
                                  want)


def test_reference_packing_and_liveness():
    from repro.core.predicate import And, Atom, Or
    cols = {"x": np.arange(40, dtype=np.float32),
            "s": np.array(["Oak", "pine", "oak", "fir"] * 10)}
    tree = Or([And([Atom("x", "ge", 30.0), Atom("x", "lt", 34.0)]),
               Atom("s", "like", "o%")])
    live = np.ones(40, dtype=bool)
    live[0] = False
    got = cs.reference_bitmap(tree, cols, live)
    want = np.zeros(40, dtype=bool)
    want[30:34] = True
    want[[i for i in range(40) if i % 4 in (0, 2)]] = True
    want[0] = False
    assert got.dtype == np.dtype("<u4") and len(got) == 2
    assert np.array_equal(
        np.unpackbits(got.view(np.uint8), bitorder="little")[:40], want)
    assert not cs._same(got ^ np.uint32(1 << 5), got)


# -- the phases, at a small size ---------------------------------------------

def test_phase_run_query(table, clock):
    line = cs.phase_run_query(table, _suite(table), clock)
    assert line["tape_interpret"] is True          # CPU: interpret mode
    assert line["tape-pallas_interpret"] is True
    assert line["tpu_custom_call"] is False
    assert len(line["checks"]) == 2 and line["wall_s"] > 0


def test_phase_lockstep(table, clock):
    line = cs.phase_lockstep(table, _suite(table), clock)
    assert "3/3 == numpy, 0 host fallbacks, 1 host sync(s)" in line["checks"]


def test_phase_served(clock):
    table = make_forest_table(ROWS, n_dup=2, seed=1)
    line = cs.phase_served(table, _suite(table), clock)
    assert line["rows_final"] == ROWS + ROWS // 100 + (ROWS + ROWS // 100) \
        // 100
    assert len(line["checks"]) == 5
    assert "0 degraded / quarantined / retried" in line["checks"][-1]


def test_phase_strings(strings_table, clock):
    line = cs.phase_strings(strings_table, clock)
    assert line["checks"] == ["2/2 == numpy with dictionary lookups on "
                              "device, 0 host fallbacks"]


def test_phase_fails_on_wrong_answer(table, clock, monkeypatch):
    monkeypatch.setattr(cs, "reference_bitmap",
                        lambda tree, cols, live=None: np.zeros(1, np.uint32))
    with pytest.raises(cs.SmokeFailure):
        cs.phase_lockstep(table, _suite(table), clock)


SHARDED = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {root!r})
    import chip_smoke as cs
    from repro.columnar import make_forest_table
    table = make_forest_table({rows}, n_dup=2, seed=0)
    suite = cs.build_suite(table, n_queries=3, seed=0, atoms=(6, 8),
                           depths=(2, 3))
    print("LINE " + json.dumps(cs.phase_sharded(table, suite,
                                                 cs.CompileClock())))
""")


def test_phase_sharded_on_four_host_devices():
    """The ``--chips 4`` path on four simulated CPU devices (the device
    count is fixed at JAX start-up, hence the child process)."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", SHARDED.format(root=ROOT, rows=ROWS)],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.split("LINE ", 1)[1])
    assert line["shards"] == 4
    assert line["delta_upload_shards"] >= 1
    assert len(line["checks"]) == 2


# -- the entry point -----------------------------------------------------------

def _says_ok(stdout: str) -> bool:
    return '"ok": true' in stdout


def test_main_refuses_without_tpu(tmp_path):
    proc = _run_script([], {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert proc.returncode != 0
    assert not _says_ok(proc.stdout)
    assert "no TPU" in proc.stderr


def test_script_alone_fails(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    proc = _run_script([], cwd=str(tmp_path), script=str(alone))
    assert proc.returncode != 0
    assert not _says_ok(proc.stdout)


def test_result_line_shape():
    line = cs.result_line("tpu", "TPU v5 lite", 1)
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


CACHE_PROBE = textwrap.dedent("""
    import sys
    sys.path.insert(0, {src!r})
    import jax
    from repro.columnar import persist
    path = persist.enable_compilation_cache()
    print(path)
    print(jax.config.jax_compilation_cache_dir)
""")


@pytest.mark.parametrize("env_set", [True, False])
def test_compilation_cache_dir_rule(tmp_path, env_set):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, the process's cache is that
    directory; without it, the fixed ``<checkout>/.jax_cache``.  Run in a
    child so this process's JAX config is left as it was."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    want = os.path.join(ROOT, ".jax_cache")
    if env_set:
        want = str(tmp_path / "xla")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    proc = subprocess.run(
        [sys.executable, "-c",
         CACHE_PROBE.format(src=os.path.join(ROOT, "src"))],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    returned, configured = proc.stdout.split()[-2:]
    assert returned == configured == want
