"""One run of one benchmark cell.

Everything that belongs to one configuration, traffic mix or metric sits
in a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the deployment; its ``generator`` names
  ``bench/data/<generator>.py``, whose ``build(config, seed)`` makes the
  data and the query families;
* ``bench/traffic/<traffic>.json``: the mix ``bench/traffic.py`` offers;
* ``bench/metrics/<metric>.py``: ``read(run)``, one number from the run's
  requests, spans, counters and trace, or None where it finds nothing.

A run: build the data from the seed, open the system, warm it up with
the mix (set-up, ``warm_up``), then drive the window of ``seconds``;
close the system and compare a sample of the window's answers, drawn from
the seed, with the plain reference at the state each answer reports.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from bench import reference, tracefile
from bench.traffic import (POLL_S, Mix, Mutations, RefreshThread,
                           Request, drive)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: no compile for this long ends the warm-up's wait (a Q19 tape program
#: traces in well under it between two compiles of the background compiler)
COMPILE_QUIET_S = 3.0
#: replayed warm-up queries submitted at once (half the session's
#: ``max_pending``, so a burst is one drain)
REPLAY_BURST = 32


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no cell {name!r} in BENCHMARK.json")


def load_config(bench: dict, name: str, root: str = ROOT) -> dict:
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            with open(os.path.join(root, cfg["file"])) as f:
                return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(name: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_generator(name: str, root: str = ROOT):
    return _module(os.path.join(root, "bench", "data", f"{name}.py"),
                   f"bench_data_{name}")


def load_metric(name: str, root: str = ROOT) -> Callable:
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    return _module(path, "bench_metric_" + name.replace(".", "_")
                   .replace("-", "_")).read


def load_peaks(kind: str, root: str = ROOT) -> dict:
    """Peaks of the device ``kind`` as JAX names it; an unknown kind is an
    error, never a default."""
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return peaks[kind]


def metrics_of(bench: dict, cell: str, section: str) -> List[dict]:
    """The cell's metrics of ``end_to_end`` or ``per_layer``."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


# ---------------------------------------------------------------------------
# what a metric reads
# ---------------------------------------------------------------------------

@dataclass
class Drain:
    """One ``stream.drain`` of the window."""

    t0: float
    t1: float
    queries: int
    columns: frozenset          # columns its queries read
    rows: int                   # live rows of its snapshot


@dataclass
class Run:
    """Everything one run's metric readers may read."""

    seconds: float
    window: tuple                       # (open, open + seconds), perf_counter
    setup_s: float
    requests: List[Request]             # the window's, in submission order
    spans: List[tuple] = field(default_factory=list)   # (name, t0, t1, depth)
    drains: List[Drain] = field(default_factory=list)
    counters: Optional[Dict[str, float]] = None   # deltas over the window
    compiles: int = 0                   # programs compiled or loaded
    trace: Optional[tracefile.DeviceTrace] = None
    peaks: Optional[dict] = None
    distinct: Callable[[str], int] = None

    @property
    def answered(self) -> List[Request]:
        return [r for r in self.requests
                if r.answered is not None and r.error is None]

    def window_spans(self, name: str) -> List[tuple]:
        lo, hi = self.window
        return [s for s in self.spans if s[0] == name and lo <= s[1] < hi]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class CompileCounter:
    """Programs JAX compiled or loaded from its cache (copied from the
    bring-up check's ``CompileClock``: JAX's own monitoring events).  The
    event is recorded as a scalar when a compile starts and as a duration
    when it ends, so the counter also knows whether one is running and
    when the last started or ended."""

    def __init__(self):
        import jax
        self.count = 0
        self._lock = threading.Lock()
        self._open = 0                  # compiles running
        self._last = -math.inf          # last start or end
        jax.monitoring.register_scalar_listener(self._on_start)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _mark(self, step: int) -> None:
        with self._lock:
            self._open += step
            self._last = time.perf_counter()

    def _on_start(self, event: str, value: float, **_) -> None:
        if event == COMPILE_EVENT:
            self._mark(1)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1
            self._mark(-1)

    def quiet_since(self) -> Optional[float]:
        """When the last compile started or ended (-inf if none ever
        did); None while one is running."""
        with self._lock:
            return None if self._open > 0 else self._last


def wait_for_compiles(compiles: CompileCounter, cap_s: float,
                      quiet_s: float = COMPILE_QUIET_S,
                      poll_s: float = 0.05) -> float:
    """Wait until no compile has run for ``quiet_s`` seconds (a background
    compiler traces its next program between two compiles, for less than
    that), at most ``cap_s`` seconds; returns the seconds waited."""
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter()
        since = compiles.quiet_since()
        if since is not None and now - max(since, t0) >= quiet_s:
            return now - t0
        if now - t0 >= cap_s:
            return now - t0
        time.sleep(poll_s)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    stats = devs[0].memory_stats() or {}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs) if stats else 0
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def rngs(seed: int, n: int) -> List[np.random.Generator]:
    return [np.random.default_rng(s)
            for s in np.random.SeedSequence(seed).spawn(n)]


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, root: str = ROOT,
             config_overrides: Optional[dict] = None,
             traffic_overrides: Optional[dict] = None,
             make_system=None, late_s: float = 60.0,
             log=print) -> dict:
    """Run one cell; returns the result line's object (``checks`` last)."""
    bench = load_benchmark(root)
    cell = cell_of(bench, cell_name)
    config = load_config(bench, cell["config"], root)
    config.update(config_overrides or {})
    traffic = load_traffic(cell["traffic"], root)
    traffic.update(traffic_overrides or {})
    r_family, r_warm, r_window, r_refresh, r_sample = rngs(seed, 5)

    compiles = CompileCounter()
    data = load_generator(config["generator"], root).build(config, seed)
    if make_system is None:
        from bench.system import ProgramSystem as make_system
    system = make_system(data)
    mix = Mix(traffic, data, r_family)
    muts = Mutations()
    refresher = None
    if "refresh" in traffic:
        refresher = RefreshThread(traffic["refresh"], data, system, muts,
                                  r_refresh)
    try:
        t_warm = time.perf_counter()
        warm_up(system, mix, traffic, r_warm, muts, late_s, refresher)
        warmup_s = time.perf_counter() - t_warm
        compile_wait_s = wait_for_compiles(
            compiles, float(traffic.get("warmup_compile_wait_s", 0.0)))
        state = {}
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None

        def on_start(t0: float) -> None:
            if refresher is not None:
                refresher.start(t0, t0 + seconds)
            state["c0"] = system.counters()
            state["compiles0"] = compiles.count
            if trace:
                import jax
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0    # a Python tracer would
                opts.host_tracer_level = 1      # slow the host it measures
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                state["ann"] = jax.profiler.TraceAnnotation(
                    tracefile.MARKER)
                state["ann"].__enter__()
            state["t0"] = time.perf_counter()

        reqs = drive(system, mix, traffic, r_window, muts, seconds=seconds,
                     on_start=on_start, late_s=late_s)
        t1 = time.perf_counter()
        t0 = state["t0"]
        if refresher is not None:
            refresher.join()
        if trace:
            import jax
            state["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()
        compiled = compiles.count - state["compiles0"]
        c1 = system.counters()
    finally:
        if refresher is not None:
            refresher.stop()
    device = device_info()
    spans = [(s.name, s.t0, s.t0 + s.dur_ms / 1000.0, s.depth,
              s.attrs.get("queries", 0)) for s in system.spans()]
    spans_dropped = system.spans_dropped()
    system.close()
    run = Run(seconds=seconds, window=(t0, t0 + seconds),
              setup_s=t0 - t_process,
              requests=reqs,
              spans=[s[:4] for s in spans], compiles=compiled,
              distinct=data.distinct)
    c0 = state["c0"]
    if c0 is not None and c1 is not None and c0["backend"] == c1["backend"]:
        run.counters = {k: c1[k] - c0[k] for k in c1 if k != "backend"}
    fallbacks = host_fallbacks(c0, c1)
    late = [r.submitted - r.due for r in reqs if r.submitted is not None]
    refresh = {} if refresher is None else {
        "refresh_pairs": len(refresher.late),
        "refresh_late_p95_ms": p95_ms(refresher.late),
        "refresh_pair_s": [round(t, 4) for t in refresher.took]}
    # where the window's time went, in every run (the traced run's
    # per-layer metrics read the same spans and counters)
    span_s = {name: sum(b - a for _, a, b, _ in run.window_spans(name))
              for name in ("jax.compile", "stream.append", "stream.delete")}
    log(json.dumps({"phase": "window", "requests": len(reqs),
                    "generator_late_p95_ms": p95_ms(late), **refresh,
                    "warmup_s": warmup_s, "compile_wait_s": compile_wait_s,
                    "compiles_in_window": compiled,
                    "compile_ms": span_s["jax.compile"] * 1e3,
                    "mutation_s": (span_s["stream.append"]
                                   + span_s["stream.delete"]),
                    "cold_tapes": (run.counters or {}).get("cold_tapes"),
                    "mutations": muts.done,
                    "degraded_batches": (c1 or {}).get("degraded_batches"),
                    "retries": (c1 or {}).get("retries"),
                    "quarantined": (c1 or {}).get("quarantined"),
                    "spans_dropped": spans_dropped}), file=sys.stderr)
    if trace:
        run.peaks = load_peaks(device["kind"], root)
        run.drains = drains_of(spans, reqs, t0, t1)
        run.trace = tracefile.load(tracefile.xplane_path(trace_dir), t0)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(bench, cell_name, section):
        value = load_metric(m["name"], root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    checks, checked = check_answers(data, reqs, muts, r_sample,
                                    int(traffic["check_sample"]))
    checks["host_fallbacks"] = {"value": fallbacks, "limit": 0}
    if refresher is not None and refresher.error is not None:
        raise refresher.error
    failed = sum(1 for r in reqs if r.error is not None or r.answered is None)
    out = {"correct": all(v["value"] <= v["limit"] for v in checks.values()),
           "attempted": len(reqs), "failed": failed, "metrics": metrics,
           "device": device}
    if trace:
        gap_spans = [s[:4] for s in spans if t0 <= s[2] and s[1] <= t1]
        out["breakdown"] = {
            "device_ops": tracefile.top_ops(run.trace),
            "idle_gaps": tracefile.idle_gaps(run.trace, gap_spans)}
    log(json.dumps({"phase": "check", "checked": checked}), file=sys.stderr)
    out["checks"] = checks
    return out


def p95_ms(seconds: List[float]) -> float:
    return float(np.percentile(seconds, 95)) * 1e3 if seconds else 0.0


def host_fallbacks(c0: Optional[dict], c1: Optional[dict]) -> int:
    """Batches the system did not answer on its device path: the session's
    retried, degraded (re-run on the host) and quarantined ones since it
    opened, plus one where the device backend was replaced in the window.
    A run with any is not a run of the served path, however right its
    answers."""
    if c1 is None:
        return 0
    n = int(c1["degraded_batches"] + c1["retries"] + c1["quarantined"])
    if c0 is not None and c0["backend"] != c1["backend"]:
        n += 1
    return n


def warm_up(system, mix: Mix, traffic: dict, rng: np.random.Generator,
            muts: Mutations, late_s: float,
            refresher: Optional[RefreshThread] = None) -> None:
    """Set-up's share of the traffic, so the drains compile (or load from
    the cache) the programs of the shapes this cell's traffic makes: one
    refresh pair, one burst of ``k`` requests at once for each ``k`` of
    ``warmup_bursts`` (a drain of ``k`` queries each), the replayed
    queries of ``warmup_replay``, then ``warmup_requests`` of the mix as
    it runs."""
    if refresher is not None:
        refresher.pair()
    for k in traffic.get("warmup_bursts", []):
        drive(system, mix, {**traffic, "loop": "closed", "streams": int(k)},
              rng, muts, requests=int(k), late_s=late_s)
    replay = traffic.get("warmup_replay", {})
    specs = [mix.spec(k, i) for k, m in enumerate(traffic["mix"])
             for i in range(int(replay.get(m["family"], 0)))]
    for j in range(0, len(specs), REPLAY_BURST):
        submit_all(system, specs[j:j + REPLAY_BURST], late_s)
    drive(system, mix, traffic, rng, muts,
          requests=int(traffic["warmup_requests"]), late_s=late_s)
    mix.rewind()


def submit_all(system, specs: Sequence, late_s: float) -> None:
    """Submit ``specs`` at once and wait for every answer, at most
    ``late_s`` seconds; a set-up request that fails fails the run."""
    handles = [system.submit(spec) for spec in specs]
    deadline = time.perf_counter() + late_s
    for h in handles:
        while not h.done():
            if time.perf_counter() > deadline:
                raise TimeoutError("a warm-up request was not answered")
            time.sleep(POLL_S)
        h.result()


def drains_of(spans: List[tuple], reqs: List[Request], t0: float,
              t1: float) -> List[Drain]:
    """The window's drains with the requests each answered.  Every request
    goes through one lane in admission order, so the k-th drain of the
    session answers the next ``queries`` admission ids."""
    by_id = {r.handle.id: r for r in reqs if r.handle is not None}
    out = []
    next_id = 0
    live_rows = {}
    for name, a, b, _, q in sorted(
            (s for s in spans if s[0] == "stream.drain"),
            key=lambda s: s[1]):
        ids = range(next_id, next_id + q)
        next_id += q
        if not (t0 <= a and b <= t1):
            continue
        mine = [by_id[i] for i in ids if i in by_id]
        if not mine:
            continue
        cols = frozenset().union(*(reference.columns_of(r.spec)
                                   for r in mine))
        n, lw = (mine[0].handle.snapshot if mine[0].answered is not None
                 else (0, None))
        if lw is not None:
            key = id(lw)
            if key not in live_rows:
                live_rows[key] = int(np.unpackbits(
                    np.asarray(lw, np.uint32).view(np.uint8))[:n].sum())
            n = live_rows[key]
        out.append(Drain(a, b, q, cols, n))
    return out


def check_answers(data, reqs: List[Request], muts: Mutations,
                  rng: np.random.Generator, sample: int):
    """Compare a sample of the answered requests, drawn from the seed,
    with the reference.  An answer must equal the reference at the state
    it reports, and that state must be one the table passed through
    between the request's submission and its answer."""
    answered = [i for i, r in enumerate(reqs)
                if r.answered is not None and r.error is None]
    pick = sorted(rng.choice(len(answered), size=min(sample, len(answered)),
                             replace=False)) if answered else []
    states: Dict[int, tuple] = {}
    words: Dict[int, Optional[np.ndarray]] = {}

    def state(k):
        if k not in states:
            states[k] = data.state(k)
            live = states[k][2]
            words[k] = None if live is None else reference.pack(live)
        return states[k]

    wrong = bad = 0
    memo: Dict[tuple, np.ndarray] = {}
    for i in pick:
        req = reqs[answered[i]]
        n_got, lw_got = req.handle.snapshot
        k_match = None
        for k in range(req.lo, req.hi + 1):
            cols, n, live = state(k)
            if n != n_got:
                continue
            if (lw_got is None) != (words[k] is None):
                continue
            if lw_got is not None and not np.array_equal(
                    np.asarray(lw_got, np.uint32)[:len(words[k])], words[k]):
                continue
            k_match = k
            break
        if k_match is None:
            bad += 1
            continue
        cols, n, live = state(k_match)
        key = (repr(req.spec), k_match)
        if key not in memo:
            memo[key] = reference.reference_bitmap(req.spec, cols, n, live)
        wrong += reference.wrong_rows(req.handle.result(), memo[key])
    failed = sum(1 for r in reqs if r.error is not None or r.answered is None)
    checks = {"wrong_rows": {"value": wrong, "limit": 0},
              "bad_snapshots": {"value": bad, "limit": 0},
              "failed_requests": {"value": failed, "limit": 0}}
    return checks, len(pick)


def print_checks(checks: Dict[str, dict]) -> None:
    """The numbers compared, each beside its limit, as the last lines on
    standard error."""
    for name, c in checks.items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
