"""Find the rate an open-loop cell's system sustains: one process, one
set-up, then one window per offered rate, lowest first.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates <q/s> [<q/s> ...] [--bursts <k> ...]

The set-up is the cell's own warm-up, with ``--bursts`` (default: the
mix's ``warmup_bursts``) in place of its burst sizes, so that the first
windows do not pay compiles the later ones would not.  Prints one JSON line
per rate: latency percentiles, the generator's lateness, the programs
compiled in the window, and the backlog (requests submitted in the window
and still unanswered when it closed).  The sweep stops after the first rate
that leaves a tenth of its requests unanswered at the close, or any
``--seconds`` past it.  A cell's ``rate_qps`` is set once, from such a
sweep, at about four fifths of the highest rate whose backlog stays near
zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--bursts", type=int, nargs="*", default=None)
    args = ap.parse_args(argv)

    import numpy as np

    from bench import harness
    from bench.system import ProgramSystem
    from bench.traffic import Mix, Mutations, drive
    from repro.columnar.persist import enable_compilation_cache
    enable_compilation_cache()
    bench = harness.load_benchmark(ROOT)
    cell = harness.cell_of(bench, args.workload)
    config = harness.load_config(bench, cell["config"], ROOT)
    traffic = harness.load_traffic(cell["traffic"], ROOT)
    if traffic["loop"] != "open":
        raise SystemExit("sweep: only open-loop cells have a rate")
    if args.bursts is not None:
        traffic["warmup_bursts"] = args.bursts
    r_family, r_warm, r_window = harness.rngs(args.seed, 3)
    compiles = harness.CompileCounter()
    data = harness.load_generator(config["generator"], ROOT).build(
        config, args.seed)
    system = ProgramSystem(data)
    mix = Mix(traffic, data, r_family)
    muts = Mutations()
    try:
        harness.warm_up(system, mix, traffic, r_warm, muts, 60.0)
        for rate in sorted(args.rates):
            state = {}
            c0 = compiles.count
            reqs = drive(system, mix, traffic, r_window, muts,
                         seconds=args.seconds, rate_qps=rate,
                         on_start=lambda t0: state.update(t0=t0),
                         late_s=args.seconds)
            close = state["t0"] + args.seconds
            ok = [r for r in reqs if r.answered is not None
                  and r.error is None]
            lat = np.array([r.latency_s for r in ok] or [np.inf]) * 1e3
            late = np.array([r.submitted - r.due for r in reqs]) * 1e3
            backlog = sum(1 for r in reqs
                          if r.answered is None or r.answered > close)
            print(json.dumps({
                "rate_qps": rate, "requests": len(reqs),
                "answered": len(ok),
                "latency_p50_ms": float(np.percentile(lat, 50)),
                "latency_p95_ms": float(np.percentile(lat, 95)),
                "generator_late_p95_ms": float(np.percentile(late, 95)),
                "compiles_in_window": compiles.count - c0,
                "backlog_at_close": backlog}), flush=True)
            if len(ok) < len(reqs) or backlog > 0.1 * len(reqs):
                break                   # past capacity: stop climbing
        print(json.dumps({"host_fallbacks": harness.host_fallbacks(
            None, system.counters())}), flush=True)
    finally:
        system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
