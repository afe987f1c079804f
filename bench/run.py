"""Run one cell of the benchmark on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json``.  The last line of
standard output is the result as one JSON object; the numbers compared
for ``correct`` also end standard error, each beside its limit.  With no
TPU, or fewer chips than the cell asks for, the run exits non-zero and
prints no result.  JAX's persistent compilation cache is the program's
own (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the script's own directory is not an import root: modules are ``bench.*``
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.cell_of(harness.load_benchmark(ROOT), args.workload)
    from repro.columnar.persist import enable_compilation_cache
    enable_compilation_cache()
    import jax
    if jax.default_backend() != "tpu":
        print(f"bench: no TPU (JAX backend is {jax.default_backend()!r})",
              file=sys.stderr)
        return 3
    if jax.device_count() < int(cell["chips"]):
        print(f"bench: the cell needs {cell['chips']} chip(s), JAX sees "
              f"{jax.device_count()}", file=sys.stderr)
        return 3
    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_process=T_PROCESS, root=ROOT)
    harness.print_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
