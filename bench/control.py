"""The control: the reference, in a lower precision, in the program's place.

    python bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...] [--dtype <d>]

The configurations state float32 columns (and integers that float32 holds
exactly).  Each names its control's precision as ``control_dtype``: the
nearest precision below float32 that changes an answer of its queries,
the step a later change might be tempted to take (bfloat16 for the
forest; float8 e4m3 for TPC-H, whose Q19 reads only integers up to 50,
which bfloat16 holds exactly).  The harness runs the cell with
:class:`ReferenceSystem` answering every request, skipping the warm-up
(there is nothing to warm), and compares as usual; a sound comparison
reads the control as not correct.  Prints one JSON line per seed with the
numbers compared.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]

from bench import reference  # noqa: E402

#: the control offers the cell's load without the set-up's warm-up
NO_WARMUP = {"warmup_requests": 0, "warmup_bursts": [],
             "warmup_compile_wait_s": 0}
#: ``run_control``'s default: the precision the configuration names
CONFIGURED = "configured"


class _Answer:
    __slots__ = ("id", "_bits", "snapshot")

    def __init__(self, rid, bits, snapshot):
        self.id, self._bits, self.snapshot = rid, bits, snapshot

    def done(self) -> bool:
        return True

    def result(self):
        return self._bits


class ReferenceSystem:
    """Answers each request at once with the reference in ``dtype``, at
    the table state of the mutations handed to it so far."""

    def __init__(self, data, dtype: Optional[str]):
        self.data, self.dtype = data, dtype
        self.applied = 0
        self._states = {}
        self._next = 0

    def _state(self):
        k = self.applied
        if k not in self._states:
            cols, n, live = self.data.state(k)
            words = None if live is None else reference.pack(live)
            self._states = {k: (cols, n, live, words)}
        return self._states[k]

    def submit(self, spec) -> _Answer:
        cols, n, live, words = self._state()
        bits = reference.reference_bitmap(spec, cols, n, live, self.dtype)
        self._next += 1
        return _Answer(self._next - 1, bits, (n, words))

    def mutate(self, kind, payload) -> None:
        self.applied += 1

    def counters(self):
        return None

    def spans(self):
        return []

    def spans_dropped(self) -> bool:
        return False

    def close(self) -> None:
        pass


def run_control(cell: str, seed: int, seconds: float, *, root: str = ROOT,
                config_overrides=None, traffic_overrides=None,
                dtype: Optional[str] = CONFIGURED) -> dict:
    """One run of ``cell`` with the reference in the program's place, in
    the configuration's ``control_dtype`` (``dtype=None``: exact)."""
    from bench import harness
    bench = harness.load_benchmark(root)
    if dtype == CONFIGURED:
        config = harness.load_config(
            bench, harness.cell_of(bench, cell)["config"], root)
        dtype = config["control_dtype"]
    no_warmup = dict(NO_WARMUP)
    if "warmup_replay" in harness.load_traffic(
            harness.cell_of(bench, cell)["traffic"], root):
        # the window's queries as the cell draws them, none replayed
        no_warmup["warmup_replay"] = {}
    return harness.run_cell(
        cell, seed, seconds, False, t_process=time.perf_counter(), root=root,
        config_overrides=config_overrides,
        traffic_overrides={**no_warmup, **(traffic_overrides or {})},
        make_system=lambda data: ReferenceSystem(data, dtype),
        log=lambda *a, **k: None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--dtype", default=CONFIGURED,
                    help="a precision of ml_dtypes; default: the "
                         "configuration's control_dtype")
    args = ap.parse_args(argv)
    for seed in args.seeds:
        out = run_control(args.workload, seed, args.seconds,
                          dtype=args.dtype)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "dtype": args.dtype,
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
