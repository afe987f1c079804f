"""Reduction of a profiler trace to device busy time, idle gaps and op time.

The JAX profiler writes an ``.xplane.pb``.  Its device planes
(``/device:TPU:<n>``) hold the operations the chip ran, on the line
``XLA Ops``; its host plane holds the ``jax.profiler.TraceAnnotation``
events.  The harness opens one annotation, ``bench_window``, when the
traced window opens and closes it when the window closes.  Its start puts
the host's ``perf_counter`` clock, which the program's spans use, on the
trace's clock: ``trace_ns = perf_ns + offset``.

Busy time is the union of the intervals in which an operation ran on a
device, within the window, averaged over the devices.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

MARKER = "bench_window"
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


@dataclass
class DeviceTrace:
    """A traced window on the ``perf_counter`` clock (seconds)."""

    window: Interval
    #: per device: (op name, start, end), clipped to the window
    ops: Dict[str, List[Tuple[str, float, float]]]
    _busy: Optional[Dict[str, List[Interval]]] = None

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy(self) -> Dict[str, List[Interval]]:
        """Merged busy intervals per device."""
        if self._busy is None:
            self._busy = {dev: merge([(a, b) for _, a, b in ops])
                          for dev, ops in self.ops.items()}
        return self._busy

    def busy_s(self, lo: float = None, hi: float = None) -> float:
        """Busy seconds in ``[lo, hi]`` (default: the window), averaged
        over the devices."""
        lo = self.window[0] if lo is None else lo
        hi = self.window[1] if hi is None else hi
        per = [overlap(iv, lo, hi) for iv in self.busy().values()]
        return sum(per) / len(per) if per else 0.0


def xplane_path(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def load(path: str, window_t0: float) -> DeviceTrace:
    """Read ``path`` and put it on the ``perf_counter`` clock, given that
    the ``bench_window`` annotation opened at ``window_t0``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    marker = None
    device_events: Dict[str, list] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                device_events[plane.name] = [
                    (op_name(ev.name), ev.start_ns, ev.duration_ns)
                    for ev in lines[OPS_LINE].events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == MARKER:
                        marker = (ev.start_ns, ev.duration_ns)
    if marker is None:
        raise ValueError(f"no {MARKER!r} annotation in {path}")
    return from_events(marker, device_events, window_t0)


def op_name(hlo: str) -> str:
    """``%fusion.3 = u32[8]{0} fusion(...)`` -> ``fusion.3``: the
    instruction's name, without its text."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def from_events(marker: Tuple[float, float],
                device_events: Dict[str, Sequence[Tuple[str, float, float]]],
                window_t0: float) -> DeviceTrace:
    """``marker`` is the window annotation's (start_ns, duration_ns) and
    each device event (name, start_ns, duration_ns), all on the trace's
    clock."""
    offset_ns = marker[0] - window_t0 * 1e9
    lo = window_t0
    hi = window_t0 + marker[1] * 1e-9
    ops = {}
    for dev, events in device_events.items():
        out = []
        for name, start, dur in events:
            a = (start - offset_ns) * 1e-9
            b = a + dur * 1e-9
            a, b = max(a, lo), min(b, hi)
            if b > a:
                out.append((name, a, b))
        ops[dev] = out
    return DeviceTrace((lo, hi), ops)


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def overlap(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Seconds of ``merged`` (sorted, disjoint) inside ``[lo, hi]``."""
    i = bisect.bisect_left(merged, (lo, lo))
    if i > 0:
        i -= 1
    total = 0.0
    for a, b in merged[i:]:
        if a >= hi:
            break
        total += max(0.0, min(b, hi) - max(a, lo))
    return total


def top_ops(trace: DeviceTrace, k: int = 10) -> List[list]:
    """The ``k`` op names that took the most device time (seconds, summed
    over the devices and divided by their number)."""
    total: Dict[str, float] = {}
    for ops in trace.ops.values():
        for name, a, b in ops:
            total[name] = total.get(name, 0.0) + (b - a)
    n = max(1, len(trace.ops))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, secs / n] for name, secs in ranked]


def idle_gaps(trace: DeviceTrace, spans: Sequence[Tuple[str, float, float,
                                                        int]],
              k: int = 10) -> List[list]:
    """The ``k`` longest idle gaps of the first device within the window,
    each named by the innermost host span (name, start, end, depth) that
    holds the gap's midpoint, or ``outside_spans``."""
    if not trace.ops:
        return []
    busy = next(iter(trace.busy().values()))
    lo, hi = trace.window
    gaps = []
    prev = lo
    for a, b in busy:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if hi > prev:
        gaps.append((prev, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:k]:
        mid = 0.5 * (a + b)
        inner = None
        for name, s0, s1, depth in spans:
            if s0 <= mid <= s1 and (inner is None or depth > inner[1]):
                inner = (name, depth)
        out.append([inner[0] if inner else "outside_spans", b - a])
    return out
