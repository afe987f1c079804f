"""The one traffic generator: it reads a mix from ``bench/traffic/<name>.json``.

Keys of a mix file:

``loop``
    ``"closed"``: ``streams`` clients, each submitting its next request
    when its last one is answered; latency runs from submit to answer.
    ``"open"``: arrivals at ``rate_qps``, whatever the system does;
    latency runs from when a request was due to its answer.  A window of
    ``s`` seconds holds exactly ``round(rate_qps * s)`` arrivals, placed
    uniformly at random (a Poisson process given its count), so every seed
    offers the same load in another order.
``mix``
    query families of the configuration's data generator, each an object
    with ``family`` and its parameters.  ``order`` is ``"weighted"``
    (each request draws a family by ``weight``, default 1) or ``"cycle"``
    (each closed-loop stream walks the list in turn).
``refresh`` (optional)
    ``{"every_s": s, "mutations": [...]}``: a refresh stream that applies
    the named mutations of the data generator, in order, every ``s``
    seconds, on a thread of its own.
``warmup_bursts`` (optional)
    sizes ``k``: before the window, ``k`` requests of the mix submitted at
    once for each, so that drains of each size the window makes compile in
    set-up.
``warmup_requests``
    requests driven with the same mix before the window, as set-up.
``check_sample``
    answered requests of the window compared with the reference, drawn
    from the seed.
"""
from __future__ import annotations

import threading
import time
from typing import List, Optional

import numpy as np

POLL_S = 0.0005


class Request:
    __slots__ = ("spec", "due", "submitted", "answered", "handle", "lo",
                 "hi", "error")

    def __init__(self, spec, due: float):
        self.spec = spec
        self.due = due
        self.submitted: Optional[float] = None
        self.answered: Optional[float] = None
        self.handle = None
        self.lo = self.hi = 0          # mutation states it may see
        self.error: Optional[BaseException] = None

    @property
    def latency_s(self) -> float:
        return self.answered - self.due


class Mix:
    """The families of a mix file, bound to a dataset."""

    def __init__(self, traffic: dict, data, rng: np.random.Generator):
        self.traffic = traffic
        self.draws = [data.family(m["family"], m, rng)
                      for m in traffic["mix"]]
        w = np.array([float(m.get("weight", 1.0)) for m in traffic["mix"]])
        self.p = w / w.sum()
        self.cycle = traffic.get("order", "weighted") == "cycle"

    def next(self, rng: np.random.Generator, position: int):
        if self.cycle:
            return self.draws[position % len(self.draws)](rng)
        return self.draws[int(rng.choice(len(self.draws), p=self.p))](rng)


class Mutations:
    """The benchmark's record of the refresh stream, shared with the
    ``drive`` loop: ``started`` counts mutations handed to the system, ``done``
    those it has returned from."""

    def __init__(self):
        self.started = 0
        self.done = 0


class RefreshThread:
    """Applies the refresh mutations every ``every_s`` seconds."""

    def __init__(self, spec: dict, data, system, muts: Mutations,
                 rng: np.random.Generator):
        self.every_s = float(spec["every_s"])
        self.names = list(spec["mutations"])
        self.data, self.system, self.muts, self.rng = data, system, muts, rng
        self._stop = threading.Event()
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._loop, name="refresh",
                                        daemon=True)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()

    def _loop(self) -> None:
        try:
            while not self._stop.wait(self.every_s):
                for name in self.names:
                    kind, payload = self.data.mutation(name, self.rng)
                    self.data.mutations.append((kind, payload))
                    self.muts.started += 1
                    self.system.mutate(kind, payload)
                    self.muts.done += 1
        except BaseException as exc:        # surfaced by the harness
            self.error = exc


def arrivals(rate_qps: float, seconds: float, t0: float,
             rng: np.random.Generator) -> np.ndarray:
    n = int(round(rate_qps * seconds))
    return t0 + np.sort(rng.uniform(0.0, seconds, n))


def drive(system, mix: Mix, traffic: dict, rng: np.random.Generator,
          muts: Mutations, *, seconds: Optional[float] = None,
          requests: Optional[int] = None, rate_qps: Optional[float] = None,
          on_start=None, late_s: float = 60.0) -> List[Request]:
    """Offer the mix for ``seconds`` (or until ``requests`` are submitted)
    and wait until every submitted request is answered or failed, at most
    ``late_s`` past the close.  Returns the requests in submission order;
    ``on_start(t0)`` runs as the window opens."""
    closed = traffic["loop"] == "closed"
    t0 = time.perf_counter()
    if on_start is not None:
        on_start(t0)
    t_end = t0 + seconds if seconds is not None else float("inf")
    limit = requests if requests is not None else float("inf")
    out: List[Request] = []
    outstanding: List[Request] = []
    closed_at: Optional[float] = None
    if closed:
        streams = int(traffic["streams"])
        idle = list(range(streams))
        owner = {}
        position = [0] * streams
    else:
        rate = float(rate_qps if rate_qps is not None
                     else traffic["rate_qps"])
        if seconds is None:
            seconds = requests / rate
            t_end = t0 + seconds
        due = arrivals(rate, seconds, t0, rng)
        nxt = 0

    def submit(req: Request) -> None:
        req.lo = muts.done
        req.submitted = time.perf_counter()
        try:
            req.handle = system.submit(req.spec)
        except Exception as exc:            # refused: counts as failed
            req.error = exc
            req.answered = time.perf_counter()
            return
        outstanding.append(req)

    while True:
        now = time.perf_counter()
        open_window = now < t_end and len(out) < limit
        if closed and open_window:
            while idle and len(out) < limit:
                s = idle.pop()
                req = Request(mix.next(rng, position[s]), now)
                position[s] += 1
                owner[id(req)] = s
                out.append(req)
                submit(req)
                if req.error is not None:
                    idle.append(s)
        elif not closed:
            while nxt < len(due) and due[nxt] <= now:
                req = Request(mix.next(rng, nxt), float(due[nxt]))
                nxt += 1
                out.append(req)
                submit(req)
        still = []
        for req in outstanding:
            if req.handle.done():
                req.answered = time.perf_counter()
                req.hi = muts.started
                try:
                    req.handle.result()
                except Exception as exc:
                    req.error = exc
                if closed:
                    idle.append(owner.pop(id(req)))
            else:
                still.append(req)
        outstanding[:] = still
        finished = (not open_window if closed else nxt >= len(due))
        if finished:
            closed_at = closed_at or time.perf_counter()
            if not outstanding or now > closed_at + late_s:
                return out
        if not closed and nxt < len(due):
            wait = min(POLL_S, max(0.0, due[nxt] - time.perf_counter()))
        else:
            wait = POLL_S
        if wait > 0:
            time.sleep(wait)

