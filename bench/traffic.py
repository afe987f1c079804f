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
    ``{"period_s": p, "mutations": [...]}``: a refresh stream that applies
    the named mutations of the data generator, in order, as one pair on a
    thread of its own.  Pairs are due on the window's clock, not paced by
    the system: the first at ``p / 2`` after the window opens, then every
    ``p``, so a window of ``s`` seconds holds exactly
    ``ceil((s - p / 2) / p)`` pairs on every run and seed.  A pair that
    comes due while the one before is still running starts when that one
    ends (its lateness is logged), and every pair due in the window is
    carried out.  Set-up applies one pair before its queries, so that
    these run on a table that has taken appends and deletes, as the
    window's do.
``warmup_bursts`` (optional)
    sizes ``k``: before the window, ``k`` requests of the mix submitted at
    once for each, so that drains of each size the window makes compile in
    set-up.
``warmup_replay`` (optional)
    ``{family: n}``: each family of the mix draws its queries from a
    stream of its own, so the window's i-th query of a family is known
    before the window opens; set-up submits the first ``n`` of each named
    family, a burst at a time, so that the programs of the plan structures
    the window meets are compiled when it opens.
``warmup_requests``
    requests driven with the same mix before the window, as set-up.
``warmup_compile_wait_s`` (optional)
    after the warm-up, wait until no program has compiled or loaded (JAX's
    compile events) for ``COMPILE_QUIET_S``, at most this many seconds, so
    that the programs the warm-up met are compiled when the window opens.
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
        self._queued = self._streams = None
        if "warmup_replay" in traffic:
            self._streams = rng.spawn(len(self.draws))
            self._queued = [[] for _ in self.draws]
            self._taken = [0] * len(self.draws)

    def spec(self, k: int, i: int):
        """The ``i``-th query of family ``k`` (a replayed mix)."""
        queued = self._queued[k]
        while len(queued) <= i:
            queued.append(self.draws[k](self._streams[k]))
        return queued[i]

    def next(self, rng: np.random.Generator, position: int):
        if self.cycle:
            k = position % len(self.draws)
        else:
            k = int(rng.choice(len(self.draws), p=self.p))
        if self._queued is None:
            return self.draws[k](rng)
        self._taken[k] += 1
        return self.spec(k, self._taken[k] - 1)

    def rewind(self) -> None:
        """The window starts again at each family's first query."""
        if self._queued is not None:
            self._taken = [0] * len(self.draws)


class Mutations:
    """The benchmark's record of the refresh stream, shared with the
    ``drive`` loop: ``started`` counts mutations handed to the system, ``done``
    those it has returned from."""

    def __init__(self):
        self.started = 0
        self.done = 0


class RefreshThread:
    """Applies the refresh mutations, in order, as one pair due every
    ``period_s`` seconds on the window's clock (see the mix keys above)."""

    def __init__(self, spec: dict, data, system, muts: Mutations,
                 rng: np.random.Generator):
        self.period_s = float(spec["period_s"])
        self.names = list(spec["mutations"])
        self.data, self.system, self.muts, self.rng = data, system, muts, rng
        self._t0 = self._end = 0.0
        self._abort = threading.Event()
        #: lateness and wall time (seconds) of each pair the thread ran
        self.late: List[float] = []
        self.took: List[float] = []
        self.error: Optional[BaseException] = None
        self.thread = threading.Thread(target=self._loop, name="refresh",
                                       daemon=True)

    def pair(self) -> None:
        """Apply one pair, on the calling thread."""
        for name in self.names:
            kind, payload = self.data.mutation(name, self.rng)
            self.data.mutations.append((kind, payload))
            self.muts.started += 1
            self.system.mutate(kind, payload)
            self.muts.done += 1

    def start(self, t0: float, end: float) -> None:
        """Pairs are due at ``t0 + period_s / 2``, then every
        ``period_s``, up to ``end``; the thread ends after the last."""
        self._t0, self._end = t0, end
        self.thread.start()

    def join(self) -> None:
        """Wait until every pair due before ``end`` has been carried out
        (at once, where it is late)."""
        self.thread.join()

    def stop(self) -> None:
        """End the thread after the pair it is running, if any."""
        self._abort.set()
        if self.thread.ident is not None:
            self.thread.join()

    def _loop(self) -> None:
        try:
            k = 0
            while (due := self._t0 + self.period_s * (k + 0.5)) < self._end:
                if self._abort.wait(max(0.0, due - time.perf_counter())):
                    return
                start = time.perf_counter()
                self.pair()
                self.late.append(start - due)
                self.took.append(time.perf_counter() - start)
                k += 1
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

