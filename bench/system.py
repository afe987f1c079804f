"""The system under test, as the harness drives it.

:class:`ProgramSystem` opens the program's ``StreamSession`` with its
background drainer exactly as a user does: the session's own
``DEFAULT_CONFIG``, default ``DrainPolicy`` and ``max_pending``, with one
replacement, ``trace=``, a benchmark-owned ``Tracer`` whose ring holds the
whole run.  The harness talks to it through four calls (``submit``,
``mutate``, ``counters``, ``close``), so a test or the control can put
something else in its place.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

#: spans the ring can hold; a run records about ten per drain
TRACE_CAPACITY = 2_000_000


def to_predicate(spec):
    """The program's predicate tree for a plain query tuple."""
    from repro.core.predicate import And, Atom, Or
    if spec[0] == "atom":
        _, column, op, value = spec
        return Atom(column, op, value)
    cls = And if spec[0] == "and" else Or
    return cls([to_predicate(c) for c in spec[1]])


class Handle:
    """One submitted request's answer, as the program reports it."""

    __slots__ = ("_fut",)

    def __init__(self, fut):
        self._fut = fut

    @property
    def id(self) -> int:
        return self._fut.id

    def done(self) -> bool:
        return self._fut.done()

    def result(self) -> np.ndarray:
        return self._fut.result(timeout=0)

    @property
    def snapshot(self):
        """``(n_records, live_words or None)`` the answer was computed at."""
        return self._fut.snapshot


class ProgramSystem:
    """The program's served path: a background-drained ``StreamSession``."""

    def __init__(self, data):
        from repro.columnar.stream import StreamSession
        from repro.columnar.table import Table
        from repro.columnar.trace import Tracer
        self.tracer = Tracer(capacity=TRACE_CAPACITY)
        self.table = Table(data.table_columns())
        self.session = StreamSession(
            self.table,
            config=StreamSession.DEFAULT_CONFIG.replace(trace=self.tracer),
            background=True)

    def submit(self, spec) -> Handle:
        return Handle(self.session.submit(to_predicate(spec)))

    def mutate(self, kind: str, payload) -> None:
        if kind == "append":
            from bench.dataset import Dataset
            self.session.append(Dataset.rows_for_table(payload))
        else:
            self.session.delete(payload)

    def counters(self) -> Optional[Dict[str, float]]:
        """Lifetime counters of the device backend the drains run on, and
        the session's completed, degraded, retried and quarantined counts
        (None before the first drain)."""
        from repro.columnar.trace import backend_counters
        res = self.session.last_result
        if res is None:
            return None
        be = res.backend
        out = backend_counters(be)
        out["records_evaluated"] = float(be.stats.records_evaluated)
        st = self.session.stats
        out["completed"] = float(st.completed)
        out["degraded_batches"] = float(st.degraded_batches)
        out["retries"] = float(st.retries)
        out["quarantined"] = float(st.quarantined_queries)
        out["backend"] = id(be)
        return out

    def spans(self) -> list:
        return self.tracer.recent()

    def spans_dropped(self) -> bool:
        return len(self.tracer) >= TRACE_CAPACITY

    def close(self) -> None:
        self.session.close()
