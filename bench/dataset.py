"""What a data generator (``bench/data/<generator>.py``) hands the harness.

A generator module defines ``build(config, seed) -> Dataset``.  The
dataset holds the benchmark's own copy of every column, which the
reference reads; the program gets its table from :meth:`table_columns`.
Appends and deletes a traffic mix asks for are recorded as a list of
mutations, so the reference can rebuild the table as it stood after any
prefix of them.
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Optional, Tuple

import numpy as np

from bench.reference import Categorical


class Dataset:
    """Columns made from the seed, plus the mutations applied since."""

    def __init__(self, columns: Dict[str, object], n: int):
        for col in columns.values():
            arr = col.codes if isinstance(col, Categorical) else col
            arr.flags.writeable = False
        self.columns = columns
        self.n = n
        #: ("append", {name: chunk}) or ("delete", row indices), in order
        self.mutations: List[Tuple[str, object]] = []

    # -- what the program is given --------------------------------------------
    def table_columns(self) -> Dict[str, np.ndarray]:
        return {k: _plain(v) for k, v in self.columns.items()}

    @staticmethod
    def rows_for_table(rows: Dict[str, object]) -> Dict[str, np.ndarray]:
        return {k: _plain(v) for k, v in rows.items()}

    # -- query families and mutations: generators override ---------------------
    def family(self, name: str, params: dict, rng: np.random.Generator):
        """A function ``draw(rng) -> query`` for the named query family."""
        raise KeyError(f"{type(self).__name__} has no query family {name!r}")

    def mutation(self, name: str, rng: np.random.Generator
                 ) -> Tuple[str, object]:
        """The next ``("append", rows)`` or ``("delete", indices)`` of the
        named refresh function."""
        raise KeyError(f"{type(self).__name__} has no mutation {name!r}")

    def distinct(self, column: str) -> int:
        """Distinct values of ``column`` in the generated data."""
        col = self.columns[column]
        if isinstance(col, Categorical):
            return len(np.unique(col.codes))
        return len(np.unique(col))

    # -- the table at a given state, for the reference -------------------------
    def state(self, k: int) -> Tuple[Mapping[str, object], int,
                                      Optional[np.ndarray]]:
        """``(columns, n_rows, live)`` after the first ``k`` mutations
        (``live`` is None while nothing is deleted).  A column is put
        together from its appended chunks when it is first read, so a
        state costs only the columns its queries read."""
        chunks: Dict[str, list] = {name: [] for name in self.columns}
        n = self.n
        dead: List[np.ndarray] = []
        for kind, payload in self.mutations[:k]:
            if kind == "append":
                for name, chunk in payload.items():
                    chunks[name].append(chunk)
                n += len(next(iter(payload.values())))
            else:
                dead.append(np.asarray(payload))
        live = None
        if dead:
            live = np.ones(n, dtype=bool)
            live[np.concatenate(dead)] = False
        return _State(self.columns, chunks), n, live


class _State(Mapping):
    """The columns of one table state, each concatenated when first read."""

    def __init__(self, base: Dict[str, object], chunks: Dict[str, list]):
        self._base, self._chunks, self._done = base, chunks, {}

    def __getitem__(self, name: str):
        if name not in self._done:
            col, more = self._base[name], self._chunks[name]
            if not more:
                self._done[name] = col
            elif isinstance(col, Categorical):
                self._done[name] = Categorical(
                    np.concatenate([col.codes] + [c.codes for c in more]),
                    col.vocab)
            else:
                self._done[name] = np.concatenate([col] + more)
        return self._done[name]

    def __iter__(self):
        return iter(self._base)

    def __len__(self) -> int:
        return len(self._base)


def _plain(col) -> np.ndarray:
    return col.strings() if isinstance(col, Categorical) else col
