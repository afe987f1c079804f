"""The plain reference: a full numpy scan of the benchmark's own data.

A query is a plain tuple tree, independent of the program's predicate
classes:

    ("atom", column, op, value)        op in lt le gt ge eq ne in not_in
    ("and", (child, ...)) / ("or", (child, ...))

Columns are numpy arrays, or :class:`Categorical` (codes + vocabulary) for
string columns, which the reference evaluates on the vocabulary and maps
back through the codes.  Nothing here imports the program or reads
anything it made.

``dtype`` selects the precision numeric columns and constants are compared
in.  ``None`` is exact (the deployment's own values).  ``"bfloat16"`` is
the control: the nearest precision below the float32 the configurations
state, which must come out as not correct.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Mapping, Optional

import numpy as np

_CMP = {"lt": np.less, "le": np.less_equal, "gt": np.greater,
        "ge": np.greater_equal, "eq": np.equal, "ne": np.not_equal}


@dataclass(frozen=True)
class Categorical:
    """A dictionary-encoded string column: ``vocab[codes]`` is the column."""

    codes: np.ndarray           # int32 per row
    vocab: np.ndarray           # the distinct strings

    def __len__(self) -> int:
        return len(self.codes)

    def strings(self, start: int = 0, stop: Optional[int] = None):
        return self.vocab[self.codes[start:stop]]


def _lower(values: np.ndarray, dtype: Optional[str]) -> np.ndarray:
    if dtype is None:
        return values
    import ml_dtypes
    return np.asarray(values, dtype=np.float32).astype(
        getattr(ml_dtypes, dtype))


def eval_atom(op: str, value, col: np.ndarray,
              dtype: Optional[str] = None) -> np.ndarray:
    """``col OP value`` on every element of ``col``."""
    if op in ("in", "not_in"):
        hit = np.isin(col, np.asarray(list(value)))
        return hit if op == "in" else ~hit
    if col.dtype.kind in "US":
        return _CMP[op](col, value)
    return _CMP[op](_lower(col, dtype), _lower(np.asarray(value), dtype))


def eval_mask(spec, columns: Mapping, n: int,
              dtype: Optional[str] = None) -> np.ndarray:
    """Boolean mask of the first ``n`` rows of ``columns`` satisfying
    ``spec``."""
    kind = spec[0]
    if kind == "atom":
        _, name, op, value = spec
        col = columns[name]
        if isinstance(col, Categorical):
            return eval_atom(op, value, col.vocab)[col.codes[:n]]
        return eval_atom(op, value, col[:n], dtype)
    parts = [eval_mask(c, columns, n, dtype) for c in spec[1]]
    return (np.logical_and if kind == "and" else np.logical_or).reduce(parts)


def pack(mask: np.ndarray) -> np.ndarray:
    """Packed ``u32`` words: record ``r`` is word ``r // 32``, bit
    ``r % 32``."""
    raw = np.packbits(mask, bitorder="little")
    raw = np.concatenate([raw, np.zeros(-len(raw) % 4, dtype=np.uint8)])
    return raw.view("<u4")


def reference_bitmap(spec, columns: Mapping, n: int,
                     live: Optional[np.ndarray] = None,
                     dtype: Optional[str] = None) -> np.ndarray:
    """Packed words of the first ``n`` rows that satisfy ``spec`` and are
    ``live`` (a boolean mask over those rows; None means all live)."""
    mask = eval_mask(spec, columns, n, dtype)
    if live is not None:
        mask = mask & live[:n]
    return pack(mask)


def wrong_rows(got: np.ndarray, want: np.ndarray) -> int:
    """Rows on which two packed bitmaps disagree; a length mismatch counts
    every row of the longer one's extra words."""
    got = np.asarray(got, dtype=np.uint32).ravel()
    want = np.asarray(want, dtype=np.uint32).ravel()
    n = max(len(got), len(want))
    a = np.zeros(n, np.uint32)
    b = np.zeros(n, np.uint32)
    a[:len(got)] = got
    b[:len(want)] = want
    return int(np.unpackbits((a ^ b).view(np.uint8)).sum())


def columns_of(spec) -> set:
    """The column names ``spec`` reads."""
    if spec[0] == "atom":
        return {spec[1]}
    out = set()
    for c in spec[1]:
        out |= columns_of(c)
    return out
