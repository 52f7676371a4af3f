"""The plain reference of an aggregate over an inner equi-join of two tables.

Filter each side, inner merge, computed inputs (``CASE`` included), group,
aggregate: pandas and NumPy, float64, no index, no cache, nothing imported
from ``hyperspace_tpu.exec``. What the resident join-aggregate tier
(``hyperspace_tpu/exec/join_agg.py``) is held to in
``tests/test_join_agg_resident.py`` and ``tests/test_q12_resident.py``.

SQL's rules, spelled out where pandas has others:

- a NULL key (``None``, ``NaN``, ``NaT``) matches nothing, not another NULL;
- a comparison with a NULL operand is unknown, and only rows whose filter is
  definitely true go on; a ``CASE`` takes the first branch whose condition is
  definitely true, else its ``ELSE``, else NULL;
- ``count(*)`` counts rows, ``count(x)``/``sum``/``avg``/``min``/``max`` skip
  NULLs, and ``sum``/``avg``/``min``/``max`` over nothing are NULL (NaN);
- a NULL group key is a group of its own.
"""

from __future__ import annotations

import numpy as np
import pandas as pd


def eq(values, literal) -> np.ndarray:
    """``values = literal``, definitely true (a NULL is unknown: false here)."""
    return np.asarray([v is not None and v == v and v == literal for v in np.asarray(values, dtype=object)], dtype=bool)


def ne(values, literal) -> np.ndarray:
    """``values != literal``, definitely true (a NULL is unknown: false here)."""
    return np.asarray([v is not None and v == v and v != literal for v in np.asarray(values, dtype=object)], dtype=bool)


def case(n: int, branches, otherwise=None) -> np.ndarray:
    """``CASE WHEN c1 THEN v1 ... ELSE otherwise END`` over ``n`` rows:
    ``branches`` is ``[(bool mask, value)]``; without ``otherwise`` NULL (NaN,
    so float64)."""
    out = np.full(n, np.nan) if otherwise is None else np.broadcast_to(np.asarray(otherwise), (n,)).copy()
    taken = np.zeros(n, dtype=bool)
    for mask, value in branches:
        hit = np.asarray(mask, dtype=bool) & ~taken
        if otherwise is None:
            out = out.astype(np.float64)
        out = np.where(hit, np.broadcast_to(np.asarray(value), (n,)), out)
        taken |= hit
    return out


def join_aggregate(left: pd.DataFrame, right: pd.DataFrame, on, *, left_filter=None, right_filter=None,
                   computes=None, keys=(), aggs=()) -> dict:
    """``aggs`` (``(name, fn, column or None)``) grouped by ``keys`` over
    ``left`` inner-joined to ``right`` on ``on = (left key, right key)``.
    Filters and computes are callables of a frame giving a bool mask, or a
    column. The answer: ``{column: array}``, groups sorted by ``keys`` (NULL
    last); one row without keys."""
    lkey, rkey = on
    if left_filter is not None:
        left = left[np.asarray(left_filter(left), dtype=bool)]
    if right_filter is not None:
        right = right[np.asarray(right_filter(right), dtype=bool)]
    left = left[~pd.isna(left[lkey]).to_numpy()]
    right = right[~pd.isna(right[rkey]).to_numpy()]
    joined = left.merge(right, left_on=lkey, right_on=rkey, how="inner")
    for name, fn in (computes or {}).items():
        joined[name] = fn(joined)

    def fold(frame: pd.DataFrame, fn: str, column):
        if fn == "count" and column is None:
            return len(frame)
        values = frame[column].to_numpy()
        live = values[~pd.isna(values)]
        if fn == "count":
            return len(live)
        if len(live) == 0:
            return np.nan
        if fn == "sum":
            return live.sum() if live.dtype.kind in "iub" else float(live.astype(np.float64).sum())
        if fn == "avg":
            return float(live.astype(np.float64).mean())
        return live.min() if fn == "min" else live.max()

    keys = list(keys)
    if not keys:
        return {name: np.asarray([fold(joined, fn, column)]) for name, fn, column in aggs}
    out = {k: [] for k in keys}
    out.update({name: [] for name, _, _ in aggs})
    for values, frame in joined.groupby(keys, dropna=False, sort=True):
        values = values if isinstance(values, tuple) else (values,)
        for k, v in zip(keys, values):
            out[k].append(None if pd.isna(v) else v)
        for name, fn, column in aggs:
            out[name].append(fold(frame, fn, column))
    return {k: np.asarray(v, dtype=object if k in keys else None) for k, v in out.items()}
