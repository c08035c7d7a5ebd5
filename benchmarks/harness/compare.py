"""The comparison that decides ``correct``: answers against the plain
reference, reduced to a few numbers, each held to a limit.

* ``wrong_rows``: rows of an answer that the reference does not have, or
  has with another key, count or other non-float value; plus rows the
  reference has and the answer lacks.  Exact: its limit is 0.
* ``rel_err``: the largest relative error of a float aggregate,
  ``|got - want| / |want|``, over every compared answer.
* ``missing``: answers that were due and never came.  Limit 0.

A number above its limit makes the run not correct.
"""
from __future__ import annotations

from typing import Dict, Iterable, List

import numpy as np
import pandas as pd


def _floats(df: pd.DataFrame) -> List[str]:
    return [c for c in df.columns if pd.api.types.is_float_dtype(df[c])]


def _comparable(s: pd.Series) -> pd.Series:
    if pd.api.types.is_numeric_dtype(s) and not isinstance(
            s.dtype, pd.CategoricalDtype):
        return s
    return s.astype(str).astype(object)


def compare_answer(got: pd.DataFrame, want: pd.DataFrame, keys) -> Dict:
    """``got`` against ``want`` (same column names): rows matched on
    ``keys`` (positionally where there are none)."""
    if list(got.columns) != list(want.columns):
        return {"wrong_rows": max(len(got), len(want), 1), "rel_err": 0.0}
    got = got.reset_index(drop=True).apply(_comparable)
    want = want.reset_index(drop=True).apply(_comparable)
    floats = _floats(want)
    others = [c for c in want.columns if c not in floats and c not in keys]
    if keys:
        m = got.merge(want, on=list(keys), how="outer",
                      suffixes=("_g", "_w"), indicator=True)
        wrong = int((m["_merge"] != "both").sum())
        m = m[m["_merge"] == "both"]
        pairs = [(m[f"{c}_g"].to_numpy(), m[f"{c}_w"].to_numpy())
                 for c in floats + others]
    else:
        n = min(len(got), len(want))
        wrong = abs(len(got) - len(want))
        pairs = [(got[c].to_numpy()[:n], want[c].to_numpy()[:n])
                 for c in floats + others]
    rows = len(pairs[0][0]) if pairs else 0
    bad = np.zeros(rows, bool)
    rel = 0.0
    for (g, w), c in zip(pairs, floats + others):
        if c in floats:
            g, w = g.astype(np.float64), w.astype(np.float64)
            err = np.abs(g - w) / np.where(w != 0, np.abs(w), 1.0)
            err = np.where(np.isnan(err), np.inf, err)
            if len(err):
                rel = max(rel, float(err.max()))
        else:
            bad |= g != w
    return {"wrong_rows": wrong + int(bad.sum()), "rel_err": rel}


def row_hashes(cols: Iterable[np.ndarray]) -> np.ndarray:
    """A 64-bit mix of each row's bit patterns (equal rows, equal hash;
    a collision between different rows has odds of about 2**-64)."""
    h = None
    for c in cols:
        c = np.ascontiguousarray(c)
        bits = c.view(f"u{c.dtype.itemsize}").astype(np.uint64)
        h = bits * np.uint64(0x9E3779B97F4A7C15) if h is None else \
            (h ^ bits) * np.uint64(0x9E3779B97F4A7C15)
        h ^= h >> np.uint64(29)
    return h


def multiset_mismatch(got: np.ndarray, want: np.ndarray) -> int:
    """Rows of either side that the other lacks, as multisets of row
    hashes: 0 exactly when the two hold the same rows."""
    gv, gc = np.unique(got, return_counts=True)
    wv, wc = np.unique(want, return_counts=True)
    _, gi, wi = np.intersect1d(gv, wv, assume_unique=True,
                               return_indices=True)
    common = int(np.minimum(gc[gi], wc[wi]).sum())
    return len(got) + len(want) - 2 * common


def merge(totals: Dict, new: Dict) -> Dict:
    """Worst of two readings: sums for counts, maximum for errors."""
    out = dict(totals)
    for k, v in new.items():
        if k == "rel_err":
            out[k] = max(out.get(k, 0.0), v)
        else:
            out[k] = out.get(k, 0) + v
    return out


def verdict(numbers: Dict, limits: Dict) -> bool:
    return all(numbers.get(k, 0) <= lim for k, lim in limits.items())


def report(numbers: Dict, limits: Dict) -> Dict:
    """``{name: {"value": v, "limit": l}}``: each number beside its
    limit, as the result line and standard error print them."""
    return {k: {"value": numbers.get(k, 0), "limit": lim}
            for k, lim in limits.items()}
