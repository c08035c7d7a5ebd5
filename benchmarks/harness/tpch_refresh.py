"""TPC-H's refresh functions: the update sets, the keys RF2 deletes, the
plain pandas reference of the refreshed tables, and q18 with the live
order-key bounds.

Clause 2.5.2 (RF1) inserts SF x 1,500 orders of 1 to 7 lineitems each;
Clause 2.5.3 (RF2) deletes SF x 1,500 orders and their lineitems by
order key.  Order keys are dense in this generator (not the
specification's sparse 8 of 32), so with ``n = 1,500 x SF`` orders per
set and ``N = 1,500,000 x SF`` base orders:

* update set ``i`` holds the orders of index ``N + n*i + j`` (key
  ``N + n*i + j + 1``), ``j < n``, their columns drawn by the
  benchmark's own column functions (``tpch_data._orders_cols``,
  ``_lineitem_cols``) called with numpy, so a set is made on the host
  from ``(seed, i)`` like dbgen's update files; line ``l`` of order
  ``o`` draws its values at line index ``7*o + l - 1``, past every
  base line;
* RF2 of pair ``i`` deletes the keys ``n*i + 1 ... n*i + n``: the ``n``
  oldest live orders, which after ``N / n`` pairs are orders an earlier
  RF1 inserted.

Sets are contiguous in the order index, so any run of them is made by
one call.  A pair is RF1 of set ``i``, then RF2 of pair ``i``; the
warm-up of ``drivers/refresh.py`` is pair 0.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import numpy as np
import pandas as pd

from . import compare, tpch_data

LINE_SLOTS = 7  # lines per order at most: line index 7*o + l - 1


def set_orders(scale: float) -> int:
    """Orders per update set: 1,500 x SF."""
    return max(int(1_500 * scale), 1)


def update_sets(scale: float, seed: int, first: int, count: int
                ) -> Dict[str, Dict[str, np.ndarray]]:
    """Update sets ``first .. first + count - 1`` as host columns:
    ``{"orders": {column: array}, "lineitem": {...}}``, string columns
    as codes in the base's dictionaries (``tpch_data.dictionaries``)."""
    sz, n = tpch_data.sizes(scale), set_orders(scale)
    o_idx = sz["orders"] + n * first + np.arange(n * count, dtype=np.int64)
    s = np.uint32(int(seed) % (1 << 32))
    with np.errstate(over="ignore"):
        lines = 1 + tpch_data._randint(
            np, tpch_data._salt(np, s, tpch_data._T.LINES), o_idx, 7)
        orders = tpch_data._orders_cols(np, s, o_idx, LINE_SLOTS * o_idx,
                                        lines, sz["customer"], sz["clerk"])
        order_of = np.repeat(o_idx, lines)
        first_line = np.cumsum(lines) - lines
        line = (np.arange(len(order_of)) - np.repeat(first_line, lines)
                + 1).astype(np.int32)
        lineitem = tpch_data._lineitem_cols(
            np, s, LINE_SLOTS * order_of + line - 1, order_of, line,
            sz["part"], sz["supplier"])
    return {t: {c: np.ascontiguousarray(cols[c]) for c in
                tpch_data.COLUMN_ORDER[t]}
            for t, cols in (("orders", orders), ("lineitem", lineitem))}


def rf2_keys(scale: float, i: int) -> np.ndarray:
    """The order keys RF2 of pair ``i`` deletes."""
    n = set_orders(scale)
    return np.arange(n * i + 1, n * i + n + 1, dtype=np.int32)


def live_key_range(scale: float, pairs: int) -> Tuple[int, int]:
    """The order keys live after ``pairs`` pairs: every key in
    ``[lo, hi]``, each once."""
    n = set_orders(scale)
    return n * pairs + 1, tpch_data.sizes(scale)["orders"] + n * pairs


def reference_state(base: Dict[str, pd.DataFrame], scale: float, seed: int,
                    pairs: int) -> Dict[str, pd.DataFrame]:
    """``base``'s orders and lineitem after ``pairs`` refresh pairs,
    applied in pandas: pair ``i`` concatenates update set ``i`` and then
    drops every row whose order key RF2 ``i`` names.  So a row survives
    when no RF2 at or after the pair that inserted it (the base: before
    pair 0) names its key — which ``isin`` and the last pair naming
    each key decide for all pairs at once."""
    sets = update_sets(scale, seed, 0, pairs)
    n = set_orders(scale)
    deleted = np.concatenate([rf2_keys(scale, i) for i in range(pairs)]) \
        if pairs else np.empty(0, np.int32)
    last = pd.Series(np.repeat(np.arange(pairs), n), index=deleted)
    last = last.groupby(level=0).max()
    out = {}
    for t, key in (("orders", "o_orderkey"), ("lineitem", "l_orderkey")):
        b = base[t]
        b = b[~b[key].isin(deleted)]
        new = pd.DataFrame(sets[t])
        inserted_at = (new[key].to_numpy() - tpch_data.sizes(scale)["orders"]
                       - 1) // n
        deleted_at = new[key].map(last).fillna(-1).to_numpy()
        out[t] = pd.concat([b, new[deleted_at < inserted_at]],
                           ignore_index=True)
    return out


def apply_pair(state: Dict[str, pd.DataFrame], scale: float, seed: int,
               i: int) -> Dict[str, pd.DataFrame]:
    """One pair applied literally: concat set ``i``, then drop RF2
    ``i``'s keys (the CPU tests' cross-check of ``reference_state``)."""
    sets, keys = update_sets(scale, seed, i, 1), rf2_keys(scale, i)
    out = {}
    for t, key in (("orders", "o_orderkey"), ("lineitem", "l_orderkey")):
        df = pd.concat([state[t], pd.DataFrame(sets[t])], ignore_index=True)
        out[t] = df[~df[key].isin(keys)].reset_index(drop=True)
    return out


def base_frames(scale: float, seed: int, sharding,
                tables=("orders", "lineitem", "customer")
                ) -> Dict[str, pd.DataFrame]:
    """The base tables regenerated from the seed, string columns as
    codes (``customer``'s ``c_name`` as its strings, for q18)."""
    import jax
    cols, counts = tpch_data.generate(scale, seed, sharding)
    out = {}
    for t in tables:
        cap = cols[t][tpch_data.COLUMN_ORDER[t][0]].shape[0] // len(counts[t])
        host = jax.device_get(cols[t])
        out[t] = pd.DataFrame({
            c: np.concatenate([a[p * cap:p * cap + int(k)]
                               for p, k in enumerate(counts[t])])
            for c, a in host.items()})[tpch_data.COLUMN_ORDER[t]]
    if "customer" in out:
        names = tpch_data.dictionaries(scale)["c_name"]
        out["customer"]["c_name"] = names[out["customer"]["c_name"]]
    return out


def table_arrays(dt, dictionaries) -> Dict[str, np.ndarray]:
    """A ``DTable``'s rows read back by blocks, string columns re-coded
    onto ``dictionaries`` (a value they lack reads -1)."""
    import jax
    counts = dt.counts_host()
    host = jax.device_get([c.data for c in dt.columns])
    out = {}
    for c, a in zip(dt.columns, host):
        v = np.concatenate([a[p * dt.cap:p * dt.cap + int(k)]
                            for p, k in enumerate(counts)])
        if c.name in dictionaries:
            want = dictionaries[c.name]
            pos = np.minimum(np.searchsorted(want, c.dictionary),
                             max(len(want) - 1, 0))
            remap = np.where(want[pos] == c.dictionary, pos, -1)
            v = remap.astype(np.int32)[v]
        out[c.name] = v
    return out


def rows_mismatch(got: Dict[str, np.ndarray], want: pd.DataFrame) -> int:
    """Rows of either side that the other lacks (row-hash multisets)."""
    cols = list(want.columns)
    if sorted(got) != sorted(cols):
        return max(len(want), 1)
    return compare.multiset_mismatch(
        compare.row_hashes([got[c] for c in cols]),
        compare.row_hashes([want[c].to_numpy() for c in cols]))


def q18(ctx, t, orders_range, quantity: float = 300.0, limit: int = 100):
    """``tpch_queries.q18`` with the orders' dense key range given: the
    live bounds of a refreshed table, where ``(1, num_rows)`` is false."""
    from cylon_tpu.config import JoinType
    from cylon_tpu.parallel import (dist_groupby, dist_head, dist_join,
                                    dist_project, dist_select,
                                    dist_sort_multi)

    from .tpch_queries import _cfg, _gt, _pk1, _strip
    li = dist_project(t["lineitem"], ["l_orderkey", "l_quantity"])
    per_order = dist_groupby(li, ["l_orderkey"], [("l_quantity", "sum")],
                             dense_key_range=orders_range)
    big = dist_select(per_order, _gt("sum_l_quantity", quantity))
    orders = dist_project(t["orders"], ["o_orderkey", "o_custkey",
                                        "o_orderdate", "o_totalprice"])
    m = _strip(dist_join(big, orders,
                         _cfg("l_orderkey", "o_orderkey", JoinType.LEFT),
                         dense_key_range=orders_range))
    m = _strip(dist_join(m, dist_project(t["customer"],
                                         ["c_custkey", "c_name"]),
                         _cfg("o_custkey", "c_custkey", JoinType.LEFT),
                         dense_key_range=_pk1(t, "customer")))
    m = dist_project(m, ["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                         "o_totalprice", "sum_l_quantity"])
    s = dist_sort_multi(m, ["o_totalprice", "o_orderdate"],
                        ascending=[False, True])
    return dist_head(s, limit)


@functools.lru_cache(maxsize=None)
def q18_op(ctx, orders_range, quantity: float = 300.0):
    """One callable per bounds, so the planner's cache sees one plan."""
    return functools.partial(q18, ctx, orders_range=orders_range,
                             quantity=quantity)
