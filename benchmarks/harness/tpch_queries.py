"""The TPC-H queries the benchmark runs, written against the engine's
public operators (the benchmark's copy of ``cylon_tpu/tpch/queries.py``
for q1, q3, q6, q18 and q21, so that a change to the program cannot
change the work it is measured on).  q18 and q21 return the name
columns that the specification selects and the repo's copies leave out
(``c_name``; ``s_name`` in place of the supplier key).

Parameters are the TPC-H validation values (Clause 2.4).  Predicates
come from cached factories: one closure per literal, so repeating a
query reuses its compiled kernels.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from cylon_tpu import JoinConfig
from cylon_tpu.compute import sort_multi
from cylon_tpu.config import JoinAlgorithm, JoinType
from cylon_tpu.dtypes import Type
from cylon_tpu.parallel import (dist_aggregate, dist_groupby, dist_head,
                                dist_join, dist_project, dist_select,
                                dist_semi_join, dist_sort, dist_sort_multi,
                                dist_with_column)

from .tpch_data import NATIONS, date_to_days


def _cfg(lkey, rkey, how=JoinType.INNER):
    return JoinConfig(how, JoinAlgorithm.SORT, lkey, rkey)


def _strip(dt):
    names = []
    for n in dt.column_names:
        while n.startswith(("lt-", "rt-")):
            n = n[3:]
        names.append(n)
    return dt.rename(names)


def _code(dt, column: str, value: str) -> int:
    """Dictionary code of a string literal (-1 matches nothing)."""
    import numpy as np
    d = dt.column(column).dictionary
    pos = int(np.searchsorted(d, value))
    return pos if pos < len(d) and d[pos] == value else -1


def _pk1(t, table: str):
    return (1, t[table].num_rows)


@functools.lru_cache(maxsize=None)
def _lt(col, v):
    return lambda env: env[col] < v


@functools.lru_cache(maxsize=None)
def _le(col, v):
    return lambda env: env[col] <= v


@functools.lru_cache(maxsize=None)
def _gt(col, v):
    return lambda env: env[col] > v


@functools.lru_cache(maxsize=None)
def _eq(col, v):
    return lambda env: env[col] == v


@functools.lru_cache(maxsize=None)
def _q6_pred(d0, d1, dlo, dhi, q):
    return lambda env: ((env["l_shipdate"] >= d0) & (env["l_shipdate"] < d1)
                        & (env["l_discount"] >= dlo)
                        & (env["l_discount"] <= dhi)
                        & (env["l_quantity"] < q))


def _revenue(env):
    return env["l_extendedprice"] * (1.0 - env["l_discount"])


def _charge(env):
    return (env["l_extendedprice"] * (1.0 - env["l_discount"])
            * (1.0 + env["l_tax"]))


def _disc_rev(env):
    return env["l_extendedprice"] * env["l_discount"]


def _late(env):
    return (env["l_receiptdate"] > env["l_commitdate"]).astype(jnp.int32)


def _q21_cand(env):
    return (env["count_l_suppkey"] >= 2) & (env["sum_max_late"] == 1)


def q1(ctx, t, delta_days: int = 90):
    cutoff = date_to_days("1998-12-01") - delta_days
    li = dist_project(t["lineitem"], [
        "l_shipdate", "l_returnflag", "l_linestatus", "l_quantity",
        "l_extendedprice", "l_discount", "l_tax", "l_orderkey"])
    li = dist_with_column(li, "disc_price", _revenue, Type.DOUBLE)
    li = dist_with_column(li, "charge", _charge, Type.DOUBLE)
    g = dist_groupby(li, ["l_returnflag", "l_linestatus"], [
        ("l_quantity", "sum"), ("l_extendedprice", "sum"),
        ("disc_price", "sum"), ("charge", "sum"),
        ("l_quantity", "mean"), ("l_extendedprice", "mean"),
        ("l_discount", "mean"), ("l_orderkey", "count"),
    ], where=_le("l_shipdate", cutoff))
    return sort_multi(g.to_table(), ["l_returnflag", "l_linestatus"])


def q3(ctx, t, segment: str = "BUILDING", date: str = "1995-03-15",
       limit: int = 10):
    day = date_to_days(date)
    seg = _code(t["customer"], "c_mktsegment", segment)
    cust = dist_select(dist_project(t["customer"],
                                    ["c_custkey", "c_mktsegment"]),
                       _eq("c_mktsegment", seg))
    orders = dist_select(dist_project(t["orders"],
                                      ["o_orderkey", "o_custkey",
                                       "o_orderdate", "o_shippriority"]),
                         _lt("o_orderdate", day), compact=False)
    li = dist_select(dist_project(t["lineitem"],
                                  ["l_orderkey", "l_shipdate",
                                   "l_extendedprice", "l_discount"]),
                     _gt("l_shipdate", day), compact=False)
    co = _strip(dist_join(orders, cust, _cfg("o_custkey", "c_custkey"),
                          dense_key_range=_pk1(t, "customer")))
    col = _strip(dist_join(li, co, _cfg("l_orderkey", "o_orderkey"),
                           dense_key_range=_pk1(t, "orders")))
    col = dist_with_column(col, "volume", _revenue, Type.DOUBLE)
    g = dist_groupby(col, ["l_orderkey", "o_orderdate", "o_shippriority"],
                     [("volume", "sum")])
    return dist_head(dist_sort(g, "sum_volume", ascending=False), limit)


def q6(ctx, t, date: str = "1994-01-01", discount: float = 0.06,
       quantity: float = 24.0):
    d0 = date_to_days(date)
    li = dist_with_column(t["lineitem"], "rev", _disc_rev, Type.DOUBLE)
    return dist_aggregate(li, [("rev", "sum")],
                          where=_q6_pred(d0, d0 + 365, discount - 0.011,
                                         discount + 0.011, quantity))


def q18(ctx, t, quantity: float = 300.0, limit: int = 100):
    li = dist_project(t["lineitem"], ["l_orderkey", "l_quantity"])
    per_order = dist_groupby(li, ["l_orderkey"], [("l_quantity", "sum")],
                             dense_key_range=_pk1(t, "orders"))
    big = dist_select(per_order, _gt("sum_l_quantity", quantity))
    orders = dist_project(t["orders"], ["o_orderkey", "o_custkey",
                                        "o_orderdate", "o_totalprice"])
    m = _strip(dist_join(big, orders,
                         _cfg("l_orderkey", "o_orderkey", JoinType.LEFT),
                         dense_key_range=_pk1(t, "orders")))
    m = _strip(dist_join(m, dist_project(t["customer"],
                                         ["c_custkey", "c_name"]),
                         _cfg("o_custkey", "c_custkey", JoinType.LEFT),
                         dense_key_range=_pk1(t, "customer")))
    m = dist_project(m, ["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                         "o_totalprice", "sum_l_quantity"])
    s = dist_sort_multi(m, ["o_totalprice", "o_orderdate"],
                        ascending=[False, True])
    return dist_head(s, limit)


def q21(ctx, t, nation: str = "SAUDI ARABIA", limit: int = 100):
    sk = [n for n, _ in NATIONS].index(nation)
    fcode = _code(t["orders"], "o_orderstatus", "F")
    orders_f = dist_project(
        dist_select(dist_project(t["orders"], ["o_orderkey",
                                               "o_orderstatus"]),
                    _eq("o_orderstatus", fcode), compact=False),
        ["o_orderkey"])
    li = dist_project(t["lineitem"], ["l_orderkey", "l_suppkey",
                                      "l_commitdate", "l_receiptdate"])
    li = dist_semi_join(li, orders_f, "l_orderkey", "o_orderkey",
                        dense_key_range=_pk1(t, "orders"))
    li = dist_with_column(li, "late", _late, Type.INT32)
    per_os = dist_groupby(li, ["l_orderkey", "l_suppkey"], [("late", "max")])
    per_o = dist_groupby(per_os, ["l_orderkey"],
                         [("l_suppkey", "count"), ("max_late", "sum")],
                         dense_key_range=_pk1(t, "orders"))
    cand = dist_select(per_o, _q21_cand, compact=False)
    supp = dist_project(
        dist_select(dist_project(t["supplier"], ["s_suppkey",
                                                 "s_nationkey"]),
                    _eq("s_nationkey", sk)), ["s_suppkey"])
    l1 = dist_select(li, _eq("late", 1), compact=False)
    l1 = dist_semi_join(l1, supp, "l_suppkey", "s_suppkey",
                        dense_key_range=_pk1(t, "supplier"))
    l1 = dist_semi_join(l1, cand, "l_orderkey", "l_orderkey",
                        dense_key_range=_pk1(t, "orders"))
    g = dist_groupby(l1, ["l_suppkey"], [("l_suppkey", "count")])
    # s_name is "Supplier#" and the key in nine digits: its order is the key's
    g = _strip(dist_join(g, dist_project(t["supplier"],
                                         ["s_suppkey", "s_name"]),
                         _cfg("l_suppkey", "s_suppkey"),
                         dense_key_range=_pk1(t, "supplier")))
    g = dist_sort_multi(g, ["count_l_suppkey", "l_suppkey"],
                        ascending=[False, True])
    g = dist_project(g, ["s_name", "count_l_suppkey"])
    return dist_head(g, limit).rename_column("count_l_suppkey", "numwait")


QUERIES = {"q1": q1, "q3": q3, "q6": q6, "q18": q18, "q21": q21}
