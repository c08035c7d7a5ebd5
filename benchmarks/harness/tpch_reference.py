"""Plain pandas reference for the benchmark's TPC-H queries, written
from the TPC-H text over the generated tables (the benchmark's copy of
``cylon_tpu/tpch/reference.py`` for q1, q3, q6, q18 and q21).  It
imports nothing of the program.

``answer(q, data, control=True)`` is the control: the same query with
every float input and every float answer rounded to bfloat16, the next
precision below the float32 that the configuration states.  The
comparison has to find it wrong.
"""
from __future__ import annotations

import numpy as np
import pandas as pd

from .tpch_data import date_to_days

# engine result column -> reference column, per query; float columns are
# the aggregates held to a relative tolerance, the rest must be equal
COLUMNS = {
    "q1": (("l_returnflag", "l_returnflag"), ("l_linestatus", "l_linestatus"),
           ("sum_l_quantity", "sum_qty"), ("sum_l_extendedprice", "sum_base"),
           ("sum_disc_price", "sum_disc"), ("sum_charge", "sum_charge"),
           ("mean_l_quantity", "avg_qty"),
           ("mean_l_extendedprice", "avg_price"),
           ("mean_l_discount", "avg_disc"), ("count_l_orderkey", "n")),
    "q3": (("l_orderkey", "l_orderkey"), ("o_orderdate", "o_orderdate"),
           ("o_shippriority", "o_shippriority"), ("sum_volume", "volume")),
    "q6": (("sum_rev", "sum_rev"),),
    "q18": (("c_name", "c_name"), ("c_custkey", "c_custkey"),
            ("o_orderkey", "o_orderkey"), ("o_orderdate", "o_orderdate"),
            ("o_totalprice", "o_totalprice"),
            ("sum_l_quantity", "l_quantity")),
    "q21": (("s_name", "s_name"), ("numwait", "numwait")),
}
KEYS = {"q1": ["l_returnflag", "l_linestatus"], "q3": ["l_orderkey"],
        "q6": [], "q18": ["o_orderkey"], "q21": ["s_name"]}

# the input columns each query reads, by table
INPUTS = {
    "q1": {"lineitem": ["l_shipdate", "l_returnflag", "l_linestatus",
                        "l_quantity", "l_extendedprice", "l_discount",
                        "l_tax", "l_orderkey"]},
    "q3": {"customer": ["c_custkey", "c_mktsegment"],
           "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                      "o_shippriority"],
           "lineitem": ["l_orderkey", "l_shipdate", "l_extendedprice",
                        "l_discount"]},
    "q6": {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                        "l_extendedprice"]},
    "q18": {"lineitem": ["l_orderkey", "l_quantity"],
            "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                       "o_totalprice"],
            "customer": ["c_custkey", "c_name"]},
    "q21": {"orders": ["o_orderkey", "o_orderstatus"],
            "lineitem": ["l_orderkey", "l_suppkey", "l_commitdate",
                         "l_receiptdate"],
            "supplier": ["s_suppkey", "s_nationkey", "s_name"]},
}


def inputs(queries):
    """The union of ``INPUTS`` over ``queries``: {table: [column, ...]}."""
    out = {}
    for q in queries:
        for t, cs in INPUTS[q].items():
            out.setdefault(t, set()).update(cs)
    return out


def _bf16(x):
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float32)


def _rev(df):
    return (df["l_extendedprice"].astype(np.float64)
            * (1.0 - df["l_discount"].astype(np.float64)))


def _q1(d):
    li = d["lineitem"]
    li = li[li["l_shipdate"] <= date_to_days("1998-12-01") - 90].copy()
    li["disc_price"] = _rev(li)
    li["charge"] = li["disc_price"] * (1.0 + li["l_tax"])
    return li.groupby(["l_returnflag", "l_linestatus"], observed=True) \
        .agg(sum_qty=("l_quantity", "sum"),
             sum_base=("l_extendedprice", "sum"),
             sum_disc=("disc_price", "sum"),
             sum_charge=("charge", "sum"),
             avg_qty=("l_quantity", "mean"),
             avg_price=("l_extendedprice", "mean"),
             avg_disc=("l_discount", "mean"),
             n=("l_orderkey", "count")).reset_index()


def _q3(d):
    day = date_to_days("1995-03-15")
    c, o, li = d["customer"], d["orders"], d["lineitem"]
    c = c[c["c_mktsegment"] == "BUILDING"]
    o = o[o["o_orderdate"] < day]
    li = li[li["l_shipdate"] > day].copy()
    li["volume"] = _rev(li)
    m = c.merge(o, left_on="c_custkey", right_on="o_custkey") \
        .merge(li, left_on="o_orderkey", right_on="l_orderkey")
    return m.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                     observed=True)["volume"].sum().reset_index() \
        .sort_values("volume", ascending=False).head(10)


def _q6(d):
    d0 = date_to_days("1994-01-01")
    li = d["lineitem"]
    f = li[(li["l_shipdate"] >= d0) & (li["l_shipdate"] < d0 + 365)
           & (li["l_discount"] >= 0.06 - 0.011)
           & (li["l_discount"] <= 0.06 + 0.011)
           & (li["l_quantity"] < 24)]
    rev = float((f["l_extendedprice"].astype(np.float64)
                 * f["l_discount"].astype(np.float64)).sum())
    return pd.DataFrame({"sum_rev": [rev]})


def _q18(d):
    li = d["lineitem"]
    per = li.groupby("l_orderkey")["l_quantity"].sum().reset_index()
    big = per[per["l_quantity"] > 300.0]
    m = big.merge(d["orders"], left_on="l_orderkey", right_on="o_orderkey")
    m = m.merge(d["customer"], left_on="o_custkey", right_on="c_custkey")
    return m.sort_values(["o_totalprice", "o_orderdate"],
                         ascending=[False, True]).head(100)


def _q21(d):
    o = d["orders"]
    fkeys = o[o["o_orderstatus"] == "F"]["o_orderkey"]
    li = d["lineitem"]
    li = li[li["l_orderkey"].isin(fkeys)].copy()
    li["late"] = (li["l_receiptdate"] > li["l_commitdate"]).astype(int)
    per_os = li.groupby(["l_orderkey", "l_suppkey"])["late"].max() \
        .reset_index(name="any_late")
    per_o = per_os.groupby("l_orderkey").agg(
        n_supp=("l_suppkey", "count"), n_late=("any_late", "sum")) \
        .reset_index()
    cand = per_o[(per_o["n_supp"] >= 2) & (per_o["n_late"] == 1)]
    s = d["supplier"]
    s = s[s["s_nationkey"] == 20]  # SAUDI ARABIA
    l1 = li[(li["late"] == 1) & li["l_suppkey"].isin(s["s_suppkey"])
            & li["l_orderkey"].isin(cand["l_orderkey"])]
    g = l1.groupby("l_suppkey").size().reset_index(name="numwait") \
        .merge(s, left_on="l_suppkey", right_on="s_suppkey")
    g["s_name"] = g["s_name"].astype(str)
    return g.sort_values(["numwait", "s_name"],
                         ascending=[False, True]).head(100)


_FNS = {"q1": _q1, "q3": _q3, "q6": _q6, "q18": _q18, "q21": _q21}


def answer(q: str, data, control: bool = False) -> pd.DataFrame:
    """The reference answer to ``q`` in the reference's column names."""
    if control:
        data = {t: df.assign(**{c: _bf16(df[c]) for c in df.columns
                                if df[c].dtype == np.float32})
                for t, df in data.items()}
    want = _FNS[q](data)
    want = want[[w for _, w in COLUMNS[q]]].reset_index(drop=True)
    if control:
        want = want.assign(**{c: _bf16(want[c]) for c in want.columns
                              if pd.api.types.is_float_dtype(want[c])})
    return want
