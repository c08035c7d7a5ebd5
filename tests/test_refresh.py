"""The write path on resident tables: ``DTable.append`` moving only the
delta, ``DTable.delete`` by key, and ``ServeSession.ingest``/``delete``
driving TPC-H's refresh pairs (RF1, RF2) at SF-0.01, each checked
against pandas on a 1-device and a 4-device CPU mesh."""
import functools

import jax
import numpy as np
import pandas as pd
import pytest

from benchmarks.harness import compare, tpch_data, tpch_queries, tpch_refresh
from benchmarks.harness import tpch_reference as ref
from cylon_tpu import CylonContext, trace
from cylon_tpu import plan as planner
from cylon_tpu.parallel import DTable, dist_groupby, run_pipeline
from cylon_tpu.serve import ServeSession
from cylon_tpu.status import CylonError

SF = 0.01
SEED = 2**31 + 7
MESHES = [1, 4]
TABLES = (("orders", "o_orderkey"), ("lineitem", "l_orderkey"))


@functools.lru_cache(maxsize=None)
def _ctx(n: int):
    return CylonContext({"backend": "tpu", "devices": jax.devices("cpu")[:n]})


@pytest.fixture(autouse=True)
def _counters():
    from cylon_tpu.parallel import dtable
    planner.clear_plan_cache()
    dtable._delete_cap_hints.clear()  # each test sizes its own deletes
    trace.enable_counters()
    trace.reset()
    yield
    trace.disable_counters()
    trace.reset()
    planner.clear_plan_cache()


def _tpch(ctx):
    cols, counts = tpch_data.generate(SF, SEED, ctx.sharding())
    return tpch_data.device_tables(ctx, cols, counts, SF)


@functools.lru_cache(maxsize=None)
def _base():
    return tpch_refresh.base_frames(
        SF, SEED, jax.sharding.SingleDeviceSharding(jax.devices("cpu")[0]))


def _mismatch(dt, want: pd.DataFrame) -> int:
    return tpch_refresh.rows_mismatch(
        tpch_refresh.table_arrays(dt, tpch_data.dictionaries(SF)), want)


# q18's quantity at SF-0.01: above 300 (the validation value) hardly an
# order qualifies at this scale, so the answer would be empty
Q18_QUANTITY = 200.0


def _q18(ctx, tables, orders_range):
    got = run_pipeline(lambda: ctx.optimize(
        tpch_refresh.q18_op(ctx, orders_range, Q18_QUANTITY),
        tables)).to_pandas()
    pairs = ref.COLUMNS["q18"]
    return got[[a for a, _ in pairs]].rename(columns=dict(pairs))


def _q18_pandas(d):
    """``tpch_reference``'s q18 at ``Q18_QUANTITY``."""
    per = d["lineitem"].groupby("l_orderkey")["l_quantity"].sum() \
        .reset_index()
    big = per[per["l_quantity"] > Q18_QUANTITY]
    m = big.merge(d["orders"], left_on="l_orderkey", right_on="o_orderkey")
    m = m.merge(d["customer"], left_on="o_custkey", right_on="c_custkey")
    m = m.sort_values(["o_totalprice", "o_orderdate"],
                      ascending=[False, True]).head(100)
    return m[[w for _, w in ref.COLUMNS["q18"]]].reset_index(drop=True)


def _pair(s: ServeSession, i: int) -> None:
    sets = tpch_refresh.update_sets(SF, SEED, i, 1)
    for t, _ in TABLES:
        s.ingest(t, sets[t]).result(timeout=600)
    keys = tpch_refresh.rf2_keys(SF, i)
    for t, k in TABLES:
        s.delete(t, k, keys).result(timeout=600)


# ---------------------------------------------------------------------------
# the refresh stream against pandas
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chips", MESHES)
def test_refresh_pairs_match_pandas(chips):
    """After each of three pairs: orders' and lineitem's rows equal the
    pandas state (concat, then isin), and q18 through ctx.optimize with
    the live order-key bounds equals pandas on that state."""
    ctx = _ctx(chips)
    tables = _tpch(ctx)
    base = _base()
    state = {t: base[t] for t, _ in TABLES}
    with ServeSession(ctx, tables=tables, batch_window_ms=0.0) as s:
        for i in range(3):
            _pair(s, i)
            state = tpch_refresh.apply_pair(state, SF, SEED, i)
            for t, _ in TABLES:
                assert _mismatch(tables[t], state[t]) == 0, (i, t)
            assert tables["orders"].num_rows == len(state["orders"])
            want = _q18_pandas({**state, "customer": base["customer"]})
            got = _q18(ctx, tables, tpch_refresh.live_key_range(SF, i + 1))
            assert len(want) > 0
            num = compare.compare_answer(got, want, ref.KEYS["q18"])
            assert num["wrong_rows"] == 0 and num["rel_err"] <= 1e-4, (i, num)
    # the reference the benchmark checks with is the same apply, in bulk
    bulk = tpch_refresh.reference_state(base, SF, SEED, 3)
    for t, _ in TABLES:
        h = lambda df: compare.row_hashes(  # noqa: E731
            [df[c].to_numpy() for c in df.columns])
        assert compare.multiset_mismatch(h(bulk[t]), h(state[t])) == 0


@pytest.mark.parametrize("chips", MESHES)
def test_old_hint_is_refused_on_a_refreshed_table(chips):
    """``(1, num_rows)`` is false once RF1 added keys above it and RF2
    removed keys below: the engine raises the dense_key_range contract
    error, it does not return wrong rows."""
    ctx = _ctx(chips)
    tables = _tpch(ctx)
    with ServeSession(ctx, tables=tables, batch_window_ms=0.0) as s:
        _pair(s, 0)
    with pytest.raises(CylonError, match="dense_key_range"):
        run_pipeline(lambda: ctx.optimize(
            functools.partial(tpch_queries.q18, ctx), tables)).to_pandas()


# ---------------------------------------------------------------------------
# DTable.delete and DTable.append cases
# ---------------------------------------------------------------------------

def _frame(n=200, seed=0, lo=1):
    r = np.random.default_rng(seed)
    return pd.DataFrame({
        "k": np.arange(lo, lo + n, dtype=np.int64),
        "v": r.normal(size=n),
        "s": r.choice(["ash", "birch", "cedar"], n)})


def _rows(dt) -> pd.DataFrame:
    df = dt.to_table().to_pandas()
    df["s"] = df["s"].astype(str)
    return df.sort_values(["k", "v"]).reset_index(drop=True)


def _same(dt, want: pd.DataFrame) -> None:
    want = want.assign(s=want["s"].astype(str)) \
        .sort_values(["k", "v"]).reset_index(drop=True)
    pd.testing.assert_frame_equal(_rows(dt), want, check_dtype=False)
    assert int(dt.counts_host().sum()) == len(want)


def _scattered(df):
    return np.random.default_rng(1).permutation(df["k"].to_numpy()[::3])


@pytest.mark.parametrize("chips", MESHES)
@pytest.mark.parametrize("case", ["scattered", "absent", "every_row",
                                  "on_several_shards", "past_the_rows",
                                  "past_the_size_hint",
                                  "few_of_many_past_the_size_hint"])
def test_delete(chips, case):
    """``absent`` keys span the rows' range and match none: the rows
    stay, and the epoch bumps (the delete does not wait to learn that
    nothing matched).  Keys ``past_the_rows`` leave no candidate: no
    bump.  A delete ``past_the_size_hint`` has more candidates than the
    last delete of this shape: more passes finish it on the device, and
    its candidate count sizes the next delete.  Keys ``few_of_many``
    candidates match: each pass marks the rest, so the passes end."""
    from cylon_tpu.ops.compact import hint_value
    from cylon_tpu.parallel import dtable
    ctx = _ctx(chips)
    df = _frame()
    if case == "on_several_shards":  # each key's rows spread over shards
        df["k"] = df["k"] % 7
    dt = DTable.from_pandas(ctx, df)
    if case.endswith("past_the_size_hint"):  # one candidate: class 8
        dt.delete("k", [1])
        df = df[df["k"] != 1]
    epoch = dt.content_epoch
    hint = (ctx.mesh, dt.cap, 0)
    keys = {"scattered": _scattered(df),
            "absent": np.array([-5, 10_000, 2**40]),
            "every_row": df["k"].to_numpy()[::-1],
            "on_several_shards": np.array([3, 0]),
            "past_the_rows": np.array([10_000, 2**40]),
            "past_the_size_hint": df["k"].to_numpy()[1::2],
            "few_of_many_past_the_size_hint": np.concatenate(
                [_scattered(df), [-5, 2**40]])}[case]
    assert dt.delete("k", keys) is dt
    assert dt._counts_host is None or case == "past_the_rows"
    _same(dt, df[~df["k"].isin(keys)])
    assert dt.content_epoch == epoch + (case != "past_the_rows")
    if case == "past_the_size_hint":
        assert hint_value(dtable._delete_cap_hints, hint) == (8,)
        dt.delete("k", [2**40])
        assert hint_value(dtable._delete_cap_hints, hint)[0] >= 96 // chips
    if case == "on_several_shards" and chips > 1:
        assert (dt.counts_host() < DTable.from_pandas(ctx, df)
                .counts_host()).all()
    if case == "every_row":  # an empty table stays a valid table
        assert dt.num_rows == 0
        dt.append(DTable.from_pandas(ctx, df.head(5)))
        _same(dt, df.head(5))


@pytest.mark.parametrize("chips", MESHES)
@pytest.mark.parametrize("case", ["overflow", "new_dictionary_values",
                                  "host_columns", "arrow", "nulls"])
def test_append(chips, case):
    ctx = _ctx(chips)
    df = _frame(n=100)
    dt = DTable.from_pandas(ctx, df)
    cap = dt.cap  # 112 rows on one chip, 4 x 28 on four: room for 10
    delta = _frame(n=10 if case != "overflow" else 400, seed=2, lo=1000)
    if case == "new_dictionary_values":
        delta["s"] = np.where(delta.index % 2, "aspen", "yew")
    if case == "host_columns":
        d = dict(delta)
        d["s"] = np.searchsorted(dt.column("s").dictionary, delta["s"])
        dt.append({c: np.asarray(v) for c, v in d.items()})
    elif case == "arrow":
        import pyarrow as pa
        dt.append(pa.Table.from_pandas(delta, preserve_index=False))
    elif case == "nulls":
        delta["v"] = delta["v"].where(delta.index % 3 != 0)
        dt.append(DTable.from_pandas(ctx, delta))
    else:
        dt.append(DTable.from_pandas(ctx, delta))
    both = pd.concat([df, delta], ignore_index=True)
    if case == "nulls":
        got = _rows(dt)
        want = both.sort_values(["k"]).reset_index(drop=True)
        assert got["v"].isna().sum() == want["v"].isna().sum() > 0
        assert (got["k"].to_numpy() == want["k"].to_numpy()).all()
    else:
        _same(dt, both)
    if case == "overflow":
        assert dt.cap > cap
    else:
        assert dt.cap == cap
    if case == "new_dictionary_values":
        assert list(dt.column("s").dictionary) == sorted(
            {"ash", "birch", "cedar", "aspen", "yew"})
    counts = dt.counts_host()
    assert counts.max() - counts.min() <= 1  # the emptiest shards filled


@pytest.mark.parametrize("chips", MESHES)
@pytest.mark.parametrize("case", ["fits", "overflows"])
def test_append_after_a_delete(chips, case):
    """An append waits for an unread delete's counts only where they
    decide something: the layout over several shards, or a capacity
    that the counts before the delete, grown by the delta, would
    overflow.  Else the new counts stay on the device, and the rows
    deleted are counted once they are read."""
    ctx = _ctx(chips)
    df = _frame(n=100)
    dt = DTable.from_pandas(ctx, df)
    cap = dt.cap  # 112 rows on one chip: 10 more fit beside the 100
    keys = _scattered(df)
    dt.delete("k", keys)
    delta = _frame(n=10 if case == "fits" else 20, seed=2, lo=1000)
    cols = dict(delta, s=np.searchsorted(dt.column("s").dictionary,
                                         delta["s"]))
    reads = trace.counters().get("host.read", 0)
    dt.append({c: np.asarray(v) for c, v in cols.items()})
    waited = trace.counters().get("host.read", 0) - reads
    assert waited == (0 if chips == 1 and case == "fits" else 1)
    assert (dt._counts_host is None) == (waited == 0)
    assert dt.cap == cap
    _same(dt, pd.concat([df[~df["k"].isin(keys)], delta],
                        ignore_index=True))
    assert trace.counters()["ingest.rows_deleted"] == len(keys)


# ---------------------------------------------------------------------------
# views, compiles, bytes
# ---------------------------------------------------------------------------

def _sum_by_s(t):
    return dist_groupby(t["fact"], ["s"], [("v", "sum"), ("k", "count")])


@pytest.mark.parametrize("chips", MESHES)
def test_view_recomputes_after_delete_and_folds_after_append(chips):
    ctx = _ctx(chips)
    df = _frame(n=300)
    dt = DTable.from_pandas(ctx, df)

    def want(frame):
        g = frame.assign(s=frame["s"].astype(str)).groupby("s").agg(
            sum_v=("v", "sum"), count_k=("k", "count")).reset_index()
        return g.sort_values("s").reset_index(drop=True)

    def got(h):
        out = h.result(timeout=600).to_table().to_pandas()
        out["s"] = out["s"].astype(str)
        return out.sort_values("s").reset_index(drop=True)[
            ["s", "sum_v", "count_k"]]

    state = df
    with ServeSession(ctx, tables={"fact": dt}, batch_window_ms=0.0) as s:
        got(s.submit(_sum_by_s))
        keys = _scattered(df)
        s.delete("fact", "k", keys).result(timeout=600)
        state = state[~state["k"].isin(keys)]
        h = s.submit(_sum_by_s)
        pd.testing.assert_frame_equal(got(h), want(state), check_dtype=False)
        assert h.view is None  # recomputed
        got(s.submit(_sum_by_s))  # retained again
        delta = _frame(n=50, seed=3, lo=5000)
        s.ingest("fact", DTable.from_pandas(ctx, delta)).result(timeout=600)
        state = pd.concat([state, delta], ignore_index=True)
        h = s.submit(_sum_by_s)
        pd.testing.assert_frame_equal(got(h), want(state), check_dtype=False)
        # a one-shard groupby keeps no partial state to fold (its groups
        # are already local), so there the view recomputes
        assert h.view == ("fold" if chips > 1 else None)


class _Compiles:
    def __init__(self):
        self.n, self.on = 0, False
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **kw):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


@pytest.mark.parametrize("chips", MESHES)
def test_a_second_pair_of_another_size_compiles_nothing(chips):
    """Insert and delete programs take bucketed operands: once pairs of
    4,000 rows have run, a pair of 4,013 (the same size classes)
    compiles nothing — as RF1/RF2 at SF-10, about 60,000 lines give or
    take a few hundred, stay in one class.  (The warm-up is two pairs:
    the first delete empties one shard's block most, and the appends
    after it fill that shard first.)"""
    ctx = _ctx(chips)
    dt = DTable.from_pandas(ctx, _frame(n=50_000))
    compiles = _Compiles()
    with ServeSession(ctx, tables={"fact": dt}, batch_window_ms=0.0) as s:
        lo = 1
        for m in (4_000, 4_000, 4_013):
            compiles.on = m != 4_000
            delta = _frame(n=m, seed=m, lo=100_000 + lo)
            delta["s"] = np.searchsorted(dt.column("s").dictionary,
                                         delta["s"])
            s.ingest("fact", {c: delta[c].to_numpy() for c in delta}) \
                .result(timeout=600)
            s.delete("fact", "k", np.arange(lo, lo + m)).result(timeout=600)
            lo += m
    assert dt.num_rows == 50_000
    assert compiles.n == 0


@pytest.mark.parametrize("chips", MESHES)
def test_only_the_delta_and_keys_cross_the_host(chips, monkeypatch):
    """``ingest.host_bytes`` is the staged delta, its per-shard row
    counts, the keys (one copy per chip) and each delete's one count
    read: nothing of the table, and no table export runs.  The deleted
    rows are counted once the new counts are read."""
    from cylon_tpu.ops.compact import next_bucket
    ctx = _ctx(chips)
    tables = _tpch(ctx)
    exported = []
    real = DTable._export
    monkeypatch.setattr(DTable, "_export", lambda self, takes: (
        exported.append(self), real(self, takes))[1])
    sets = tpch_refresh.update_sets(SF, SEED, 0, 1)
    keys = tpch_refresh.rf2_keys(SF, 0)
    before = {t: tables[t].counts_host().copy() for t, _ in TABLES}
    trace.reset()
    with ServeSession(ctx, tables=tables, batch_window_ms=0.0) as s:
        for t, _ in TABLES:
            s.ingest(t, sets[t]).result(timeout=600)
        took = {t: tables[t].counts_host() - before[t] for t, _ in TABLES}
        reads = trace.counters().get("host.read", 0)
        for t, k in TABLES:
            s.delete(t, k, keys).result(timeout=600)
            assert tables[t]._counts_host is None  # left on the device
    assert trace.counters()["host.read"] - reads == len(TABLES)
    assert exported == []
    want = 0
    for t, _ in TABLES:
        dcap = next_bucket(int(took[t].max()), minimum=8)
        want += chips * dcap * sum(a.dtype.itemsize
                                   for a in sets[t].values())
        want += chips * 4                                 # rows per shard
        want += chips * next_bucket(len(keys), minimum=8) * 4  # keys
        want += chips * 4                                 # one count read
    got = trace.counters()
    assert got["ingest.host_bytes"] == want
    assert got["ingest.rows_in"] == sum(
        len(sets[t][tpch_data.COLUMN_ORDER[t][0]]) for t, _ in TABLES)
    assert "ingest.rows_deleted" not in got and got["ingest.host_us"] > 0
    gone = sum(int((before[t] + took[t]).sum()) - tables[t].num_rows
               for t, _ in TABLES)
    assert gone > 0
    assert trace.counters()["ingest.rows_deleted"] == gone
    table_bytes = sum(c.data.nbytes for t, _ in TABLES
                      for c in tables[t].columns)
    assert want < table_bytes / 20
