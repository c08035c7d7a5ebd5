"""The benchmark's copies of the generator, the queries and the pandas
reference agree with ``cylon_tpu/tpch`` on the CPU at a small scale,
and a second seed compiles no second generator."""
import jax
import numpy as np
import pandas as pd
import pytest

from benchmarks.harness import device, tpch_data, tpch_queries
from benchmarks.harness import tpch_reference as ref

SF = 0.01
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def ctx():
    from cylon_tpu import CylonContext
    return CylonContext({"backend": "tpu", "devices": jax.devices("cpu")[:1]})


@pytest.fixture(scope="module")
def tables(ctx):
    from cylon_tpu.tpch import datagen_device as dd
    cols, counts = tpch_data.generate(SF, SEED, ctx.sharding())
    mine = tpch_data.device_tables(ctx, cols, counts, SF)
    return mine, dd.generate_device(ctx, SF, SEED), cols, counts


def _valid(dt, name):
    n = int(dt.counts_host().sum())
    return np.asarray(dt.column(name).data)[:n]


def test_generator_matches_the_engine(tables):
    """Every column the engine's generator makes, equal; the benchmark's
    generator adds the rest of Clause 4.2 after them."""
    mine, engine, _, _ = tables
    for t in tpch_data.COLUMN_ORDER:
        assert mine[t].num_rows == engine[t].num_rows, t
        names = engine[t].column_names
        assert mine[t].column_names[:len(names)] == names, t
        for c in names:
            a, b = _valid(mine[t], c), _valid(engine[t], c)
            if c in tpch_data.FLOAT_COLS:
                # one program against several: XLA may contract a
                # multiply-add differently, a few float32 ulps apart
                np.testing.assert_allclose(a, b, rtol=1e-5, err_msg=f"{t}.{c}")
            else:
                np.testing.assert_array_equal(a, b, err_msg=f"{t}.{c}")
            d0 = mine[t].column(c).dictionary
            d1 = engine[t].column(c).dictionary
            assert (d0 is None) == (d1 is None), (t, c)
            if d0 is not None:
                assert list(d0) == list(d1), (t, c)


def _supplier_names(keys):
    return [f"Supplier#{int(k):09d}" for k in keys]


@pytest.mark.parametrize("q", ["q1", "q3", "q6", "q18", "q21"])
def test_queries_match_the_engine(ctx, tables, q):
    """The engine's columns equal; q18's added ``c_name`` is the name of
    its ``c_custkey``, and q21's ``s_name`` that of the engine's key."""
    from cylon_tpu.tpch import queries
    mine, _, _, _ = tables
    got = tpch_queries.QUERIES[q](ctx, mine).to_pandas() \
        .reset_index(drop=True)
    want = queries.QUERIES[q](ctx, mine).to_pandas().reset_index(drop=True)
    if q == "q21":
        assert [str(x) for x in got["s_name"]] == \
            _supplier_names(want["l_suppkey"])
        got, want = got[["numwait"]], want[["numwait"]]
    if q == "q18":
        assert [str(x) for x in got["c_name"]] == [
            f"Customer#{int(k):09d}" for k in got["c_custkey"]]
        got = got[list(want.columns)]
    pd.testing.assert_frame_equal(got, want)


@pytest.mark.parametrize("q", ["q1", "q3", "q6", "q18", "q21"])
def test_reference_matches_the_engine_reference(tables, q):
    from cylon_tpu.tpch import datagen_device as dd
    from cylon_tpu.tpch.reference import pandas_tpch
    _, _, cols, counts = tables
    data = tpch_data.host_frames(cols, counts, SF, ref.inputs([q]))
    mirror = dd.generate_mirror(SF, SEED, tables=(
        "lineitem", "orders", "customer", "supplier", "nation"))
    want = pandas_tpch(q, mirror, result=True)
    if not isinstance(want, pd.DataFrame):
        want = pd.DataFrame({"sum_rev": [want]})
    if q == "q21":
        want = want.assign(s_name=_supplier_names(want["l_suppkey"]))
    if q == "q18":
        want = want.assign(c_name=[f"Customer#{int(k):09d}"
                                   for k in want["c_custkey"]])
    want = want[[w for _, w in ref.COLUMNS[q]]].reset_index(drop=True)
    got = ref.answer(q, data)
    assert len(got) == len(want)  # q18 is empty at this scale
    for c in got.columns:
        g, w = got[c].to_numpy(), want[c].to_numpy()
        if pd.api.types.is_float_dtype(got[c]):
            np.testing.assert_allclose(g, w, rtol=1e-6, err_msg=c)
        else:
            assert [str(x) for x in g] == [str(x) for x in w], c


def test_second_seed_compiles_no_generator(ctx):
    counter = device.CompileCounter()
    tpch_data.generate(0.002, 5, ctx.sharding())
    counter.counting = True
    _, counts = tpch_data.generate(0.002, 2**31 + 99, ctx.sharding())
    counter.counting = False
    assert counts["lineitem"].sum() > 0
    assert counter.n == 0
