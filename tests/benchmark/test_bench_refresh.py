"""The refresh cell's files: found by name, its update sets and keys,
and whole runs on the CPU at SF-0.01 (the chip check steered onto the
CPU) — a sound run is correct, and a run that drops one refresh
function underneath is not."""
import json
import os
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from benchmarks.harness import (cli, manifest, refresh_roofline, tpch_data,
                                tpch_refresh, tracing, traffic)
from bench_fixtures import REPO, copy_benchmark, cpu_as_chip  # noqa: F401

CELL = "tpch-sf10-refresh.rf-pairs"
CONFIG = "tpch-sf10-refresh-1chip"
METRICS = ["idle_share.refresh", "insert_s.refresh", "delete_s.refresh",
           "insert_roofline.refresh", "delete_roofline.refresh",
           "host_mb.refresh", "ingest_host_ms.refresh",
           "window_compiles.refresh", "host_reads.refresh",
           "host_read_ms.refresh"]
SF = 0.01


@pytest.fixture
def refresh_root(tmp_path):
    root = copy_benchmark(str(tmp_path))
    p = os.path.join(root, "benchmarks", "configs", CONFIG + ".json")
    with open(p) as f:
        c = json.load(f)
    c["scale_factor"] = SF
    with open(p, "w") as f:
        json.dump(c, f)
    return root


def test_new_files_are_found_by_name():
    """The configuration, traffic mix, driver and metrics are new files
    that the harness finds by name; the cell reports setup_s, stream_s
    and exactly its ten per-layer metrics; each reads nothing where the
    program keeps nothing to read, but the window's compiles, which the
    harness counts itself."""
    m = manifest.load(REPO)
    assert manifest.validate(m, REPO) == []
    cell = manifest.workload(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "rf-pairs", 1)
    config = manifest.config(m, REPO, CONFIG)
    assert config["kind"] == "tpch" and config["scale_factor"] == 10
    assert config["guarantees"]["read_only"] is False
    spec = manifest.traffic(REPO, "rf-pairs")
    assert manifest.driver(REPO, spec).__name__ == "Driver"
    assert [x["name"] for x in manifest.e2e_metrics(m, CELL)] == \
        ["setup_s", "stream_s"]
    assert [x["name"] for x in manifest.per_layer_metrics(m, CELL)] == \
        METRICS
    empty = cli.Reading(traffic.Window(units=2), None, {}, 0, {}, {}, 1)
    for name in METRICS:
        want = 0 if name.startswith("window_compiles") else None
        assert manifest.reader(REPO, name)(empty) == want, name


def test_update_sets_are_disjoint_from_the_base_and_each_other():
    n, base = tpch_refresh.set_orders(SF), tpch_data.sizes(SF)["orders"]
    sets = [tpch_refresh.update_sets(SF, 5, i, 1) for i in range(4)]
    keys = [s["orders"]["o_orderkey"] for s in sets]
    for i, k in enumerate(keys):
        assert len(k) == n and k.min() > base
        assert set(np.unique(sets[i]["lineitem"]["l_orderkey"])) == set(k)
        for j in range(i):
            assert not set(k) & set(keys[j])
    # a run of sets is the sets one by one
    run = tpch_refresh.update_sets(SF, 5, 0, 4)
    for t in ("orders", "lineitem"):
        for c, v in run[t].items():
            assert (v == np.concatenate([s[t][c] for s in sets])).all()
    # RF2 of pair i deletes the n oldest live orders
    assert (tpch_refresh.rf2_keys(SF, 2) == np.arange(2 * n + 1,
                                                      3 * n + 1)).all()
    assert tpch_refresh.live_key_range(SF, 3) == (3 * n + 1, base + 3 * n)


def test_update_sets_draw_as_the_device_generator():
    """The column functions called with numpy give what the device
    generator gives at the same order and line indices: integers and
    codes exactly, floats to the last bit or so (XLA may fuse the
    multiply-add that numpy rounds twice)."""
    s = jax.sharding.SingleDeviceSharding(jax.devices("cpu")[0])
    cols, counts = tpch_data.generate(SF, 9, s)
    sz = tpch_data.sizes(SF)
    o_idx = np.arange(50, dtype=np.int64)
    starts = np.searchsorted(np.asarray(cols["lineitem"]["l_orderkey"]),
                             o_idx + 1)
    with np.errstate(over="ignore"):
        lines = 1 + tpch_data._randint(np, tpch_data._salt(
            np, np.uint32(9), tpch_data._T.LINES), o_idx, 7)
        host = tpch_data._orders_cols(np, np.uint32(9), o_idx, starts,
                                      lines, sz["customer"], sz["clerk"])
    for c, v in host.items():
        dev = np.asarray(cols["orders"][c])[:50]
        if c in tpch_data.FLOAT_COLS:
            np.testing.assert_allclose(dev, v, rtol=2e-7, err_msg=c)
        else:
            np.testing.assert_array_equal(dev, v, err_msg=c)


def test_roofline_shares():
    tr = tracing.Trace((0.0, 4e9), {0: [(0.0, 1e9, "jit_dtable__insert_fn"
                                         "(3):scatter.1"),
                                        (1e9, 3e9, "jit_dtable__delete_fn"
                                         "(4):fusion.2")]})
    facts = {"insert_bytes": 4.4e6, "delete_bytes": 2.9e8}
    r = cli.Reading(traffic.Window(units=2), tr, {}, 0, facts,
                    {"hbm_bytes_per_s": 819e9}, 1)
    assert manifest.reader(REPO, "insert_s.refresh")(r) == pytest.approx(0.5)
    assert manifest.reader(REPO, "delete_s.refresh")(r) == pytest.approx(1.0)
    assert manifest.reader(REPO, "insert_roofline.refresh")(r) == \
        pytest.approx(100 * 4.4e6 / 819e9 / 0.5)
    assert manifest.reader(REPO, "delete_roofline.refresh")(r) == \
        pytest.approx(refresh_roofline.share(2.9e8, 1.0, 1,
                                             {"hbm_bytes_per_s": 819e9}))
    counters = {"ingest.host_bytes": 9_000_000, "ingest.host_us": 4_000}
    r = cli.Reading(traffic.Window(units=2), None, counters, 0, {}, {}, 1)
    assert manifest.reader(REPO, "host_mb.refresh")(r) == 4.5
    assert manifest.reader(REPO, "ingest_host_ms.refresh")(r) == 2.0


def test_host_read_and_compile_readers():
    """Per pair: the engine's host reads and the time blocked in them;
    the window's compiles as the harness counted them."""
    counters = {"host.read": 8, "host.read_us": 60_000}
    r = cli.Reading(traffic.Window(units=2), None, counters, 3, {}, {}, 1)
    assert manifest.reader(REPO, "host_reads.refresh")(r) == 4.0
    assert manifest.reader(REPO, "host_read_ms.refresh")(r) == 30.0
    assert manifest.reader(REPO, "window_compiles.refresh")(r) == 3


def _run(root, seconds=2.0, trace=0):
    args = SimpleNamespace(workload=CELL, seed=3_000_000_029,
                           seconds=seconds, trace=trace)
    return cli.run(args, root, time.perf_counter())


def _skip_once(monkeypatch, method: str, table_rows_over: int):
    """``DTable.<method>`` left out, once, on the table larger than
    ``table_rows_over`` rows (lineitem), after the warm-up pair."""
    from cylon_tpu.parallel import DTable
    real = getattr(DTable, method)
    calls = {"n": 0}

    def once(self, *a, **k):
        if self.num_rows > table_rows_over:
            calls["n"] += 1
            if calls["n"] == 2:
                return self
        return real(self, *a, **k)
    monkeypatch.setattr(DTable, method, once)


@pytest.mark.parametrize("fault", [None, "delete", "append"])
def test_a_run_is_correct_unless_a_refresh_function_is_dropped(
        refresh_root, cpu_as_chip, monkeypatch, fault):
    """A sound run is correct; with one RF2 (or one RF1) on lineitem
    left out underneath, the check finds the rows."""
    if fault:
        _skip_once(monkeypatch, fault, tpch_data.sizes(SF)["orders"] * 2)
    out = _run(refresh_root)
    assert out["attempted"] >= 1 and out["failed"] == 0
    wrong = out["compared"]["wrong_rows"]["value"]
    if fault is None:
        assert out["correct"], out["compared"]
        assert list(out)[-1] == "compared"
    else:
        assert not out["correct"] and wrong > 0, out["compared"]


def test_lower_precision_fails_the_check():
    """The bfloat16 control in the program's place: the pandas reference
    state after two pairs, and q18 on it, with every float rounded to
    bfloat16, handed to the driver's own check.  Every row with a float
    differs, so ``wrong_rows`` fails the configuration's limits, and
    q18's aggregates miss ``rel_err``; the same state unrounded passes."""
    from benchmarks.drivers.refresh import Driver
    from benchmarks.harness import compare
    from benchmarks.harness import tpch_reference as ref
    from cylon_tpu import CylonContext
    with open(os.path.join(REPO, "benchmarks", "configs",
                           CONFIG + ".json")) as f:
        limits = json.load(f)["limits"]
    sf, seed, pairs = 0.1, 3_000_000_029, 2
    ctx = CylonContext({"backend": "tpu", "devices": jax.devices("cpu")[:1]})
    base = tpch_refresh.base_frames(sf, seed, ctx.sharding())
    want = tpch_refresh.reference_state(base, sf, seed, pairs)
    want["customer"] = base["customer"]
    names = {w: a for a, w in ref.COLUMNS["q18"]}

    def kept(control: bool):
        out = {"q18": ref.answer("q18", want, control=control)
               .rename(columns=names)}
        for t in ("orders", "lineitem"):
            df = want[t]
            out[t] = {c: np.asarray(ref._bf16(df[c]) if control
                                    and df[c].dtype == np.float32 else df[c])
                      for c in df.columns}
        return out

    d = Driver.__new__(Driver)
    d.scale, d.seed, d.ctx, d.pairs, d.missing = sf, seed, ctx, pairs, 0
    numbers = {}
    for control in (False, True):
        d._kept = kept(control)
        numbers[control] = d.check(None)
    assert len(d._kept["q18"]) > 0
    assert compare.verdict(numbers[False], limits), numbers[False]
    low = numbers[True]
    assert not compare.verdict(low, limits), low
    rows = sum(len(want[t]) for t in ("orders", "lineitem"))
    assert low["wrong_rows"] > rows  # every row, on each side
    assert low["rel_err"] > 10 * limits["rel_err"], low
