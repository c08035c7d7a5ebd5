"""The benchmark harness on the CPU: the manifest's rules, files found by
name, deterministic traffic and data, the trace reduction, the roofline
arithmetic, the refusal of anything but a TPU, and a lower-precision
answer failing the comparison."""
import copy
import hashlib
import json
import os
import time

from types import SimpleNamespace

import jax
import numpy as np
import pandas as pd
import pytest

from benchmarks.harness import (cli, compare, device, manifest, roofline,
                                tracing, traffic)
from bench_fixtures import REPO, bench_root, cpu_as_chip  # noqa: F401


def _manifest():
    return manifest.load(REPO)


def test_manifest_holds_the_contract():
    assert manifest.validate(_manifest(), REPO) == []


def _break_name(m):
    m["workloads"][0]["name"] = "has space"


def _break_unit(m):
    m["end_to_end"][1]["unit"] = "seconds per stream"


def _break_better(m):
    m["end_to_end"][1]["better"] = "smaller"


def _drop_setup(m):
    m["end_to_end"] = [x for x in m["end_to_end"] if x["name"] != "setup_s"]


def _second_four_chip(m):
    m["workloads"][0]["chips"] = 4


def _extra_key(m):
    m["per_layer"][0]["why"] = "a why on a metric is refused"


def _bad_moves(m):
    m["per_layer"][0]["moves"] = "no_such_metric"


def _loose_bound(m):
    m["end_to_end"][1]["bound"] = 0.3


def _bad_reduced(m):
    m["configs"][0]["reduced"] = ["a/b"]


def _greek_unit(m):
    m["per_layer"][0]["unit"] = "μs"


@pytest.mark.parametrize("breach", [
    _break_name, _break_unit, _break_better, _drop_setup, _second_four_chip,
    _extra_key, _bad_moves, _loose_bound, _bad_reduced, _greek_unit])
def test_manifest_refuses(breach):
    m = copy.deepcopy(_manifest())
    breach(m)
    assert manifest.validate(m, REPO), breach.__name__


def _digest(root):
    h = {}
    for d, _, fs in os.walk(root):
        for f in fs:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                h[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return h


_DEPLOYMENT = """
import jax.numpy as jnp
import numpy as np


class Deployment:
    def __init__(self, config, ctx, seed):
        from cylon_tpu.parallel import DTable
        import pandas as pd
        rng = np.random.default_rng(seed)
        self.seed, self.values = seed, rng.integers(0, 100, config["rows"])
        self.tables = DTable.from_pandas(ctx, pd.DataFrame(
            {"x": self.values.astype(np.int32)}))

    def facts(self):
        return {}

    def free(self):
        self.tables = None
"""

_DRIVER = """
import time

from benchmarks.harness.traffic import Window, span


class Driver:
    def __init__(self, spec, dep, ctx, seed):
        self.dep, self.sums = dep, []

    def _sum(self):
        from cylon_tpu.parallel import dist_aggregate
        with span("sum"):
            return dist_aggregate(self.dep.tables, [("x", "sum")]) \\
                .to_pandas().iloc[0, 0]

    def warm(self):
        self._sum()

    def run(self, seconds):
        w, t0 = Window(), time.perf_counter()
        with span("window"):
            while time.perf_counter() - t0 < seconds:
                self.sums.append(self._sum())
                w.units += 1
        w.seconds = time.perf_counter() - t0
        w.attempted = w.units
        w.e2e["sum_s"] = w.seconds / w.units
        return w

    def release(self):
        pass

    def facts(self):
        return {}

    def check(self, ref_dir):
        want = int(self.dep.values.sum())
        return {"wrong_rows": sum(int(s) != want for s in self.sums)}
"""


def test_new_files_are_found_by_name(bench_root, cpu_as_chip):
    """A configuration, a deployment kind, a traffic mix, a window
    driver and a metric added as new files plus new entries: found by
    name, no existing file edited, and the new cell runs."""
    before = _digest(os.path.join(bench_root, "benchmarks"))
    b = os.path.join(bench_root, "benchmarks")
    with open(os.path.join(b, "deployments", "ints.py"), "w") as f:
        f.write(_DEPLOYMENT)
    with open(os.path.join(b, "drivers", "sums.py"), "w") as f:
        f.write(_DRIVER)
    with open(os.path.join(b, "configs", "ints-1chip.json"), "w") as f:
        json.dump({"kind": "ints", "rows": 1000,
                   "limits": {"wrong_rows": 0}}, f)
    with open(os.path.join(b, "traffic", "sum-loop.json"), "w") as f:
        json.dump({"driver": "sums"}, f)
    with open(os.path.join(b, "configs", "tpch-sf1-1chip.json"), "w") as f:
        json.dump({"kind": "tpch", "scale_factor": 0.01,
                   "limits": {"wrong_rows": 0, "missing": 0,
                              "rel_err": 1e-4}}, f)
    with open(os.path.join(b, "traffic", "stream-scan.json"), "w") as f:
        json.dump({"driver": "stream", "queries": ["q1", "q6"]}, f)
    with open(os.path.join(b, "metrics", "streams.stream1.py"), "w") as f:
        f.write("def read(r):\n    return float(r.window.units)\n")
    m = manifest.load(bench_root)
    m["configs"].append({"name": "tpch-sf1-1chip", "source": "TPC-H 3.0.1",
                         "file": "benchmarks/configs/tpch-sf1-1chip.json",
                         "reduced": [], "why": "a smaller deployment"})
    m["workloads"].append({"name": "tpch-sf1.stream-scan",
                           "config": "tpch-sf1-1chip",
                           "traffic": "stream-scan", "chips": 1,
                           "why": "scans only"})
    m["end_to_end"][1]["workloads"].append("tpch-sf1.stream-scan")
    m["per_layer"].append({"name": "streams.stream1", "unit": "streams",
                           "better": "higher", "source": "host_clock",
                           "layer": "device (v5e)", "moves": "stream_s",
                           "workloads": ["tpch-sf1.stream-scan"]})
    m["configs"].append({"name": "ints-1chip", "source": "a test",
                         "file": "benchmarks/configs/ints-1chip.json",
                         "reduced": [], "why": "a new deployment kind"})
    m["workloads"].append({"name": "ints.sum-loop", "config": "ints-1chip",
                           "traffic": "sum-loop", "chips": 1,
                           "why": "a new window driver"})
    m["end_to_end"].append({"name": "sum_s", "unit": "s", "better": "lower",
                            "bound": 0.05, "source": "host_clock",
                            "workloads": ["ints.sum-loop"]})
    m["per_layer"].append({"name": "streams.stream1x", "unit": "sums",
                           "better": "higher", "source": "host_clock",
                           "layer": "device (v5e)", "moves": "sum_s",
                           "workloads": ["ints.sum-loop"]})
    with open(os.path.join(b, "metrics", "streams.stream1x.py"), "w") as f:
        f.write("def read(r):\n    return float(r.window.units)\n")
    assert manifest.validate(m, bench_root) == []
    # the manifest itself is the only existing file that changes
    after = _digest(b)
    assert {k: v for k, v in after.items() if k in before} == before
    assert manifest.config(m, bench_root, "tpch-sf1-1chip")["kind"] == "tpch"
    assert manifest.traffic(bench_root, "stream-scan")["queries"] == \
        ["q1", "q6"]
    names = [x["name"] for x in
             manifest.per_layer_metrics(m, "tpch-sf1.stream-scan")]
    assert names == ["streams.stream1"]
    read = manifest.reader(bench_root, "streams.stream1")
    assert read(cli.Reading(traffic.Window(units=3), None, {}, 0, {}, {},
                            1)) == 3.0
    with open(os.path.join(bench_root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    for trace in (0, 1):
        out = cli.run(SimpleNamespace(workload="ints.sum-loop", seed=5,
                                      seconds=0.3, trace=trace),
                      bench_root, time.perf_counter())
        assert out["correct"] and out["attempted"] > 0, out
        assert set(out["metrics"]) == ({"sum_s", "setup_s"} if not trace
                                       else {"streams.stream1x"})


def test_a_missing_driver_or_deployment_file_is_refused(bench_root):
    b = os.path.join(bench_root, "benchmarks")
    os.remove(os.path.join(b, "drivers", "join.py"))
    os.remove(os.path.join(b, "deployments", "tpch.py"))
    errs = manifest.validate(manifest.load(bench_root), bench_root)
    assert any("no driver file" in e for e in errs)
    assert any("no deployment file" in e for e in errs)


@pytest.mark.parametrize("rate,seconds", [(2.5, 40.0), (400.0, 40.0)])
def test_open_loop_schedule_is_deterministic(rate, seconds):
    """Poisson arrivals and a drawn mix: the same seed, the same
    schedule; another seed, another; about ``rate`` per second."""
    schedule = manifest._module(REPO, "drivers", "open_loop").schedule
    mix = {"q6": 3, "q1": 1}
    a = schedule(rate, seconds, mix, 3_000_000_001)
    b = schedule(rate, seconds, mix, 3_000_000_001)
    c = schedule(rate, seconds, mix, 17)
    assert np.array_equal(a[0], b[0]) and a[1] == b[1]
    assert not np.array_equal(a[0][:5], c[0][:5])
    n = rate * seconds
    for offsets, labels in (a, c):
        assert len(offsets) == len(labels)
        assert abs(len(offsets) - n) < 5 * n ** 0.5
        assert np.all(np.diff(offsets) > 0) and offsets[-1] < seconds
        assert set(labels) <= set(mix)
    big = schedule(rate, seconds * 50, mix, 9)[1]
    assert big.count("q6") / len(big) == pytest.approx(0.75, abs=0.05)


def test_generators_are_deterministic_in_the_seed():
    from benchmarks.harness import join_data, tpch_data
    s = jax.sharding.SingleDeviceSharding(jax.devices("cpu")[0])
    a, na = tpch_data.generate(0.002, 2**31 + 5, s)
    b, nb = tpch_data.generate(0.002, 2**31 + 5, s)
    c, nc = tpch_data.generate(0.002, 6, s)
    assert all(np.array_equal(na[t], nb[t]) for t in na)
    for t in a:
        for k in a[t]:
            assert np.array_equal(a[t][k], b[t][k]), (t, k)
    assert not np.array_equal(a["lineitem"]["l_extendedprice"],
                              c["lineitem"]["l_extendedprice"])
    l1, r1, _ = join_data.generate(1000, 1, 5, s)
    l2, r2, _ = join_data.generate(1000, 1, 5, s)
    assert all(np.array_equal(l1[k], l2[k]) and np.array_equal(r1[k], r2[k])
               for k in l1)
    assert not np.array_equal(l1["k"], r1["k"])


def test_tpch_blocks_over_four_chips_hold_the_same_rows():
    """The generator on a four-device mesh: each table in contiguous
    blocks, read back to the same frames as on one device."""
    from cylon_tpu import CylonContext
    from benchmarks.harness import tpch_data
    one = CylonContext({"backend": "tpu", "devices": jax.devices("cpu")[:1]})
    four = CylonContext({"backend": "tpu", "devices": jax.devices("cpu")[:4]})
    every = {t: cs for t, cs in tpch_data.COLUMN_ORDER.items()}
    frames = []
    for ctx in (one, four):
        cols, counts = tpch_data.generate(0.002, 2**31 + 77, ctx.sharding())
        assert len(counts["lineitem"]) == ctx.get_world_size()
        frames.append(tpch_data.host_frames(cols, counts, 0.002, every))
        tables = tpch_data.device_tables(ctx, cols, counts, 0.002)
        assert tables["lineitem"].num_rows == counts["lineitem"].sum()
    for t in every:
        pd.testing.assert_frame_equal(frames[0][t], frames[1][t])
    li = frames[1]["lineitem"]
    assert li.groupby("l_orderkey")["l_linenumber"].agg(
        lambda x: list(x) == list(range(1, len(x) + 1))).all()


def _synthetic_trace():
    # window [0, 100); device 0 busy [10,20) [15,30) [50,60); device 1
    # busy [0,100); host spans: window, a dispatch over [30,50)
    return tracing.Trace(
        window=(0.0, 100.0),
        devices={0: [(10, 20, "sort.1"), (15, 30, "fusion.2"),
                     (50, 60, "all-to-all.3")],
                 1: [(0, 100, "sort.1")]},
        spans=[(0, 100, "bench.window"), (30, 50, "bench.dispatch.join"),
               (60, 100, "bench.wait.join")],
        async_ops={0: [(55, 65, "all_to_all.4")]})


def test_trace_reduction():
    t = _synthetic_trace()
    assert t.window_s == pytest.approx(100e-9)
    assert t.busy_s(0) == pytest.approx(35e-9)  # 60-65 by the async op
    assert t.busy_s(1) == pytest.approx(100e-9)
    assert t.mean_busy_s() == pytest.approx(67.5e-9)
    # op-class time: sorts 10 on dev 0 and 100 on dev 1, mean 55
    assert t.op_s(r"^sort(\.\d+)?$") == pytest.approx(55e-9)
    assert t.op_s("^all[-_]to[-_]all", device=0) == pytest.approx(15e-9)
    assert t.n_ops("^all[-_]to[-_]all") == 2 and t.n_ops("^copy") == 0
    gaps = tracing.idle_gaps(t.ops(0), *t.window)
    assert gaps == [(0.0, 10), (30, 50), (65, 100.0)]
    top = tracing.top_gaps(t, 0)
    assert top[0] == ["wait.join", pytest.approx(35e-9)]
    assert top[1] == ["dispatch.join", pytest.approx(20e-9)]
    assert top[2] == ["none", pytest.approx(10e-9)]
    assert tracing.top_ops(t)[0][0] == "sort.1"
    assert tracing.op_name("%sort.51 = (s32[8]) sort(s32[8] %fusion.2)") \
        == "sort.51"


def test_trace_recorded_on_the_cpu(tmp_path):
    """A profile recorded here: the loader finds the window and the
    benchmark's host spans on one clock."""
    tr = tracing.Tracer(str(tmp_path / "trace"))
    f = jax.jit(lambda x: (x * 2).sum())
    x = np.ones(1000, np.float32)
    f(x).block_until_ready()
    tr.start()
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.dispatch.f"):
                f(x).block_until_ready()
            time.sleep(0.005)
    tr.stop()
    t = tr.trace()
    assert t.window_s > 0.01
    names = [n for _, _, n in t.spans]
    assert names.count("bench.dispatch.f") == 3
    lo, hi = t.window
    assert all(lo <= s <= e <= hi for s, e, n in t.spans
               if n != "bench.window")


def test_roofline_arithmetic():
    facts = {"rows_left": 16_000_000, "rows_right": 16_000_000,
             "rows_out": 16_104_188, "bytes_per_row_in": 16,
             "bytes_per_row_out": 32}
    assert roofline.join_bytes(facts) == 16_000_000 * 32 + 16_104_188 * 32
    read = manifest.reader(REPO, "join_roofline.join4")
    tr = tracing.Trace((0.0, 2e9), {d: [(0.0, 1e9, "x")] for d in range(4)})
    r = cli.Reading(traffic.Window(units=2), tr, {}, 0, facts,
                    {"hbm_bytes_per_s": 819e9}, 4)
    least = roofline.join_bytes(facts) / (4 * 819e9)
    assert read(r) == pytest.approx(100 * least / 0.5)
    assert read(cli.Reading(traffic.Window(units=2), None, {}, 0, facts,
                            {}, 4)) is None


def test_refuses_a_cpu(bench_root, capsys):
    rc = cli.main(["--workload", "tpch-sf10.stream-joins", "--seed", "1",
                   "--seconds", "1"], bench_root, time.perf_counter())
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "needs a TPU" in out.err


class _FakeTpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def test_refuses_fewer_chips_than_the_cell(monkeypatch, bench_root, capsys):
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu()])
    rc = cli.main(["--workload", "cylon-join.weak-4chip", "--seed", "1",
                   "--seconds", "1"], bench_root, time.perf_counter())
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "asks for 4 chips" in out.err


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        device.peaks(REPO, "TPU v9 imaginary")
    assert device.peaks(REPO, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_lower_precision_fails_the_comparison():
    """The bfloat16 control against the float64 reference, on a small
    generated TPC-H: every query's numbers held to the configuration's
    limits, and the control fails at least one of them."""
    from benchmarks.harness import tpch_data, tpch_reference as ref
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "tpch-sf10-1chip.json")) as f:
        limits = json.load(f)["limits"]
    s = jax.sharding.SingleDeviceSharding(jax.devices("cpu")[0])
    cols, counts = tpch_data.generate(0.01, 11, s)
    data = tpch_data.host_frames(cols, counts, 0.01,
                                 ref.inputs(ref.COLUMNS.keys()))
    for queries in (("q3", "q18", "q21"), ("q1", "q6")):  # the cells' mixes
        same, low = {"missing": 0}, {"missing": 0}
        for q in queries:
            want = ref.answer(q, data)
            same = compare.merge(same, compare.compare_answer(
                want, want, ref.KEYS[q]))
            low = compare.merge(low, compare.compare_answer(
                ref.answer(q, data, control=True), want, ref.KEYS[q]))
        assert compare.verdict(same, limits), queries
        assert not compare.verdict(low, limits), (queries, low)


def test_join_control_fails_the_comparison():
    import pandas as pd
    from benchmarks.harness import join_data
    rng = np.random.default_rng(0)
    side = lambda: pd.DataFrame({  # noqa: E731
        "k": rng.integers(0, 900, 1000).astype(np.int32),
        **{f"v{j}": rng.random(1000, dtype=np.float32) for j in range(3)}})
    ldf, rdf = side(), side()
    want = join_data.reference(ldf, rdf)
    low = join_data.reference(ldf, rdf, control=True)
    h = lambda df: compare.row_hashes(  # noqa: E731
        [df[c].to_numpy() for c in df.columns])
    assert compare.multiset_mismatch(h(want), h(want)) == 0
    assert compare.multiset_mismatch(h(want.sample(frac=1, random_state=1)),
                                     h(want)) == 0
    assert compare.multiset_mismatch(h(low), h(want)) > 0


@pytest.mark.parametrize("name", ["a2a_ms.join4", "sort_s.stream"])
def test_op_class_readers_read_nothing_where_no_such_op_ran(name):
    read = manifest.reader(REPO, name)
    w = traffic.Window(units=2)
    empty = tracing.Trace((0.0, 1e9), {0: [(0.0, 5e8, "m:fusion.1")]})
    assert read(cli.Reading(w, empty, {}, 0, {}, {}, 1)) is None
    op = "all_to_all.3" if name.startswith("a2a") else "sort.7"
    tr = tracing.Trace((0.0, 1e9), {0: [(0.0, 5e8, f"m:{op}")]})
    scale = 1e3 if name.startswith("a2a") else 1.0
    assert read(cli.Reading(w, tr, {}, 0, {}, {}, 1)) == \
        pytest.approx(0.25 * scale)
