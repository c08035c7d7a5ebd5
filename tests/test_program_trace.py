"""The engine's own names and spans in the device trace.

Coverage contract:
  * every program the stream (q3, q18, q21) and the distributed join
    compile is named by ``observe.compile.named_jit`` after the factory
    that built it — none is ``kernel`` or ``gather_kernel``, and no two
    factories share a name;
  * under ``jax.profiler`` the host plane carries the engine's
    ``cylon.*`` spans, and the benchmark's program-span loader finds
    them on the window's clock;
  * ``host.read_us`` moves exactly when ``host.read`` does, and with
    counters off no counter moves and no span helper reads the clock;
  * the benchmark's new per-layer readers on synthetic traces, and the
    existing readers untouched by the engine's spans.
"""
import collections
import dataclasses
import functools
import os
import types

import jax
import numpy as np
import pandas as pd
import pytest

from cylon_tpu import CylonContext, JoinConfig, Table, trace
from cylon_tpu.config import JoinAlgorithm
from cylon_tpu.observe import compile as obcompile
from cylon_tpu.parallel import DTable, dist_join, run_pipeline

from benchmarks.harness import cli, manifest, programs, tracing, traffic

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STREAM = ("q3", "q18", "q21")


@pytest.fixture(autouse=True)
def _clean():
    trace.reset()
    yield
    trace.disable_counters()
    trace.reset()


@pytest.fixture(scope="module")
def tpch():
    from benchmarks.harness import tpch_data, tpch_queries
    ctx = CylonContext({"backend": "tpu", "devices": jax.devices("cpu")[:1]})
    cols, counts = tpch_data.generate(0.01, 2**31 + 23, ctx.sharding())
    tables = tpch_data.device_tables(ctx, cols, counts, 0.01)
    ops = {q: functools.partial(tpch_queries.QUERIES[q], ctx) for q in STREAM}
    return ctx, tables, ops


@pytest.fixture(scope="module")
def join4():
    from benchmarks.harness import join_data
    ctx = CylonContext({"backend": "tpu", "devices": jax.devices("cpu")[:4]})
    left, right, cap = join_data.generate(2_000, 4, 5, ctx.sharding())
    sides = join_data.device_tables(ctx, left, right, cap, 2_000)
    # the cell's join; broadcast_threshold=0 keeps a side this small off
    # the broadcast path, as the cell's 4M-row sides are
    cfg = dataclasses.replace(JoinConfig.InnerJoin(0, 0),
                              broadcast_threshold=0)
    return sides, cfg


def _stream(ctx, tables, ops):
    for q in STREAM:
        op = ops[q]
        run_pipeline(lambda: ctx.optimize(op, tables)).to_pandas()


def _assert_named(events):
    names = obcompile.program_names()
    assert events
    for e in events:
        fun = e["program"]
        assert fun.startswith("jit(") and fun.endswith(")"), fun
        label = fun[len("jit("):-1]
        assert label in names, (fun, sorted(names))
        assert label not in ("kernel", "gather_kernel")
    # one label per origin: no two factories share a name
    assert len(set(names.values())) == len(names)


def test_stream_compiles_only_named_programs(tpch):
    ctx, tables, ops = tpch
    jax.clear_caches()   # every program of the stream compiles (or loads)
    with obcompile.attribute_compiles() as events:
        _stream(ctx, tables, ops)
    _assert_named(events)
    labels = {e["program"] for e in events}
    assert "jit(dist_ops__compact_survivors)" in labels
    assert "jit(dist_ops_dist_select)" in labels


def test_dist_join_compiles_only_named_programs(join4):
    (left, right), cfg = join4
    jax.clear_caches()
    with obcompile.attribute_compiles() as events:
        out = dist_join(left, right, cfg)
        jax.block_until_ready(out.counts)
    _assert_named(events)
    labels = {e["program"] for e in events}
    for want in ("dist_ops__join_phase1_fn", "dist_ops__join_phase2_fn",
                 "shuffle__exchange_fn"):
        assert f"jit({want})" in labels, sorted(labels)


def test_named_jit_names_by_factory_and_refuses_a_taken_name():
    @obcompile.kernel_factory
    def _names_probe_fn(n: int):
        return obcompile.named_jit(lambda x: x + n)

    with obcompile.attribute_compiles() as events:
        _names_probe_fn(1)(np.arange(3))
    label = events[0]["program"][len("jit("):-1]
    assert label.startswith("test_program_trace_")
    assert label.endswith("_names_probe_fn")
    with pytest.raises(ValueError, match="needs name"):
        obcompile.named_jit(lambda x: x)
    obcompile.named_jit(lambda x: x, name="names_probe.a_b")
    with pytest.raises(ValueError, match="is taken"):
        # another origin under the same label
        obcompile.named_jit(lambda x: x, name="names_probe_a.b")


def _small_join_plan(dctx):
    df = pd.DataFrame({"k": np.arange(64) % 7, "v": np.arange(64)})
    dt = DTable.from_table(dctx, Table.from_pandas(dctx, df))
    cfg = dataclasses.replace(
        JoinConfig.InnerJoin(0, 0, algorithm=JoinAlgorithm.HASH),
        broadcast_threshold=0)
    return dt, cfg


def test_program_spans_on_the_profilers_clock(dctx, tmp_path):
    """The engine's spans are in the profile's host plane; the
    benchmark's loader finds them inside the window; the benchmark's
    own spans and readers see only ``bench.*``."""
    dt, cfg = _small_join_plan(dctx)

    def plan(t):
        return dist_join(t, t, cfg)

    dctx.optimize(plan, dt).to_table()   # compile outside the profile
    tr = tracing.Tracer(str(tmp_path / "cell"))
    tr.start()
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.dispatch.q"):
            res = dctx.optimize(plan, dt)
        res.to_table().to_pandas()
    tr.stop()
    t = tr.trace()
    spans = programs.program_spans(t, traces_dir=str(tmp_path))
    names = {n for _, _, n in spans}
    for want in ("cylon.join.count", "cylon.host.read",
                 "cylon.plan.optimize", "cylon.export"):
        assert want in names, sorted(names)
    lo, hi = t.window
    assert all(lo <= s <= e <= hi for s, e, _ in spans)
    # the existing reduction keeps the benchmark's spans alone
    assert {n for _, _, n in t.spans} == {"bench.window", "bench.dispatch.q"}
    join_count = next(s for s in spans if s[2] == "cylon.join.count")
    mid = (join_count[0] + join_count[1]) / 2
    assert tracing.label_of(t.spans, mid) == "dispatch.q"
    # a profile of another window reads nothing
    other = tracing.Trace((0.0, 1.0))
    assert programs.program_spans(other, traces_dir=str(tmp_path)) == []


def test_host_read_us_moves_with_host_reads(dctx):
    dt, cfg = _small_join_plan(dctx)
    dist_join(dt, dt, cfg).to_table()            # warm
    trace.enable_counters()
    trace.reset()
    out = dist_join(dt, dt, cfg)
    c = trace.counters()
    assert c.get("host.read", 0) > 0 and c.get("host.read_us", 0) > 0
    trace.reset()
    jax.block_until_ready(out.counts)             # no engine host read
    c = trace.counters()
    assert "host.read" not in c and "host.read_us" not in c
    trace.reset()
    dctx.optimize(lambda t: dist_join(t, t, cfg), dt).to_table()
    c = trace.counters()
    assert c.get("host.read", 0) > 0 and c.get("host.read_us", 0) > 0
    assert c.get("plan.host_us", 0) > 0


def test_counters_off_no_counter_moves_and_no_clock(dctx, monkeypatch):
    dt, cfg = _small_join_plan(dctx)
    dctx.optimize(lambda t: dist_join(t, t, cfg), dt).to_table()   # warm

    def no_clock():
        raise AssertionError("a timing call with counters off")

    monkeypatch.setattr(trace, "time",
                        types.SimpleNamespace(perf_counter=no_clock))
    assert not trace.counters_enabled()
    dctx.optimize(lambda t: dist_join(t, t, cfg), dt).to_table()
    with trace.host_read(), trace.planner():
        pass
    monkeypatch.undo()
    assert trace.counters() == {}


# ---------------------------------------------------------------------------
# the benchmark's readers (benchmarks/metrics/*.py) on synthetic traces
# ---------------------------------------------------------------------------

def _reading(tr, counters=None, units=2):
    return cli.Reading(traffic.Window(units=units), tr, counters or {}, 0,
                       {}, {}, max(len(tr.devices), 1) if tr else 1)


def _synthetic(program_spans=None):
    """Window [0, 1 s); chip 0 runs a compaction gather [0, 0.2 s) and a
    select mask [0.1, 0.3 s), both under their stable names, and the
    join's phases and exchange; chip 1 runs the exchange alone."""
    ops0 = [(0.0, 2e8, "jit_dist_ops__compact_survivors(11):fusion.1"),
            (1e8, 3e8, "jit_dist_ops_dist_select(12):fusion"),
            (3e8, 4e8, "jit_dist_ops__join_phase1_fn(13):sort.2"),
            (4e8, 5e8, "jit_dist_ops__join_phase2_fn(14):fusion.3"),
            (5e8, 6e8, "jit_shuffle__exchange_fn(15):all_to_all.1"),
            (6e8, 7e8, "jit_dist_ops__hash_pids_fn(16):fusion"),
            (7e8, 8e8, "jit_kernel(17):fusion")]
    ops1 = [(5e8, 7e8, "jit_shuffle__exchange_fn(15):fusion.2")]
    t = tracing.Trace((0.0, 1e9), {0: ops0, 1: ops1},
                      [(0.0, 1e9, "bench.window")])
    if program_spans is not None:
        t.program_spans = program_spans
    return t


def test_program_s_reads_by_module_name():
    t = _synthetic()
    assert programs.module_of("jit_a(1):fusion.2") == "jit_a(1)"
    assert programs.program_s(t, "^jit_shuffle_", device=0) \
        == pytest.approx(0.1)
    assert programs.program_s(t, "^jit_shuffle_") == pytest.approx(0.15)
    assert programs.n_programs(t, "^jit_shuffle_") == 2
    assert programs.program_s(t, "^jit_nothing") == 0.0


@pytest.mark.parametrize("name,want", [
    ("compact_s.stream", 0.3 / 2 / 2),       # union 0.3 s on chip 0
    ("local_join_s.join4", 0.2 / 2 / 2),
    ("exchange_s.join4", (0.2 + 0.2) / 2 / 2),
])
def test_program_readers(name, want):
    read = manifest.reader(REPO, name)
    assert read(_reading(_synthetic())) == pytest.approx(want)
    # a program that names nothing of theirs (the parent's jit_kernel)
    bare = tracing.Trace((0.0, 1e9), {0: [(0.0, 5e8, "jit_kernel(1):f")]})
    assert read(_reading(bare)) is None
    assert read(_reading(None)) is None


@pytest.mark.parametrize("name,counter", [
    ("host_read_ms.stream", "host.read_us"),
    ("plan_ms.stream", "plan.host_us"),
])
def test_counter_readers(name, counter):
    read = manifest.reader(REPO, name)
    t = _synthetic()
    assert read(_reading(t, {counter: 3000})) == pytest.approx(1.5)
    # the parent keeps host.read but no such counter
    assert read(_reading(t, {"host.read": 6})) is None


def test_sync_idle_reader():
    read = manifest.reader(REPO, "sync_idle_ms.join4")
    # chip 1 is the least busy: idle [0, 0.5) and [0.7, 1.0) s
    spans = [(4e8, 6e8, "cylon.host.read"), (9e8, 9.5e8, "cylon.sync"),
             (0.0, 1e9, "cylon.plan.optimize")]
    got = read(_reading(_synthetic(spans)))
    assert got == pytest.approx((1e8 + 0.5e8) / 1e6 / 2)
    assert read(_reading(_synthetic([]))) is None
    assert read(_reading(_synthetic([(0, 1e9, "cylon.export")]))) is None


def test_existing_readers_unchanged_by_program_spans():
    spans = [(0.0, 1e9, "cylon.host.read"), (2e8, 3e8, "cylon.sync")]
    old = ["idle_share.stream", "idle_share.join4", "sort_s.stream",
           "a2a_ms.join4", "host_reads.stream", "window_compiles.stream"]
    counters = {"host.read": 6}
    for name in old:
        read = manifest.reader(REPO, name)
        before = read(_reading(_synthetic(), counters))
        after = read(_reading(_synthetic(spans), counters))
        assert before == after, name
    assert tracing.top_gaps(_synthetic(spans)) == \
        tracing.top_gaps(_synthetic())
    assert tracing.top_ops(_synthetic(spans)) == \
        tracing.top_ops(_synthetic())


def _count_annotations(monkeypatch):
    seen = collections.Counter()
    real = trace.TraceAnnotation

    def counting(name):
        seen[name] += 1
        return real(name)

    monkeypatch.setattr(trace, "TraceAnnotation", counting)
    return seen


def test_annotations_per_stream_and_join(tpch, join4, monkeypatch):
    """Off the profiler each span is one TraceAnnotation enter and exit
    (about a microsecond): the stream and the join enter tens of them,
    not thousands."""
    ctx, tables, ops = tpch
    (left, right), cfg = join4
    _stream(ctx, tables, ops)                       # warm
    jax.block_until_ready(dist_join(left, right, cfg).counts)
    seen = _count_annotations(monkeypatch)
    _stream(ctx, tables, ops)
    per_stream = sum(seen.values())
    assert seen["cylon.plan.optimize"] == len(STREAM)
    assert seen["cylon.host.read"] >= 1
    seen.clear()
    jax.block_until_ready(dist_join(left, right, cfg).counts)
    per_join = sum(seen.values())
    assert seen["cylon.join.count"] == 1
    print(f"annotations: {per_stream} per stream, {per_join} per join")
    assert per_stream < 1000 and per_join < 100
