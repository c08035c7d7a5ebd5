"""Flight recorder + device-truth profiling (ISSUE 12): compile
tracking, measured peak memory, cost-model calibration, SLO alerting.

Coverage contract:
  * every XLA compile is counted (the compile listener), hand-rolled
    program caches included; ``kernel_factory`` counts hits/misses,
    abstract plan runs count nothing, per-query compile_ms is
    attributed, and recompile storms are detected naming the
    thrashing key component;
  * ``devmem`` reads allocator truth where available and degrades to
    live-buffer accounting on CPU; EXPLAIN ANALYZE annotates every
    exchange with ``peak=predicted/observed bytes`` AND (with a probed
    mesh) ``exchange_ms=predicted/observed``, and the stats store
    round-trips both;
  * the calibrate CLI exits 0 on a self-consistent store, 1 on a
    seeded-drift fixture, 2 on a missing/empty store;
  * the flight-recorder ring is bounded with visible retention, dumps
    render through doctor, and a seeded chaos failure produces a
    bundle of identical SHAPE across identical runs;
  * ``submit(deadline_ms=)`` attributes a miss to exactly the right
    handle; the sampler's anomaly rules raise structured alerts; the
    sampler and the host pipeline shut down deterministically.
"""
import io
import json
import os
import threading
import time

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

from cylon_tpu import Table, config, faults, observe, trace
from cylon_tpu import logging as glog
from cylon_tpu.observe import compile as obcompile
from cylon_tpu.observe import devmem, doctor, flightrec
from cylon_tpu.parallel import (DTable, dist_groupby, dist_select,
                                meshprobe, shuffle_table)
from cylon_tpu.serve import ServeSession


@pytest.fixture(autouse=True)
def _clean_diagnosis():
    trace.reset()
    yield
    trace.disable()
    trace.disable_counters()
    trace.reset()
    obcompile.clear_state()
    meshprobe.clear_profiles()
    from cylon_tpu.parallel import shuffle
    shuffle.clear_chunk_state()


def _tables(dctx, rng, n_l=400, n_r=40):
    ldf = pd.DataFrame({"k": rng.integers(0, n_r, n_l),
                        "a": rng.normal(size=n_l)})
    rdf = pd.DataFrame({"k": np.arange(n_r), "b": rng.normal(size=n_r)})
    return (DTable.from_table(dctx, Table.from_pandas(dctx, ldf)),
            DTable.from_table(dctx, Table.from_pandas(dctx, rdf)))


def _plan_shuffle_groupby(t):
    return dist_groupby(shuffle_table(t["l"], ["k"]), ["k"],
                        [("a", "sum")])


# ---------------------------------------------------------------------------
# compile tracking (observe.compile)
# ---------------------------------------------------------------------------

def test_kernel_factory_counts_builds_hits_and_signatures():
    built = []

    @obcompile.kernel_factory
    def _diag_toy_fn(n: int):
        built.append(n)
        return obcompile.named_jit(lambda x: x + n)

    # host inputs: no device op of the test's own compiles in between
    x4, x8 = np.arange(4), np.arange(8)
    trace.enable_counters()
    trace.reset()
    _diag_toy_fn(1)(x4)
    c = trace.counters()
    assert c.get("compile.cache_misses", 0) == 1
    assert c.get("compile.builds", 0) == 1
    assert c.get("compile.build_us", 0) > 0
    # same key + same shape: factory hit, no new build
    _diag_toy_fn(1)(x4)
    c = trace.counters()
    assert c.get("compile.cache_hits", 0) >= 1
    assert c.get("compile.builds", 0) == 1
    assert built == [1]
    # same key, NEW shape: jit re-traces — a second build, no miss
    _diag_toy_fn(1)(x8)
    c = trace.counters()
    assert c.get("compile.builds", 0) == 2
    assert c.get("compile.cache_misses", 0) == 1
    # new key: a factory miss AND a build
    _diag_toy_fn(2)(x4)
    c = trace.counters()
    assert c.get("compile.cache_misses", 0) == 2
    assert c.get("compile.builds", 0) == 3
    assert built == [1, 2]
    # counters off: the listener counts nothing
    trace.disable_counters()
    _diag_toy_fn(3)(x4)
    assert trace.counters().get("compile.builds", 0) == 3


def test_kernel_factory_passes_abstract_runs_through():
    @obcompile.kernel_factory
    def _diag_abs_fn(n: int):
        return obcompile.named_jit(lambda x: x * n)

    x5 = np.arange(5, dtype=np.int32)
    trace.enable_counters()
    trace.reset()
    out = jax.eval_shape(lambda x: _diag_abs_fn(3)(x),
                         jax.ShapeDtypeStruct((5,), jnp.int32))
    assert out.shape == (5,)
    # the abstract call compiled nothing and recorded nothing
    assert trace.counters().get("compile.builds", 0) == 0
    # the first CONCRETE call still counts its compile
    _diag_abs_fn(3)(x5)
    assert trace.counters().get("compile.builds", 0) == 1


def test_attribute_compiles_collects_per_scope():
    @obcompile.kernel_factory
    def _diag_attr_fn(n: int):
        return obcompile.named_jit(lambda x: x - n)

    x3 = np.arange(3)
    with obcompile.attribute_compiles() as events:
        _diag_attr_fn(7)(x3)
    assert len(events) == 1
    # the program carries its factory's stable name
    assert events[0]["program"].endswith("_diag_attr_fn)")
    assert events[0]["compile_ms"] > 0
    # outside the scope nothing is attributed
    with obcompile.attribute_compiles() as events2:
        _diag_attr_fn(7)(x3)   # seen signature — no compile
    assert events2 == []


def test_compile_outside_any_kernel_factory_counts(dctx, rng):
    """A program of the hand-rolled select cache (``dist_ops.
    _select_cache``), which no kernel factory builds: the old
    first-call timer never saw it; the compile listener does."""
    lt, _ = _tables(dctx, rng)

    def _diag_pred(env):
        return env["a"] > 0.25

    trace.enable_counters()
    trace.reset()
    with obcompile.attribute_compiles() as events:
        dist_select(lt, _diag_pred)
    programs = {e["program"] for e in events}
    assert "jit(dist_ops_dist_select)" in programs, programs
    assert trace.counters().get("compile.builds", 0) >= len(events) >= 1
    assert obcompile.program_names()["dist_ops_dist_select"] \
        == "dist_ops.dist_select"


def test_recompile_storm_warns_once_naming_the_component(monkeypatch):
    monkeypatch.setattr(obcompile, "STORM_KEYS", 3)
    buf = io.StringIO()
    glog.set_sink(buf)
    try:
        trace.enable_counters()
        trace.reset()

        @obcompile.kernel_factory
        def _diag_storm_fn(mesh, block: int):
            return jax.jit(lambda x: x * block)

        for b in (8, 16, 32, 64):
            _diag_storm_fn("m", b)(jnp.arange(4))
    finally:
        glog.set_sink(__import__("sys").stderr)
    out = buf.getvalue()
    assert "recompile storm" in out
    assert "_diag_storm_fn" in out
    assert "block=" in out, out     # the differing component is NAMED
    assert out.count("recompile storm") == 1   # warn_once rate limit
    assert trace.counters().get("compile.storms", 0) >= 1


def test_analyze_totals_carry_compile_ms(dctx, rng):
    lt, _ = _tables(dctx, rng)
    rep = lt.explain(lambda t: shuffle_table(t, ["k"]), analyze=True)
    assert rep.ok
    assert "compile_ms" in rep.totals and "compiles" in rep.totals
    assert rep.totals["compile_ms"] >= 0.0


def test_served_handle_carries_compile_ms(dctx, rng):
    lt, rt = _tables(dctx, rng, n_l=1217, n_r=61)
    with ServeSession(dctx, tables={"l": lt, "r": rt},
                      batch_window_ms=10.0) as s:
        h = s.submit(_plan_shuffle_groupby, label="cq")
        h.result(timeout=300)
    assert h.compile_ms is not None and h.compile_ms >= 0.0


# ---------------------------------------------------------------------------
# device-truth memory (observe.devmem)
# ---------------------------------------------------------------------------

def test_devmem_snapshot_and_cpu_fallback(monkeypatch):
    s = devmem.snapshot()
    assert s.source in ("memory_stats", "live-buffers")
    assert s.live_bytes >= 0
    # force the portable fallback: a backend with no allocator stats
    monkeypatch.setattr(devmem, "_backend_stats", lambda dev: None)
    keep = jnp.arange(1024, dtype=jnp.int32)   # a live buffer to count
    s2 = devmem.snapshot()
    assert s2.source == "live-buffers"
    assert s2.peak_bytes is None
    assert s2.live_bytes >= keep.nbytes


def test_observed_exchange_bytes_semantics():
    S = devmem.DevMemSample
    # allocator truth, peak moved inside the window: peak - live_before
    assert devmem.observed_exchange_bytes(
        S(100, 1000, "memory_stats"), S(200, 5000, "memory_stats")) \
        == 4900
    # peak did NOT move (stale high-water): live delta
    assert devmem.observed_exchange_bytes(
        S(100, 5000, "memory_stats"), S(300, 5000, "memory_stats")) \
        == 200
    # live-buffer fallback: live delta, clamped at zero
    assert devmem.observed_exchange_bytes(
        S(500, None, "live-buffers"), S(400, None, "live-buffers")) == 0
    assert devmem.observed_exchange_bytes(None,
                                          S(0, None, "x")) is None


def test_analyze_annotates_predicted_vs_observed_peak(dctx, rng):
    lt, rt = _tables(dctx, rng)
    observe.STATS_STORE.clear()
    rep = lt.explain(_plan_shuffle_groupby, tables={"l": lt, "r": rt},
                     analyze=True, optimize=True)
    assert rep.ok
    peaks = [n.info.get("peak") for n in rep.nodes
             if n.info.get("peak")]
    assert peaks, "every sized exchange carries a peak annotation"
    assert "predicted" in peaks[0] and "observed" in peaks[0] \
        and "bytes" in peaks[0]
    assert trace.counters().get("devmem.samples", 0) >= 1
    # the stats store round-trips the observed peaks per fingerprint
    assert rep.stats_digests
    rec = observe.STATS_STORE.get(rep.stats_digests[0])
    stored = [n.get("peak") for n in rec["nodes"] if n.get("peak")]
    assert stored and "observed" in stored[0]


def test_analyze_shows_both_ms_and_peak_annotations(dctx, rng):
    """The acceptance shape: one analyzed shuffled query carries BOTH
    audit columns per exchange — meshprobe ms and device-truth bytes."""
    lt, rt = _tables(dctx, rng)
    meshprobe.probe(dctx, sizes=(1 << 10, 1 << 12), reps=1)
    rep = lt.explain(_plan_shuffle_groupby, tables={"l": lt, "r": rt},
                     analyze=True, optimize=True)
    assert rep.ok
    both = [n for n in rep.nodes
            if n.info.get("exchange_ms") and n.info.get("peak")]
    assert both, "an exchange node carries ms AND peak annotations"


# ---------------------------------------------------------------------------
# cost-model calibration (analysis/calibrate.py)
# ---------------------------------------------------------------------------

def _write_stats(path, predicted, observed, unit="ms"):
    ann = (f"single-shot: predicted {predicted} / observed "
           f"{observed} {unit}")
    field = "exchange_ms" if unit == "ms" else "peak"
    with open(path, "w") as f:
        json.dump({"d1": {"runs": 1, "label": "q1",
                          "nodes": [{"op": "shuffle_table",
                                     field: ann}]}}, f)


def test_calibrate_parse_annotation():
    from cylon_tpu.analysis.calibrate import parse_annotation
    got = parse_annotation(
        "single-shot: predicted 1.50 / observed 3.00 ms | "
        "ring: predicted 2048 / observed 1024 bytes")
    assert got == [("single-shot", 1.5, 3.0, "ms"),
                   ("ring", 2048.0, 1024.0, "bytes")]
    assert parse_annotation(None) == []
    assert parse_annotation("no pairs here") == []


def test_calibrate_exit_codes(tmp_path):
    from cylon_tpu.analysis import calibrate
    ok = str(tmp_path / "ok.json")
    _write_stats(ok, 1.0, 1.2)
    assert calibrate.main(["--stats", ok]) == 0
    drift = str(tmp_path / "drift.json")
    _write_stats(drift, 1.0, 50.0)        # 49x off: any sane gate trips
    assert calibrate.main(["--stats", drift]) == 1
    bdrift = str(tmp_path / "bdrift.json")
    _write_stats(bdrift, 1000, 64000, unit="bytes")
    assert calibrate.main(["--stats", bdrift]) == 1
    assert calibrate.main(["--stats", str(tmp_path / "nope.json")]) == 2
    empty = str(tmp_path / "empty.json")
    with open(empty, "w") as f:
        json.dump({}, f)
    assert calibrate.main(["--stats", empty]) == 2
    # records without predicted/observed pairs: cold, not drifted
    cold = str(tmp_path / "cold.json")
    with open(cold, "w") as f:
        json.dump({"d2": {"runs": 1,
                          "nodes": [{"op": "dist_join"}]}}, f)
    assert calibrate.main(["--stats", cold]) == 0


def test_calibrate_green_on_real_analyze_store(dctx, rng, tmp_path,
                                               monkeypatch):
    """The acceptance loop: ANALYZE with a probed mesh populates a
    stats file whose peak/ms samples calibrate reads back; generous
    explicit thresholds keep the green leg deterministic on a noisy
    shared host."""
    from cylon_tpu.analysis import calibrate
    path = str(tmp_path / "stats.json")
    observe.STATS_STORE.clear()
    monkeypatch.setenv("CYLON_STATS_PATH", path)
    lt, rt = _tables(dctx, rng)
    meshprobe.probe(dctx, sizes=(1 << 10, 1 << 12), reps=1)
    rep = lt.explain(_plan_shuffle_groupby, tables={"l": lt, "r": rt},
                     analyze=True, optimize=True)
    assert rep.ok and rep.stats_digests
    observe.STATS_STORE.save(path)
    assert calibrate.main(["--stats", path, "--max-ms-error", "1e9",
                           "--max-bytes-error", "1e9"]) == 0
    observe.STATS_STORE.clear()


# ---------------------------------------------------------------------------
# flight recorder + doctor
# ---------------------------------------------------------------------------

def test_flightrec_ring_is_bounded_with_visible_retention():
    flightrec.clear()
    for i in range(flightrec.CAPACITY + 44):
        flightrec.note("probe", i=i)
    evs = flightrec.events()
    assert len(evs) == flightrec.CAPACITY
    assert flightrec.dropped() == 44
    # oldest dropped, newest retained
    assert evs[-1]["i"] == flightrec.CAPACITY + 43
    assert evs[0]["i"] == 44
    flightrec.clear()
    assert flightrec.events() == [] and flightrec.dropped() == 0


def test_flightrec_dump_renders_through_doctor(tmp_path, capsys):
    flightrec.clear()
    flightrec.note("query", label="qx", qid=1, status="done",
                   latency_ms=1.5)
    flightrec.note("alert", rule="p99-drift", detail="synthetic")
    path = str(tmp_path / "bundle.json")
    got = flightrec.dump(path, reason="test")
    assert got == path and os.path.exists(path)
    assert doctor.main([path]) == 0
    out = capsys.readouterr().out
    assert "flight-recorder bundle" in out
    assert "p99-drift" in out and "qx" in out
    assert doctor.main([str(tmp_path / "missing.json")]) == 2
    not_bundle = tmp_path / "x.json"
    not_bundle.write_text("{}")
    assert doctor.main([str(not_bundle)]) == 2
    flightrec.clear()


def _chaos_serve_bundle(dctx, tables, outdir, monkeypatch):
    flightrec.clear()
    os.makedirs(outdir, exist_ok=True)
    monkeypatch.setenv("CYLON_FLIGHTREC_DIR", str(outdir))
    plan = faults.FaultPlan(seed=5, rules=[
        faults.FaultRule("compact.read_counts", kind="permanent",
                         once=True)])
    with faults.active(plan):
        with ServeSession(dctx, tables=tables,
                          batch_window_ms=40.0) as s:
            hs = [s.submit(_plan_shuffle_groupby, label=f"c{i}")
                  for i in range(3)]
            for h in hs:
                h._event.wait(300)
    assert sum(1 for h in hs if h.error is not None) == 1
    bundles = sorted(f for f in os.listdir(outdir)
                     if f.startswith("flightrec-"))
    assert bundles, "the CylonError produced a bundle"
    with open(os.path.join(outdir, bundles[-1])) as f:
        return json.load(f), hs


def test_dump_on_chaos_is_shape_deterministic(dctx, rng, tmp_path,
                                              monkeypatch):
    """Same seed, same call sequence → bundles of identical SHAPE:
    section keys, event-kind sequence, per-query statuses, error type."""
    lt, rt = _tables(dctx, rng)
    tables = {"l": lt, "r": rt}

    def shape(doc):
        return (sorted(doc.keys()),
                [e["kind"] for e in doc["events"]],
                [(q.get("label"), q.get("status"))
                 for q in doc["queries"]],
                (doc["error"] or {}).get("type"))

    doc1, _ = _chaos_serve_bundle(dctx, tables, tmp_path / "a",
                                  monkeypatch)
    glog.reset_warn_once()
    doc2, _ = _chaos_serve_bundle(dctx, tables, tmp_path / "b",
                                  monkeypatch)
    assert shape(doc1) == shape(doc2)
    assert doc1["error"]["type"] == "PermanentFault"
    flightrec.clear()


def test_auto_dump_requires_dir_and_is_capped(dctx, rng, tmp_path,
                                              monkeypatch):
    flightrec.clear()
    monkeypatch.delenv("CYLON_FLIGHTREC_DIR", raising=False)
    assert flightrec.maybe_dump_on_error(
        "x", ValueError("boom")) is None
    monkeypatch.setenv("CYLON_FLIGHTREC_DIR", str(tmp_path))
    paths = [flightrec.maybe_dump_on_error("x", ValueError("boom"))
             for _ in range(flightrec.MAX_AUTO_DUMPS + 2)]
    written = [p for p in paths if p is not None]
    assert len(written) == flightrec.MAX_AUTO_DUMPS
    flightrec.clear()


# ---------------------------------------------------------------------------
# SLO alerting: deadlines + sampler anomaly rules
# ---------------------------------------------------------------------------

def test_deadline_miss_attributed_to_the_right_handle(dctx, rng):
    lt, rt = _tables(dctx, rng)
    flightrec.clear()
    with ServeSession(dctx, tables={"l": lt, "r": rt},
                      batch_window_ms=10.0) as s:
        tight = s.submit(_plan_shuffle_groupby, label="tight",
                         deadline_ms=0.001)
        loose = s.submit(_plan_shuffle_groupby, label="loose",
                         deadline_ms=1e9)
        tight.result(timeout=300)
        loose.result(timeout=300)
        stats = s.stats()
    assert tight.deadline_missed is True
    assert loose.deadline_missed is False
    assert stats["slo_violations"] == 1
    misses = [e for e in flightrec.events()
              if e["kind"] == "deadline_miss"]
    assert len(misses) == 1 and misses[0]["query"] == "tight"
    # a missed deadline still returns the result — observability, not
    # cancellation
    assert tight.status == "done"
    flightrec.clear()


def test_deadline_validation(dctx, rng):
    lt, rt = _tables(dctx, rng)
    from cylon_tpu.status import CylonError
    with ServeSession(dctx, tables={"l": lt, "r": rt}) as s:
        with pytest.raises(CylonError):
            s.submit(_plan_shuffle_groupby, deadline_ms=0)
        with pytest.raises(CylonError):
            s.submit(_plan_shuffle_groupby, deadline_ms=-5)


def _synthetic_history(sampler, n, qps=10.0, p99=20.0, ratio=0.9,
                       depth=0):
    for i in range(n):
        sampler._append({"t": float(i), "completed": i, "failed": 0,
                         "deferred": 0, "queue_depth": depth,
                         "qps": qps, "p50_ms": p99 / 2, "p99_ms": p99,
                         "cache_hit_ratio": ratio, "subplan_shared": 0,
                         "share_delta": 0, "exchange_bytes_peak": 0})


def test_sampler_p99_drift_alert():
    flightrec.clear()
    s = observe.TimeSeriesSampler(period_s=10.0, capacity=64,
                                  min_history=4)
    _synthetic_history(s, 6, p99=20.0)
    buf = io.StringIO()
    glog.set_sink(buf)
    try:
        s._check_anomalies({"t": 9.0, "qps": 10.0, "p99_ms": 200.0,
                            "queue_depth": 0, "cache_hit_ratio": 0.9})
    finally:
        glog.set_sink(__import__("sys").stderr)
    assert [a["rule"] for a in s.alerts] == ["p99-drift"]
    assert "SLO alert [p99-drift]" in buf.getvalue()
    fired = [e for e in flightrec.events() if e["kind"] == "alert"]
    assert fired and fired[0]["rule"] == "p99-drift"
    flightrec.clear()


def test_sampler_qps_collapse_needs_queued_demand():
    s = observe.TimeSeriesSampler(period_s=10.0, capacity=64,
                                  min_history=4)
    _synthetic_history(s, 6, qps=40.0)
    # idle (no queue): a QPS drop is not a collapse
    s._check_anomalies({"t": 9.0, "qps": 1.0, "p99_ms": 20.0,
                        "queue_depth": 0, "cache_hit_ratio": 0.9})
    assert s.alerts == []
    s._check_anomalies({"t": 10.0, "qps": 1.0, "p99_ms": 20.0,
                        "queue_depth": 3, "cache_hit_ratio": 0.9})
    assert [a["rule"] for a in s.alerts] == ["qps-collapse"]


def test_sampler_cache_hit_collapse_alert():
    s = observe.TimeSeriesSampler(period_s=10.0, capacity=64,
                                  min_history=4)
    _synthetic_history(s, 6, ratio=0.9)
    s._check_anomalies({"t": 9.0, "qps": 10.0, "p99_ms": 20.0,
                        "queue_depth": 0, "cache_hit_ratio": 0.1})
    assert [a["rule"] for a in s.alerts] == ["cache-hit-collapse"]


def test_sampler_below_min_history_stays_silent():
    s = observe.TimeSeriesSampler(period_s=10.0, capacity=64,
                                  min_history=8)
    _synthetic_history(s, 3)
    s._check_anomalies({"t": 9.0, "qps": 0.01, "p99_ms": 9999.0,
                        "queue_depth": 5, "cache_hit_ratio": 0.0})
    assert s.alerts == []


def test_sampler_alerts_bump_slo_counter_and_session_tally(dctx, rng):
    lt, rt = _tables(dctx, rng)
    trace.enable_counters()
    trace.reset()
    with ServeSession(dctx, tables={"l": lt, "r": rt},
                      batch_window_ms=5.0) as sess:
        s = observe.TimeSeriesSampler(period_s=10.0, capacity=64,
                                      session=sess, min_history=4)
        _synthetic_history(s, 6, p99=10.0)
        s._check_anomalies({"t": 9.0, "qps": 10.0, "p99_ms": 500.0,
                            "queue_depth": 0, "cache_hit_ratio": 0.9})
        stats = sess.stats()
    assert stats["slo_violations"] == 1
    assert trace.counters().get("serve.slo_violations", 0) == 1


# ---------------------------------------------------------------------------
# deterministic shutdown (the interpreter-exit satellite)
# ---------------------------------------------------------------------------

def test_sampler_stop_is_deterministic_and_idempotent():
    s = observe.TimeSeriesSampler(period_s=0.01, capacity=16,
                                  alerts=False)
    s.start()
    t = s._thread
    assert t is not None and t.is_alive()
    s.stop()
    assert s._thread is None and not t.is_alive()
    s.stop()   # idempotent
    assert s.samples(), "the final sample landed"


def test_host_pipeline_close_joins_workers():
    from cylon_tpu.parallel.streaming import HostPipeline
    p = HostPipeline(workers=2, name="diag-pipe")
    results = [p.submit(lambda i=i: i * 2) for i in range(4)]
    assert [t.wait(10) for t in results] == [0, 2, 4, 6]
    threads = list(p._threads)
    p.close()
    assert all(not t.is_alive() for t in threads)
    p.close()  # idempotent


def test_serve_close_leaves_no_running_threads(dctx, rng):
    lt, rt = _tables(dctx, rng)
    s = ServeSession(dctx, tables={"l": lt, "r": rt},
                     batch_window_ms=5.0)
    h = s.submit(_plan_shuffle_groupby)
    h.result(timeout=300)
    dispatcher = s._dispatcher
    pipeline_threads = list(s._pipeline._threads)
    s.close()
    assert not dispatcher.is_alive()
    assert all(not t.is_alive() for t in pipeline_threads)


def test_stats_store_atexit_flush_skips_a_held_lock(tmp_path):
    """The shutdown race: a frozen daemon thread holding the store lock
    must not deadlock the atexit flush — the bounded acquire skips."""
    from cylon_tpu.observe.stats import StatsStore
    store = StatsStore(path=str(tmp_path / "s.json"))
    store.record_run("d1", latency_ms=1.0)
    assert store._lock.acquire()
    try:
        t0 = time.perf_counter()
        store._flush_at_exit()            # must return, not hang
        assert time.perf_counter() - t0 < 10
    finally:
        store._lock.release()
    store._flush_at_exit()                # and flush when it can
    assert StatsStore(path=str(tmp_path / "s.json")).get("d1")
