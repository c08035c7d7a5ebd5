"""One run of one cell: load, warm up, measure, check, print one line.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (``setup_s``) runs from the start of the process to the start of
the window: JAX's start-up, data generated on the device from the seed,
and one pass of the cell's own traffic (loading or
compiling every program the window uses).  Then the window runs for
``--seconds``.  With ``--trace 1`` the profiler records the window and
the result carries the cell's per-layer metrics; with ``--trace 0`` its
end-to-end metrics.  After the window: the peak device memory is read,
the program's state is freed, and the answers are compared with the
plain reference (not counted in ``setup_s``).

The last line of standard output is one JSON object; the numbers
compared, each beside its limit, are the last lines of standard error
and the last key of that object.  No TPU, or fewer chips than the cell
asks for: exit 1 and no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from . import compare, device, manifest


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Reading:
    """What a per-layer metric reader gets: the window, the trace, the
    program's counters over the window, the compiles in it, the
    deployment's sizes and the chip's peaks."""

    def __init__(self, window, trace, counters, compiles, facts, peaks,
                 chips):
        self.window, self.trace = window, trace
        self.counters, self.compiles = counters, compiles
        self.facts, self.peaks, self.chips = facts, peaks, chips


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _counter_delta(before, after):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if isinstance(v, (int, float)) and v != before.get(k, 0)}


def run(args, root: str, t0: float) -> dict:
    """One run; returns the result object (raises ``device.NoChip``)."""
    m = manifest.load(root)
    cell = manifest.workload(m, args.workload)
    config = manifest.config(m, root, cell["config"])
    spec = manifest.traffic(root, cell["traffic"])
    cache = os.path.join(root, ".bench_cache")
    _log(f"compile cache: {device.enable_cache(root)}")
    devs = device.require_tpu(cell["chips"])
    kind = devs[0].device_kind
    peaks = device.peaks(root, kind)

    from cylon_tpu import CylonContext, trace as ptrace
    from . import tracing

    ctx = CylonContext({"backend": "tpu", "devices": devs})
    if args.trace:
        ptrace.enable_counters()
    compiles = device.CompileCounter()
    t_data = time.perf_counter()
    dep = manifest.deployment(root, config)(config, ctx, args.seed)
    driver = manifest.driver(root, spec)(spec, dep, ctx, args.seed)
    t_warm = time.perf_counter()
    driver.warm()
    setup_s = time.perf_counter() - t0
    _log(f"set-up {setup_s:.3f} s (start {t_data - t0:.3f}, data "
         f"{t_warm - t_data:.3f}, warm-up {t0 + setup_s - t_warm:.3f})")

    tracer = None
    if args.trace:
        tracer = tracing.Tracer(os.path.join(cache, "trace", args.workload))
        tracer.start()
    c0 = ptrace.counters() if args.trace else {}
    compiles.counting = True
    window = driver.run(args.seconds)
    compiles.counting = False
    counters = _counter_delta(c0, ptrace.counters()) if args.trace else {}
    if tracer:
        tracer.stop()
    _log(f"window {window.seconds:.3f} s, {window.units} done, "
         f"{window.attempted} attempted, {window.failed} failed, "
         f"{compiles.n} compiles")
    mem_peak = device.memory_peak(devs)
    facts = dep.facts()
    driver.release()
    facts.update(driver.facts())
    dep.free()

    t1 = time.perf_counter()
    numbers = driver.check(os.path.join(cache, "ref", cell["config"]))
    limits = config["limits"]
    ok = compare.verdict(numbers, limits)
    _log(f"check {time.perf_counter() - t1:.3f} s")

    if args.trace:
        tr = tracer.trace()
        reading = Reading(window, tr, counters, compiles.n, facts, peaks,
                          len(devs))
        metrics = {}
        for x in manifest.per_layer_metrics(m, args.workload):
            v = manifest.reader(root, x["name"])(reading)
            if v is not None:
                metrics[x["name"]] = {"value": v, "unit": x["unit"]}
    else:
        values = dict(window.e2e, setup_s=setup_s)
        metrics = {x["name"]: {"value": values[x["name"]], "unit": x["unit"]}
                   for x in manifest.e2e_metrics(m, args.workload)}
    out = {"correct": ok, "attempted": window.attempted,
           "failed": window.failed, "metrics": metrics,
           "device": {"platform": devs[0].platform, "kind": kind,
                      "count": len(devs), "memory_peak_bytes": mem_peak}}
    if args.trace:
        out["device"]["busy_s"] = tr.mean_busy_s()
        out["device"]["window_s"] = tr.window_s
        out["breakdown"] = {"device_ops": tracing.top_ops(tr),
                            "idle_gaps": tracing.top_gaps(tr)}
    out["compared"] = compare.report(numbers, limits)
    return out


def main(argv, root: str, t0: float) -> int:
    args = parse(argv)
    try:
        out = run(args, root, t0)
    except device.NoChip as e:
        _log(f"refused: {e}")
        return 1
    for k, v in out["compared"].items():
        print(f"compared {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
