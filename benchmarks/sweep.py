"""Find the highest rate an open-loop mix sustains on a configuration:
one process, one set-up, then a window at each offered rate.

    python benchmarks/sweep.py --config tpch-sf10-1chip --traffic <mix> \
        --chips 1 --seed 7 --seconds 50 --rates 1,2,2.5,3

The mix is a ``benchmarks/traffic/<mix>.json`` file of the ``open_loop``
driver, whether or not a cell uses it yet.  For each rate it prints the
requests, their p50 and p90 latency, and the p50 of the first and of the
last third of the requests: a backlog that grows through the window
shows as a last third far slower than the first.  A cell's rate is then
fixed in its traffic file at about four fifths of the highest rate
without such growth.  Not run by the benchmark's own runs.
"""
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    import argparse
    sys.path.insert(0, ROOT)
    from benchmarks.harness import device, manifest, traffic

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    config = manifest.config(manifest.load(ROOT), ROOT, args.config)
    spec = manifest.traffic(ROOT, args.traffic)
    device.enable_cache(ROOT)
    devs = device.require_tpu(args.chips)
    from cylon_tpu import CylonContext
    ctx = CylonContext({"backend": "tpu", "devices": devs})
    dep = manifest.deployment(ROOT, config)(config, ctx, args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        drv = manifest.driver(ROOT, spec)(dict(spec, rate_qps=rate), dep,
                                          ctx, args.seed)
        drv.warm()
        w = drv.run(args.seconds)
        lat = drv.latencies
        third = max(len(lat) // 3, 1)
        print(json.dumps({
            "rate_qps": rate, "requests": w.attempted, "failed": w.failed,
            "window_s": w.seconds, "p50_ms": w.e2e["serve_p50_ms"],
            "p90_ms": w.e2e["serve_p90_ms"],
            "first_third_p50_ms": traffic.percentile(lat[:third], 50) * 1e3,
            "last_third_p50_ms": traffic.percentile(lat[-third:], 50) * 1e3,
            "setup_and_sweep_s": time.perf_counter() - T0}), flush=True)
        drv.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
