"""Readings that the limits of ``correct`` are set from: the program's
numbers and the control's, on several seeds, at a cell's own size.

    python benchmarks/control.py --workloads tpch-sf10.stream-joins \
        --seeds 11,12,13 --seconds 10

The cells, one or more, share one configuration.  For each seed: the data is
generated once; each cell runs its warm-up and a short window at its
own load, and its answers are compared as a run compares them (the
program's reading).  Then the control, the plain reference computed in
the next precision below the configuration's (bfloat16 for float32), is
compared with the reference the same way (the control's reading).  One
JSON line per seed and cell.  Not run by the benchmark's own runs.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_numbers(dep, cell_spec, ref_dir):
    """The control's numbers for one cell: its answers against the
    reference's, as the check compares the program's."""
    from benchmarks.harness import compare, join_data, traffic
    if cell_spec["driver"] == "join":
        ldf, rdf = dep.host_data()
        h = lambda df: compare.row_hashes(  # noqa: E731
            [df[c].to_numpy() for c in df.columns])
        want = join_data.reference(ldf, rdf)
        low = join_data.reference(ldf, rdf, control=True)
        return {"wrong_rows": compare.multiset_mismatch(h(low), h(want)),
                "missing": 0}
    from benchmarks.harness import tpch_reference as ref
    queries = (cell_spec["queries"] if cell_spec["driver"] == "stream"
               else sorted(cell_spec["mix"]))
    data = dep.host_data(ref.inputs(queries))
    numbers = {"wrong_rows": 0, "rel_err": 0.0, "missing": 0}
    for q in queries:
        want = traffic.cached_reference(ref_dir, f"seed{dep.seed}/{q}",
                                        lambda: ref.answer(q, data))
        numbers = compare.merge(numbers, compare.compare_answer(
            ref.answer(q, data, control=True), want, ref.KEYS[q]))
    return numbers


def main(argv=None) -> int:
    import argparse
    sys.path.insert(0, ROOT)
    from benchmarks.harness import device, manifest

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    m = manifest.load(ROOT)
    cells = [manifest.workload(m, w) for w in args.workloads.split(",")]
    if len({c["config"] for c in cells}) != 1:
        raise SystemExit("the cells must share one configuration")
    config = manifest.config(m, ROOT, cells[0]["config"])
    device.enable_cache(ROOT)
    devs = device.require_tpu(max(c["chips"] for c in cells))
    from cylon_tpu import CylonContext
    ctx = CylonContext({"backend": "tpu", "devices": devs})
    ref_dir = os.path.join(ROOT, ".bench_cache", "ref", cells[0]["config"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        dep = manifest.deployment(ROOT, config)(config, ctx, seed)
        for cell in cells:
            t0 = time.perf_counter()
            spec = manifest.traffic(ROOT, cell["traffic"])
            drv = manifest.driver(ROOT, spec)(spec, dep, ctx, seed)
            drv.warm()
            w = drv.run(args.seconds)
            drv.release()
            program = drv.check(ref_dir)
            t1 = time.perf_counter()
            low = control_numbers(dep, spec, ref_dir)
            print(json.dumps({
                "workload": cell["name"], "seed": seed,
                "program": program, "control": low,
                "limits": config["limits"], "units": w.units,
                "program_s": t1 - t0,
                "control_s": time.perf_counter() - t1}), flush=True)
        dep.free()
    return 0


if __name__ == "__main__":
    sys.exit(main())
