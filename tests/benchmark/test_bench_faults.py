"""Whole benchmark runs on the CPU at tiny sizes, the chip check
steered onto the CPU: a sound run is correct, and a run with the timed
path broken underneath is not, once for each fault a cell can have.
Besides the manifest's cells, an open-loop serving cell (the
``open_loop`` driver, which no cell uses yet) runs from a mix written
here."""
import json
import os
import time
from types import SimpleNamespace

import pandas as pd
import pytest

from benchmarks.harness import cli, manifest
from bench_fixtures import bench_root, cpu_as_chip  # noqa: F401

SERVE = "tpch-sf10.serve-test"


def _add_serve_cell(root):
    with open(os.path.join(root, "benchmarks", "traffic",
                           "serve-test.json"), "w") as f:
        json.dump({"driver": "open_loop", "mix": {"q6": 3, "q1": 1},
                   "rate_qps": 4.0, "session": {"views": False}}, f)
    m = manifest.load(root)
    m["workloads"].append({"name": SERVE, "config": "tpch-sf10-1chip",
                           "traffic": "serve-test", "chips": 1,
                           "why": "open loop, q6 and q1"})
    m["end_to_end"].append({"name": "serve_p90_ms", "unit": "ms",
                            "better": "lower", "bound": 0.25,
                            "source": "host_clock", "workloads": [SERVE]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)


def _run(root, workload, seconds=2.0, seed=3_000_000_019):
    if workload == SERVE:
        _add_serve_cell(root)
    args = SimpleNamespace(workload=workload, seed=seed, seconds=seconds,
                           trace=0)
    return cli.run(args, root, time.perf_counter())


def _alter_answers(monkeypatch):
    """An answer altered where it is produced: the first value of every
    numeric column of every exported result is off by 1% plus one."""
    from cylon_tpu.table import Table
    real = Table.to_pandas

    def altered(self, *a, **k):
        df = real(self, *a, **k)
        for c in df.columns:
            if len(df) and pd.api.types.is_numeric_dtype(df[c]):
                df.loc[df.index[0], c] = df[c].dtype.type(
                    df[c].iloc[0] * 1.01 + 1)
        return df
    monkeypatch.setattr(Table, "to_pandas", altered)


def _skip_the_exchange(monkeypatch):
    """The exchange between chips left out: each chip joins only the
    rows it already holds."""
    from cylon_tpu.parallel import dist_ops
    monkeypatch.setattr(dist_ops, "_try_broadcast_join", lambda *a: None)
    monkeypatch.setattr(dist_ops, "_copartition",
                        lambda dt, keys, alg, splitters: dt)


@pytest.mark.parametrize("workload", ["tpch-sf10.stream-joins", SERVE,
                                      "cylon-join.weak-4chip"])
def test_sound_run_is_correct(bench_root, cpu_as_chip, workload):
    out = _run(bench_root, workload)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["count"] == (4 if "4chip" in workload else 1)
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("workload,fault", [
    ("tpch-sf10.stream-joins", _alter_answers),
    (SERVE, _alter_answers),
    ("cylon-join.weak-4chip", _skip_the_exchange),
])
def test_fault_is_not_correct(bench_root, cpu_as_chip, monkeypatch,
                              workload, fault):
    fault(monkeypatch)
    out = _run(bench_root, workload)
    assert not out["correct"], (fault.__name__, out["compared"])
