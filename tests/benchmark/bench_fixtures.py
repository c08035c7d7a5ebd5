"""Fixtures for the benchmark's own tests: a copy of the benchmark at
tiny sizes, and the harness's chip check steered onto the CPU."""
import json
import os
import shutil

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# tiny stand-ins for the configurations' scale, for CPU runs
TINY = {"tpch-sf10-1chip": {"scale_factor": 0.01},
        "cylon-join-4chip": {"rows_per_chip": 20_000}}


def copy_benchmark(dest: str, tiny: bool = True) -> str:
    """``BENCHMARK.json`` and ``benchmarks/`` copied under ``dest``, the
    configurations cut to ``TINY`` sizes."""
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(dest, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dest)
    if tiny:
        for name, upd in TINY.items():
            p = os.path.join(dest, "benchmarks", "configs", name + ".json")
            with open(p) as f:
                c = json.load(f)
            c.update(upd)
            with open(p, "w") as f:
                json.dump(c, f)
    return dest


@pytest.fixture
def bench_root(tmp_path):
    return copy_benchmark(str(tmp_path))


@pytest.fixture
def cpu_as_chip(monkeypatch):
    """The harness's chip check answered with CPU devices, and a peak
    table entry for them: the rest of a run drives the CPU backend."""
    from benchmarks.harness import device
    monkeypatch.setattr(device, "require_tpu",
                        lambda chips: jax.devices("cpu")[:chips])
    monkeypatch.setattr(device, "peaks",
                        lambda root, kind: {"hbm_bytes_per_s": 819e9})
    return device
