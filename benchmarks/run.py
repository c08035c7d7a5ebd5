"""Run one benchmark cell once and print its result line.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

See ``benchmarks/harness/cli.py`` for what a run does, and
``BENCHMARK.json`` for the cells.
"""
import time

_T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    from benchmarks.harness import cli
    sys.exit(cli.main(sys.argv[1:], ROOT, _T0))
