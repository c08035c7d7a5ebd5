"""``stream``: one analyst in a closed loop.

A stream is one pass of ``queries`` in order through the planner
(``ctx.optimize``), each ending in a pandas frame.  No stream starts
after the window's seconds; the window ends when the last stream ends.
Reports ``stream_s``, the window's time over the streams completed.
"""
import functools
import time
import traceback

from benchmarks.harness import tpch_queries
from benchmarks.harness.traffic import (Window, block, span, tpch_check,
                                        warn)


class Driver:
    def __init__(self, spec, dep, ctx, seed: int):
        self.queries = list(spec["queries"])
        self.dep, self.ctx = dep, ctx
        self.ops = {q: functools.partial(tpch_queries.QUERIES[q], ctx)
                    for q in self.queries}
        self.answers = []
        self.missing = 0

    def _query(self, q: str):
        from cylon_tpu.parallel import run_pipeline
        ctx, op, tables = self.ctx, self.ops[q], self.dep.tables
        with span("plan_dispatch." + q):
            res = run_pipeline(lambda: ctx.optimize(op, tables))
        with span("wait." + q):
            block(res)
        with span("export." + q):
            return res.to_pandas()

    def _stream(self, keep: bool) -> None:
        for q in self.queries:
            try:
                df = self._query(q)
            except Exception:  # a failed query is a missing answer
                warn(f"{q} failed:\n{traceback.format_exc()}")
                self.missing += int(keep)
                continue
            if keep:
                self.answers.append((q, df))

    def warm(self) -> None:
        self._stream(keep=False)

    def run(self, seconds: float) -> Window:
        w = Window()
        t0 = time.perf_counter()
        with span("window"):
            while time.perf_counter() - t0 < seconds:
                w.attempted += len(self.queries)
                self._stream(keep=True)
                w.units += 1
        w.seconds = time.perf_counter() - t0
        w.failed = self.missing
        w.e2e["stream_s"] = w.seconds / w.units
        return w

    def release(self) -> None:
        pass

    def facts(self):
        return {}

    def check(self, ref_dir: str):
        numbers = tpch_check(self.dep, self.answers, ref_dir)
        numbers["missing"] = self.missing
        return numbers
