"""``join``: a closed loop of distributed inner joins of the
deployment's two tables on their first column, each ending in
``block_until_ready``.  Reports ``join_s``, the window's time over the
joins completed."""
import time
import traceback

import numpy as np

from benchmarks.harness import compare, join_data
from benchmarks.harness.traffic import (Window, block, cached_reference,
                                        span, warn)


class Driver:
    def __init__(self, spec, dep, ctx, seed: int):
        from cylon_tpu import JoinConfig
        self.dep = dep
        self.cfg = JoinConfig.InnerJoin(0, 0)
        self.counts = []
        self.last = None
        self.last_host = None
        self.missing = 0

    def _join(self):
        from cylon_tpu.parallel import dist_join
        left, right = self.dep.tables
        with span("dispatch.join"):
            out = dist_join(left, right, self.cfg)
        with span("wait.join"):
            block(out)
        return out

    def warm(self) -> None:
        self._join()

    def run(self, seconds: float) -> Window:
        w = Window()
        t0 = time.perf_counter()
        with span("window"):
            while time.perf_counter() - t0 < seconds:
                w.attempted += 1
                self.last = None
                try:
                    self.last = self._join()
                except Exception:  # a failed join is a missing answer
                    warn(f"join failed:\n{traceback.format_exc()}")
                    w.failed += 1
                    continue
                self.counts.append(self.last.counts)
                w.units += 1
        w.seconds = time.perf_counter() - t0
        self.missing = w.failed
        w.e2e["join_s"] = w.seconds / max(w.units, 1)
        return w

    def release(self) -> None:
        """Read the last join's rows and every join's row count back,
        then drop the program's output."""
        import jax
        out = self.last
        if out is None:
            return
        chips, cap = out.ctx.get_world_size(), out.cap
        got = jax.device_get({"counts": out.counts, "cols": {
            c.name: c.data for c in out.columns}})
        n = np.asarray(got["counts"])
        self.last_host = {name: np.concatenate([
            np.asarray(v).reshape(chips, cap)[p, :n[p]] for p in range(chips)])
            for name, v in got["cols"].items()}
        self.counts = [int(np.asarray(c).sum())
                       for c in jax.device_get(self.counts)]
        self.last = None

    def facts(self):
        return {"rows_out": self.counts[-1]} if self.counts else {}

    def check(self, ref_dir: str):
        missing = self.missing + (self.last_host is None)

        def compute():
            ldf, rdf = self.dep.host_data()
            want = join_data.reference(ldf, rdf)
            return {"rows": len(want), "hashes": compare.row_hashes(
                [want[c].to_numpy() for c in want.columns])}
        want = cached_reference(ref_dir, f"seed{self.dep.seed}/join", compute)
        wrong = sum(abs(c - want["rows"]) for c in self.counts)
        if self.last_host is not None:
            got = compare.row_hashes(
                [self.last_host[c] for c in ("lt-k", "lt-v0", "lt-v1",
                                             "lt-v2", "rt-k", "rt-v0",
                                             "rt-v1", "rt-v2")])
            wrong += compare.multiset_mismatch(got, want["hashes"])
        return {"wrong_rows": wrong, "missing": missing}
