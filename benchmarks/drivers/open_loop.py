"""``open_loop``: independent users sending requests at ``rate_qps`` into
one ``ServeSession`` (``session``: its keyword arguments), each request's
query drawn from ``mix`` (weights).

Arrivals are a Poisson process drawn from the seed, and so is each
request's query (``schedule``).  A request is timed from its scheduled
send to its pandas result in hand, waiting up to ``GRACE_S`` past the
window's close; one that fails, is rejected or never comes counts as the
longest wait there was.  Reports ``serve_p50_ms`` and ``serve_p90_ms``
(nearest rank).
"""
import functools
import time

import numpy as np

from benchmarks.harness import tpch_queries
from benchmarks.harness.traffic import (Window, percentile, span,
                                        tpch_check, warn)

GRACE_S = 60.0  # how long past the window's close a served answer may come


def schedule(rate_qps: float, seconds: float, mix, seed: int):
    """(arrival offsets in seconds, query per request): Poisson arrivals
    at ``rate_qps`` within ``seconds``, queries drawn in proportion to
    ``mix``'s weights, all from ``seed``."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_qps,
                           int(rate_qps * seconds * 2) + 16)
    offsets = np.cumsum(gaps)
    while offsets[-1] < seconds:  # the draw ran short: extend it
        offsets = np.concatenate([offsets, offsets[-1] + np.cumsum(
            rng.exponential(1.0 / rate_qps, len(gaps)))])
    offsets = offsets[offsets < seconds]
    names = sorted(mix)
    w = np.array([mix[k] for k in names], float)
    labels = rng.choice(len(names), len(offsets), p=w / w.sum())
    return offsets, [names[i] for i in labels]


class Driver:
    def __init__(self, spec, dep, ctx, seed: int):
        self.dep, self.ctx, self.seed = dep, ctx, seed
        self.mix = dict(spec["mix"])
        self.rate = float(spec["rate_qps"])
        self.session_args = dict(spec.get("session", {}))
        self.ops = {q: functools.partial(tpch_queries.QUERIES[q], ctx)
                    for q in self.mix}
        self.session = None
        self.answers = []
        self.latencies = []
        self.missing = 0

    @staticmethod
    def _export(result):
        df = result.to_pandas()
        return df, time.perf_counter()

    def _open(self):
        from cylon_tpu.serve import ServeSession
        if self.session is None:
            self.session = ServeSession(self.ctx, tables=self.dep.tables,
                                        **self.session_args)
        return self.session

    def warm(self) -> None:
        """Each query alone, then all of them at once (a batch)."""
        s = self._open()
        for q in sorted(self.mix):
            s.submit(self.ops[q], label=q,
                     export=self._export).result(timeout=900)
        hs = [s.submit(self.ops[q], label=q, export=self._export)
              for q in sorted(self.mix)]
        for h in hs:
            h.result(timeout=900)

    def run(self, seconds: float) -> Window:
        s = self._open()
        offsets, labels = schedule(self.rate, seconds, self.mix, self.seed)
        w = Window(attempted=len(labels))
        sent = []
        t0 = time.perf_counter()
        with span("window"):
            for off, q in zip(offsets, labels):
                due = t0 + off
                now = time.perf_counter()
                if due > now:
                    with span("idle"):
                        time.sleep(due - now)
                try:
                    with span("submit." + q):
                        h = s.submit(self.ops[q], label=q, block=False,
                                     export=self._export)
                except Exception as e:  # rejected: never answered
                    warn(f"request {q} rejected: {e!r}")
                    h = None
                sent.append((due, q, h))
            close = time.perf_counter()
            lat = []
            with span("collect"):
                for due, q, h in sent:
                    left = close + GRACE_S - time.perf_counter()
                    try:
                        if h is None:
                            raise RuntimeError("rejected")
                        df, stamp = h.result(timeout=max(left, 0.0))
                    except Exception as e:  # failed or never came
                        warn(f"request {q} not answered: {e!r}")
                        lat.append(None)
                        self.missing += 1
                        continue
                    lat.append(stamp - due)
                    self.answers.append((q, df))
                    if h.queue_wait_ms is not None:
                        w.queue_waits_ms.append(h.queue_wait_ms)
        w.seconds = time.perf_counter() - t0
        worst = max([x for x in lat if x is not None] +
                    [time.perf_counter() - d for d, _, _ in sent])
        lat = [worst if x is None else x for x in lat]
        self.latencies = lat
        w.units = len(self.answers)
        w.failed = self.missing
        w.e2e["serve_p50_ms"] = percentile(lat, 50) * 1e3
        w.e2e["serve_p90_ms"] = percentile(lat, 90) * 1e3
        return w

    def release(self) -> None:
        if self.session is not None:
            self.session.close()
            self.session = None

    def facts(self):
        return {}

    def check(self, ref_dir: str):
        numbers = tpch_check(self.dep, self.answers, ref_dir)
        numbers["missing"] = self.missing
        return numbers
