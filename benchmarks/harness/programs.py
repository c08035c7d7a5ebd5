"""Device time by program, and the engine's own host spans.

The engine names every program it builds after the factory that builds
it (``cylon_tpu/observe/compile.py`` ``named_jit``): ``shuffle.
_exchange_fn`` runs in the trace as XLA module
``jit_shuffle__exchange_fn(<id>)``.  ``program_s`` reads device time by
that module name, the part of an op's ``<module>:<instruction>`` name
before the colon.

The engine also marks its host phases with profiler spans named
``cylon.*`` (``cylon_tpu/trace.py``): ``cylon.host.read`` where the
host blocks on a device-to-host read, ``cylon.sync`` in a completion
barrier, ``cylon.plan.optimize`` around the planner.  ``tracing.load``
keeps only the benchmark's ``bench.*`` spans, so ``program_spans``
reads the ``cylon.*`` ones from the same profile file, on the same
clock.
"""
from __future__ import annotations

import glob
import os
import re
from typing import List, Optional

from .tracing import WINDOW_SPAN, Interval, Trace, union_ns

PROGRAM_PREFIX = "cylon."
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# where the harness's Tracer writes each cell's profile (harness/cli.py)
_TRACES = os.path.join(_ROOT, ".bench_cache", "trace")


def module_of(op: str) -> str:
    """``"jit_f(12):fusion.3"`` -> ``"jit_f(12)"``."""
    return op.split(":", 1)[0]


def program_s(trace: Trace, pattern: str,
              device: Optional[int] = None) -> float:
    """Device seconds in which an op of a program whose module name
    matches ``pattern`` (a regex) runs, inside the window: the union
    of those ops' intervals, averaged over the devices (or on one)."""
    rx = re.compile(pattern)
    devs = [device] if device is not None else list(trace.devices)
    if not devs:
        return 0.0
    tot = sum(union_ns([iv for iv in trace.ops(d)
                        if rx.search(module_of(iv[2]))], *trace.window)
              for d in devs)
    return tot / len(devs) / 1e9


def n_programs(trace: Trace, pattern: str) -> int:
    """How many ops inside the window, on any device, belong to a
    program whose module name matches ``pattern``."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    return sum(1 for d in trace.devices for s, e, n in trace.ops(d)
               if e > lo and s < hi and rx.search(module_of(n)))


def load_program_spans(path: str) -> tuple:
    """(window, spans): the ``bench.window`` interval and every host
    span whose name starts with ``cylon.``, from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window, spans = None, []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith(PROGRAM_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  name))
                elif name == WINDOW_SPAN and window is None:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    return window, spans


def program_spans(trace: Trace, traces_dir: str = _TRACES
                  ) -> List[Interval]:
    """The ``cylon.*`` host spans of ``trace``'s window: read from the
    newest profile under ``traces_dir`` whose ``bench.window`` is this
    trace's window (nothing where none is)."""
    kept = getattr(trace, "program_spans", None)
    if kept:
        return list(kept)
    paths = glob.glob(os.path.join(traces_dir, "**", "*.xplane.pb"),
                      recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        window, spans = load_program_spans(path)
        if window is not None and tuple(window) == tuple(trace.window):
            return spans
    return []


def covered_ns(gaps, spans, lo: float, hi: float) -> float:
    """Length of the parts of ``gaps`` (``(start, end)`` pairs) that
    lie under the union of ``spans``, clipped to [lo, hi]."""
    tot = 0.0
    for a, b in gaps:
        tot += union_ns([(max(s, a), min(e, b), n) for s, e, n in spans
                         if e > a and s < b], max(a, lo), min(b, hi))
    return tot
