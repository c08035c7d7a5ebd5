"""Profiler trace of the measured window, and its reduction to numbers.

``Tracer`` wraps ``jax.profiler`` around the window and reads the
written ``.xplane.pb`` back with ``jax.profiler.ProfileData``.  The
reduction works on plain tuples so that it can be checked without a
chip:

* device intervals: ``(start_ns, end_ns, "<program>:<instruction>")``
  per device, from each ``/device:TPU:<n>`` plane's ``XLA Ops`` and
  ``Async XLA Ops`` lines, named by the ``XLA Modules`` event around
  them;
* host spans: ``(start_ns, end_ns, name)`` of the benchmark's own
  ``jax.profiler.TraceAnnotation`` s, whose names start with ``bench.``.

Busy time is the union of a device's op intervals inside the window;
idle share is one minus busy over the window.  An idle gap is labelled
by the innermost benchmark span that covers its midpoint.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
_OPS_LINES = ("XLA Ops", "Async XLA Ops")
_MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float, str]


@dataclass
class Trace:
    """One traced window, reduced to intervals (nanoseconds)."""
    window: Tuple[float, float]
    devices: Dict[int, List[Interval]] = field(default_factory=dict)
    spans: List[Interval] = field(default_factory=list)
    async_ops: Dict[int, List[Interval]] = field(default_factory=dict)

    def ops(self, device: int) -> List[Interval]:
        """A device's synchronous and asynchronous ops together."""
        return self.devices.get(device, []) + self.async_ops.get(device, [])

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self, device: int) -> float:
        return union_ns(self.ops(device), *self.window) / 1e9

    def mean_busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def n_ops(self, pattern: str) -> int:
        """How many ops on any device have an instruction name matching
        ``pattern`` (a regex), inside the window."""
        rx = re.compile(pattern)
        lo, hi = self.window
        return sum(1 for d in self.devices for s, e, n in self.ops(d)
                   if e > lo and s < hi and rx.search(n.rsplit(":", 1)[-1]))

    def op_s(self, pattern: str, device: Optional[int] = None) -> float:
        """Device seconds in which an op whose instruction name matches
        ``pattern`` (a regex) runs, inside the window (the union, so
        overlapping async ops count once), averaged over the devices
        (or on one)."""
        rx = re.compile(pattern)
        devs = [device] if device is not None else list(self.devices)
        if not devs:
            return 0.0
        tot = sum(union_ns([iv for iv in self.ops(d)
                            if rx.search(iv[2].rsplit(":", 1)[-1])],
                           *self.window) for d in devs)
        return tot / len(devs) / 1e9


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` clipped to [lo, hi]."""
    tot, end = 0.0, lo
    for s, e, _ in sorted(intervals):
        s, e = max(s, end), min(e, hi)
        if e > s:
            tot += e - s
            end = e
    return tot


def idle_gaps(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] in which no interval runs."""
    gaps, end = [], lo
    for s, e, _ in sorted(intervals):
        if s > end and s > lo:
            gaps.append((end, min(s, hi)))
        end = max(end, e)
        if end >= hi:
            break
    if end < hi:
        gaps.append((end, hi))
    return [(a, b) for a, b in gaps if b > a]


def label_of(spans, t: float) -> str:
    """The innermost (latest-starting) benchmark span covering ``t``
    other than the window itself; ``"none"`` if there is none."""
    best = None
    for s, e, n in spans:
        if s <= t <= e and n != WINDOW_SPAN and (best is None or s > best[0]):
            best = (s, n)
    return best[1][len(SPAN_PREFIX):] if best else "none"


def top_ops(trace: Trace, k: int = 10) -> List[list]:
    """The ``k`` synchronous ops with the most device seconds (mean over
    devices), inside the window.  Async ops (prefetch copies, async
    collectives) overlap others and are left out here."""
    lo, hi = trace.window
    tot: Dict[str, float] = {}
    for ivs in trace.devices.values():
        for s, e, n in ivs:
            if e > lo and s < hi:
                tot[n] = tot.get(n, 0.0) + (min(e, hi) - max(s, lo))
    nd = max(len(trace.devices), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v / nd / 1e9] for n, v in best]


def top_gaps(trace: Trace, device: int = 0, k: int = 10) -> List[list]:
    """The ``k`` longest idle gaps of ``device``, each labelled by what
    the benchmark's host side was doing."""
    gaps = idle_gaps(trace.ops(device), *trace.window)
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[label_of(trace.spans, (a + b) / 2), (b - a) / 1e9]
            for a, b in gaps[:k]]


def op_name(hlo_text: str) -> str:
    """``"%sort.51 = (...) sort(...)"`` -> ``"sort.51"``: the HLO
    instruction's name, without its operands (which name other ops)."""
    return hlo_text.split(" = ", 1)[0].strip().lstrip("%")


def _device_ops(plane) -> Tuple[List[Interval], List[Interval]]:
    """A device plane's (synchronous, asynchronous) ops, each named
    ``<program>:<instruction>`` by the XLA module running around it."""
    import bisect
    lines = {line.name: list(line.events) for line in plane.lines}
    mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                  for ev in lines.get(_MODULES_LINE, []))
    starts = [m[0] for m in mods]
    out = ([], [])
    for ln, dest in zip(_OPS_LINES, out):
        for ev in lines.get(ln, []):
            s = ev.start_ns
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            dest.append((s, s + ev.duration_ns, f"{mod}:{op_name(ev.name)}"))
    return out


def load(path: str) -> Trace:
    """Reduce one ``.xplane.pb`` to a ``Trace``.  The window is the
    ``bench.window`` span; without one, there is nothing to read."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[int, List[Interval]] = {}
    async_ops: Dict[int, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            d = int(m.group(1))
            devices[d], async_ops[d] = _device_ops(plane)
            continue
        for line in plane.lines:
            if plane.name.startswith("/host"):
                spans.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name) for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
    win = [s for s in spans if s[2] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span in the trace")
    return Trace((win[0][0], win[0][1]), devices, spans, async_ops)


class Tracer:
    """Start and stop the profiler in a fixed directory; ``trace()``
    reduces what it wrote."""

    def __init__(self, logdir: str):
        self.logdir = logdir

    def start(self) -> None:
        import jax
        shutil.rmtree(self.logdir, ignore_errors=True)
        os.makedirs(self.logdir, exist_ok=True)
        jax.profiler.start_trace(self.logdir)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def trace(self) -> Trace:
        paths = glob.glob(os.path.join(self.logdir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {self.logdir}")
        return load(max(paths, key=os.path.getmtime))
