"""Milliseconds per join in which the least busy chip is idle while
the host is blocked on the device: the idle gaps of that chip that lie
under the program's ``cylon.host.read`` or ``cylon.sync`` spans
(``harness/programs.py``); nothing is read where the program marks no
such span."""

from benchmarks.harness import programs, tracing

BLOCKING = ("cylon.host.read", "cylon.sync")


def read(r):
    if r.trace is None or not r.trace.devices or not r.window.units:
        return None
    spans = [s for s in programs.program_spans(r.trace) if s[2] in BLOCKING]
    if not spans:
        return None
    d = min(r.trace.devices, key=r.trace.busy_s)
    gaps = tracing.idle_gaps(r.trace.ops(d), *r.trace.window)
    return programs.covered_ns(gaps, spans, *r.trace.window) / 1e6 \
        / r.window.units
