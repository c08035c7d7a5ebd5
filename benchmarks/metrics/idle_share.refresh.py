"""Share of the traced window in which chip 0 ran no operation:
1 - (union of its op intervals) / window, from the profiler trace."""


def read(r):
    if r.trace is None or 0 not in r.trace.devices:
        return None
    return 1.0 - r.trace.busy_s(0) / r.trace.window_s
