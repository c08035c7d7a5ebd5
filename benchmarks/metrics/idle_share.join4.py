"""Idle share of the traced window on the least busy of the chips:
1 - min over chips of (union of its op intervals) / window."""


def read(r):
    if r.trace is None or not r.trace.devices:
        return None
    busy = min(r.trace.busy_s(d) for d in r.trace.devices)
    return 1.0 - busy / r.trace.window_s
