"""Device seconds per stream in which an HLO sort runs, from the
profiler trace (mean over chips); nothing is read where no sort ran."""

SORT_OPS = r"^sort(\.\d+)?$"


def read(r):
    if r.trace is None or not r.window.units or not r.trace.n_ops(SORT_OPS):
        return None
    return r.trace.op_s(SORT_OPS) / r.window.units
