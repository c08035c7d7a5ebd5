"""Device milliseconds per join in which an all-to-all collective runs
(its synchronous op or its async start-to-done), from the profiler
trace (mean over chips).  XLA names the instruction after the JAX op
(``all_to_all.11``, opcode ``all-to-all``); nothing is read where no
such op ran."""

A2A_OPS = r"^all[-_]to[-_]all"


def read(r):
    if r.trace is None or not r.window.units or not r.trace.n_ops(A2A_OPS):
        return None
    return r.trace.op_s(A2A_OPS) * 1e3 / r.window.units
