"""Host milliseconds per stream in the planner and dispatcher: the
program's ``plan.host_us`` counter (time inside ``plan.optimize`` less
the host reads inside it) over the window, over the streams completed;
nothing is read where the program keeps no such counter."""


def read(r):
    if not r.counters or not r.window.units \
            or "plan.host_us" not in r.counters:
        return None
    return r.counters["plan.host_us"] / 1e3 / r.window.units
