"""Host milliseconds per refresh pair blocked in the engine's
device-to-host reads: the program's ``host.read_us`` counter (the time
inside its ``cylon.host.read`` spans) over the window, over the pairs
completed; nothing is read where the program keeps no such counter."""


def read(r):
    if not r.counters or not r.window.units \
            or "host.read_us" not in r.counters:
        return None
    return r.counters["host.read_us"] / 1e3 / r.window.units
