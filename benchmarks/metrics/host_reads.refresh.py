"""Device-to-host reads per refresh pair: the program's ``host.read``
counter over the window, over the pairs completed; nothing is read
where the program keeps no such counter."""


def read(r):
    if not r.counters or not r.window.units:
        return None
    return r.counters.get("host.read", 0) / r.window.units
