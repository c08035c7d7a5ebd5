"""Device-to-host reads per stream: the program's ``host.read``
counter over the window, over the streams completed."""


def read(r):
    if r.counters is None or not r.window.units:
        return None
    return r.counters.get("host.read", 0) / r.window.units
