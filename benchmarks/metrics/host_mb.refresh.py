"""Megabytes per refresh pair that cross the host-device boundary, in
either direction, inside the engine's write spans: the program's
``ingest.host_bytes`` counter over the window, over the pairs
completed; nothing is read where the program keeps no such counter."""


def read(r):
    if not r.counters or not r.window.units \
            or "ingest.host_bytes" not in r.counters:
        return None
    return r.counters["ingest.host_bytes"] / 1e6 / r.window.units
