"""Host milliseconds per refresh pair inside the engine's write spans
(``cylon.ingest.append`` and ``cylon.ingest.delete``) less the host
reads inside them: the program's ``ingest.host_us`` counter over the
window, over the pairs completed; nothing is read where the program
keeps no such counter."""


def read(r):
    if not r.counters or not r.window.units \
            or "ingest.host_us" not in r.counters:
        return None
    return r.counters["ingest.host_us"] / 1e3 / r.window.units
