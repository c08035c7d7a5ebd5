"""RF1's share of its HBM roofline, in percent: the least time the
insert could take, over its programs' device seconds per pair
(``insert_s.refresh``).  Least time is RF1's rows written once at their
column widths (``harness/refresh_roofline.py``) over the chips' HBM
bandwidth (``benchmarks/peaks.json``)."""

from benchmarks.harness import programs, refresh_roofline

INSERT = r"^jit_dtable__insert_"


def read(r):
    if r.trace is None or not r.window.units \
            or not programs.n_programs(r.trace, INSERT):
        return None
    return refresh_roofline.share(
        refresh_roofline.insert_bytes(r.facts),
        programs.program_s(r.trace, INSERT) / r.window.units,
        r.chips, r.peaks)
