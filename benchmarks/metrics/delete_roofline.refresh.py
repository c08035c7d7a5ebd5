"""RF2's share of its HBM roofline, in percent: the least time the
delete could take, over its programs' device seconds per pair
(``delete_s.refresh``).  Least time is orders' and lineitem's key
columns read once plus the deleted rows at full width
(``harness/refresh_roofline.py``) over the chips' HBM bandwidth: the
same work whatever implements the delete."""

from benchmarks.harness import programs, refresh_roofline

DELETE = r"^jit_dtable__delete_"


def read(r):
    if r.trace is None or not r.window.units \
            or not programs.n_programs(r.trace, DELETE):
        return None
    return refresh_roofline.share(
        refresh_roofline.delete_bytes(r.facts),
        programs.program_s(r.trace, DELETE) / r.window.units,
        r.chips, r.peaks)
