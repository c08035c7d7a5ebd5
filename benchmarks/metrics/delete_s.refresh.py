"""Device seconds per refresh pair in RF2's delete programs, read by
their stable program names (``harness/programs.py``): the candidate
scan of the key column (``dtable._delete_scan_fn``) and the delete
itself (``dtable._delete_fn``); nothing is read where neither ran."""

from benchmarks.harness import programs

DELETE = r"^jit_dtable__delete_"


def read(r):
    if r.trace is None or not r.window.units \
            or not programs.n_programs(r.trace, DELETE):
        return None
    return programs.program_s(r.trace, DELETE) / r.window.units
