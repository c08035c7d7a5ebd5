"""Device seconds per refresh pair in RF1's insert programs, read by
their stable program names (``harness/programs.py``): the write into
spare capacity (``dtable._insert_fn``) and, when a delta brings them,
the dictionary re-code (``dtable._insert_recode``) and the validity of
a column that had none (``dtable._insert_validity``); nothing is read
where none of them ran."""

from benchmarks.harness import programs

INSERT = r"^jit_dtable__insert_"


def read(r):
    if r.trace is None or not r.window.units \
            or not programs.n_programs(r.trace, INSERT):
        return None
    return programs.program_s(r.trace, INSERT) / r.window.units
