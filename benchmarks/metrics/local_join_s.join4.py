"""Device seconds per join in the local join programs,
``dist_ops._join_phase1_fn`` (the sort-join plan and its counts) and
``dist_ops._join_phase2_fn`` (the output gathers), read by their stable
program names (``harness/programs.py``), mean over chips; nothing is
read where neither ran."""

from benchmarks.harness import programs

LOCAL_JOIN = r"^jit_dist_ops__join_phase[12]_fn(?:[(_]|$)"


def read(r):
    if r.trace is None or not r.window.units \
            or not programs.n_programs(r.trace, LOCAL_JOIN):
        return None
    return programs.program_s(r.trace, LOCAL_JOIN) / r.window.units
