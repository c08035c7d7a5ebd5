"""Device seconds per stream in the row-compaction programs: the
survivor gather of ``dist_ops._compact_survivors``, the select masks
(``dist_ops.dist_select``, ``dist_ops._predicate_mask``) and the
semi/anti-join masks (``dist_ops._semi_mask_fn``,
``dist_ops._semi_mask_dense_fn``), read by their stable program names
(``harness/programs.py``); nothing is read where none of them ran."""

from benchmarks.harness import programs

COMPACT = (r"^jit_dist_ops__(?:compact_survivors|predicate_mask"
           r"|semi_mask_fn|semi_mask_dense_fn)(?:[(_]|$)"
           r"|^jit_dist_ops_dist_select(?:[(_]|$)")


def read(r):
    if r.trace is None or not r.window.units \
            or not programs.n_programs(r.trace, COMPACT):
        return None
    return programs.program_s(r.trace, COMPACT) / r.window.units
