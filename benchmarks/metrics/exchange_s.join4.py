"""Device seconds per join in the exchange programs, mean over chips:
every program of ``parallel/shuffle.py`` and ``parallel/broadcast.py``
(group by target, pack, all_to_all, unpack and compact) and the
partition-id programs of ``parallel/dist_ops.py`` that route rows to
them (``_hash_pids_fn``, ``_mod_pids_fn``, ``_range_pids_kernel``,
``_range_pids_desc_kernel``, and the splitter sample, ``_sample_fn``
and ``_pool_splitters_fn``), read by their stable program names
(``harness/programs.py``); nothing is read where none of them ran."""

from benchmarks.harness import programs

EXCHANGE = (r"^jit_(?:shuffle|broadcast)_"
            r"|^jit_dist_ops__(?:hash_pids_fn|mod_pids_fn|range_pids_kernel"
            r"|range_pids_desc_kernel|sample_fn|pool_splitters_fn)(?:[(_]|$)")


def read(r):
    if r.trace is None or not r.window.units \
            or not programs.n_programs(r.trace, EXCHANGE):
        return None
    return programs.program_s(r.trace, EXCHANGE) / r.window.units
