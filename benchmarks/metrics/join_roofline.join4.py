"""The join's share of its HBM roofline, in percent: the least time
the chips could take, over the device-busy seconds per join.

Least time is the logical bytes over the chips' HBM bandwidth: both
inputs read once and the output written once, from row counts and
column widths (``harness/roofline.py``), over ``chips`` x the peak in
``benchmarks/peaks.json``.  It reads the same work whatever implements
the join."""

from benchmarks.harness.roofline import join_bytes


def read(r):
    if r.trace is None or not r.trace.devices or not r.window.units:
        return None
    busy = r.trace.mean_busy_s() / r.window.units
    if busy <= 0 or "rows_out" not in r.facts:
        return None
    least = join_bytes(r.facts) / (r.chips * r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / busy
