"""Least HBM bytes of TPC-H's refresh functions, from row counts and
column widths alone (``drivers/refresh.py`` ``facts``, per pair), and a
share of the roofline they set."""


def insert_bytes(facts) -> float:
    """RF1: the delta's rows written once, at their column widths."""
    return facts["insert_bytes"]


def delete_bytes(facts) -> float:
    """RF2: orders' and lineitem's key columns read once, plus the
    deleted rows at full width."""
    return facts["delete_bytes"]


def share(least_bytes: float, seconds: float, chips: int, peaks):
    """Percent of the HBM roofline: the least time, ``least_bytes`` over
    ``chips`` x the HBM bandwidth, over ``seconds``; None where no time
    was measured."""
    if seconds <= 0:
        return None
    return 100.0 * least_bytes / (chips * peaks["hbm_bytes_per_s"]) / seconds
