"""Operations and bytes of the benchmark's kernels, from shapes alone."""


def join_bytes(facts) -> float:
    """Logical HBM bytes of one inner join: each input row read once and
    each output row written once, at the column widths of the tables."""
    return (facts["rows_left"] * facts["bytes_per_row_in"]
            + facts["rows_right"] * facts["bytes_per_row_in"]
            + facts["rows_out"] * facts["bytes_per_row_out"])
