"""Device milliseconds per query run of the blocked scan program, from the
trace."""

from bench import trace_reduce

PROGRAMS = (r"_search_sorted_padded", r"_prefix_flags", r"_rescore_rows")


def read(cell):
    r, runs = cell.reduction, cell.layer.get("runs")
    if r is None or not runs:
        return None
    s = trace_reduce.program_seconds(r, PROGRAMS)
    return None if s is None else s * 1e3 / runs
