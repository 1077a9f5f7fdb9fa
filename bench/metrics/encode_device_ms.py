"""Device milliseconds per query run of the query-encode programs
(preprocessing and the batched encoder), from the trace."""

from bench import trace_reduce

PROGRAMS = (r"_preprocess", r"encode_spectra", r"preprocess_encode")


def read(cell):
    r, runs = cell.reduction, cell.layer.get("runs")
    if r is None or not runs:
        return None
    s = trace_reduce.program_seconds(r, PROGRAMS)
    return None if s is None else s * 1e3 / runs
