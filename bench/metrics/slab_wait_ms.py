"""Host milliseconds per query run that the streaming engine's slab loop
waits for the next slab from the prefetch thread: the summed
``serve.slab.wait`` spans of the traced window over the runs."""

SPAN = "serve.slab.wait"


def span_ms_per_run(cell, name: str):
    """Summed duration of the window's program spans called ``name``, in ms
    per query run; None where the window holds no such span."""
    spans, runs = cell.layer.get("spans"), cell.layer.get("runs")
    if not spans or not runs:
        return None
    ns = [e.t_end_ns - e.t_start_ns for e in spans if e.name == name]
    return sum(ns) / 1e6 / runs if ns else None


def read(cell):
    return span_ms_per_run(cell, SPAN)
