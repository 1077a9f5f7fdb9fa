"""Device-idle milliseconds per query run while the innermost open program
span is a planning step: ``pipeline.precursors_to_host`` (the host waits for
the query precursors), ``pipeline.plan`` (``plan_search``) or
``search.sort_pad`` (``sort_pad_plan``). From the exact split of the traced
window's idle time by program span (``bench.span_reduce``)."""

from bench import span_reduce

SPANS = ("pipeline.precursors_to_host", "pipeline.plan", "search.sort_pad")


def read(cell):
    return span_reduce.idle_ms_per_run(cell, lambda name: name in SPANS)
