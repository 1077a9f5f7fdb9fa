"""Device-idle milliseconds per query run while no program span is open:
the caller's own code between searches (fetching results, the loop). From
the exact split of the traced window's idle time by program span
(``bench.span_reduce``)."""

from bench import span_reduce


def read(cell):
    return span_reduce.idle_ms_per_run(
        cell, lambda name: name == span_reduce.NO_SPAN)
