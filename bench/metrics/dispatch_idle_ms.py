"""Device-idle milliseconds per query run while the innermost open program
span is any but the planning steps of ``plan_idle_ms``: the self time of
``pipeline.search`` and ``pipeline.scan``, ``search.gather``,
``search.kernel``, ``search.restore``, ``pipeline.fdr`` and
``pipeline.encode``. From the exact split of the traced window's idle time
by program span (``bench.span_reduce``)."""

from bench import span_reduce
from bench.metrics import plan_idle_ms


def read(cell):
    return span_reduce.idle_ms_per_run(
        cell, lambda name: name not in plan_idle_ms.SPANS
        and name != span_reduce.NO_SPAN)
