"""Host milliseconds per query run that the streaming engine's prefetch
thread spends reading slabs from the store's shards (``slab_arrays``): the
summed ``serve.slab.gather`` spans of the traced window over the runs."""

from bench.metrics import slab_wait_ms

SPAN = "serve.slab.gather"


def read(cell):
    return slab_wait_ms.span_ms_per_run(cell, SPAN)
