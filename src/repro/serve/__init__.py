"""Streaming serve subsystem: bounded-memory slab scans over the
near-storage LibraryStore plus an async micro-batching query frontend with
SLO-aware admission (deadlines, per-tenant fair dequeue), an HV-keyed
result cache, and hot-reload of appended shards.
Entry points: ``OMSPipeline.from_store(..., resident=False)`` and the
``repro.launch.oms serve`` JSON-lines loop."""
from repro.serve.engine import StreamingEngine, StreamStats, TotalStats
from repro.serve.result_cache import ResultCache
from repro.serve.scheduler import (DeadlineExceeded, MicroBatcher, QuerySpec,
                                   coalesce_queries)
from repro.serve.slabs import (SlabPlan, StoreLayout, plan_slabs,
                               qblock_bucket, slab_arrays, slab_qblocks)
