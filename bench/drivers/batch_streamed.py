"""Batch search of a library streamed from its store.

As ``batch``: set-up generates the library and the run's queries from the
seed, ingests the library into a store (``OMSPipeline.ingest``) and
searches the run once, which compiles every program the window uses; the
window repeats the search of the whole run (encode the queries, plan, scan,
FDR, results to the host); ``batch_qps`` is the queries of every whole
search completed in the window over the window's time. The cold start is
``OMSPipeline.from_store(path, resident=False, slab_rows=...)`` with the
configuration's ``store`` section: the library never lands on the device,
and every search streams it from the store's memory-mapped shards slab by
slab through ``StreamingEngine``. So the store stays until the reference
check is done.

Checked after the window, as ``batch`` checks (``sample_mismatch``,
``fdr_mismatch``, ``repeat_mismatch``, all with limit 0).

``scanned_pairs`` is the (query, row) pairs the window's slab steps
compared per search, from the engine's cumulative
``TotalStats.scanned_pairs`` read before and after the window; a program
whose engine does not count them stops with an error after warm-up.
``warmup_compiles`` counts the backend compiles of the warm-up search.
"""
from __future__ import annotations

import shutil
import time

import numpy as np

from bench import faults, harness, roofline
from bench.drivers.batch import _check, _fetch


def _streamed_pipeline(cell: harness.Cell, path: str):
    from repro.core import OMSPipeline

    store = cell.cfg["store"]
    over = {**harness.serving_overrides(cell.cfg), **cell.program_overrides}
    with cell.phase("cold_start"):
        pipe = OMSPipeline.from_store(path, resident=store["resident"],
                                      slab_rows=store["slab_rows"], **over)
    if pipe.engine is None:
        raise SystemExit("[bench] the configuration's store is resident; "
                         "this driver runs streamed stores only")
    plan = pipe.engine.plan
    cell.info.update(n_slabs=plan.n_slabs, slab_rows=plan.slab_rows,
                     layout_rows=pipe.engine.layout.n_rows)
    return pipe


def _lowerings() -> dict:
    from repro.core.search import METRICS

    snap = METRICS.snapshot()
    return {k: snap.get(f"lowering_{k}", 0) for k in ("pallas", "xla")}


def run(cell: harness.Cell) -> dict:
    from jax.profiler import TraceAnnotation

    cfg = cell.cfg
    n_q = cfg["library"]["queries_per_run"]
    ds = harness.make_data(cell, n_q)
    queries = ds.queries
    path = harness.ingest(cell, ds.refs)
    del ds
    try:
        pipe = _streamed_pipeline(cell, path)
        search = faults.wrap_search(pipe.search_encoded, cell.faults)

        def one_run():
            with TraceAnnotation("bench.encode"):
                hvs, qp, qc = pipe.encode_queries(queries)
            with TraceAnnotation("bench.search"):
                out = search(hvs, qp, qc)
            with TraceAnnotation("bench.fetch"):
                return _fetch(out)

        t0 = time.perf_counter()
        with cell.phase("warmup"):
            one_run()
        cell.info["warmup_compiles"] = harness.compiles_between(
            t0, time.perf_counter())

        runs = differing = 0
        first = None
        low0 = _lowerings()
        pairs0 = pipe.engine.total_stats.scanned_pairs
        with harness.window(cell) as w:
            while True:
                got = one_run()
                runs += 1
                if first is None:
                    first = got
                elif any(not np.array_equal(got[k], first[k])
                         for k in first):
                    differing += 1
                if time.perf_counter() - w.t0 >= cell.seconds:
                    break
            w.t1 = time.perf_counter()
        low1 = _lowerings()
        pairs = (pipe.engine.total_stats.scanned_pairs - pairs0) // runs
        st = pipe.engine.last_stats
        cell.layer.update(runs=runs, compiles_in_window=w.compiles,
                          scanned_pairs=pairs)
        cell.info.update(slabs_scanned=st.n_scanned, scanned_pairs=pairs,
                         **{f"window_lowering_{k}": low1[k] - low0[k]
                            for k in low1})
        harness.log(f"window: {runs} searches of {n_q} queries in "
                    f"{w.elapsed:.3f} s, {w.compiles} compiles in the "
                    f"window; per search {st.n_scanned} of "
                    f"{st.n_slabs} slabs, {pairs} pairs")
        peak = harness.memory_peak()
        del pipe, search, one_run

        with cell.phase("reference"):
            lib = _check(cell, queries, first)
    finally:
        shutil.rmtree(path, ignore_errors=True)
    if cell.trace:
        cell.layer["work"] = roofline.window_work(
            lib.pmz, lib.charge, np.asarray(queries.pmz),
            np.asarray(queries.charge), cfg["search"]["open_tol_da"])
    cell.check("repeat_mismatch", differing, 0)
    return {"attempted": runs * n_q, "failed": 0, "peak": peak,
            "e2e": {"batch_qps": runs * n_q / w.elapsed,
                    "setup_s": cell.phases["setup_total"]}}
