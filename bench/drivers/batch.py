"""Batch search: one whole query run, searched again and again.

Set-up generates the library and the run's queries from the seed, ingests
the library into a store (``OMSPipeline.ingest``), cold-starts a resident
pipeline from it (``OMSPipeline.from_store``) and searches the run once,
which compiles every program the window uses. The window then repeats the
search of the whole run, as ``oms.py search`` does it: encode the queries,
plan and scan, FDR, results to the host. ``batch_qps`` is the queries of
every whole search completed in the window over the window's time.

Checked after the window:

* ``sample_mismatch``: of a seeded sample of queries, the (library index,
  similarity) pairs of both windows' top-k that differ from the plain
  reference (limit 0);
* ``fdr_mismatch``: matches whose acceptance differs from the reference's
  target-decoy FDR over the program's own matches of all queries (limit 0);
* ``repeat_mismatch``: searches in the window whose results differ from the
  window's first (limit 0).
"""
from __future__ import annotations

import shutil
import time

import numpy as np

from bench import faults, harness, reference, roofline

FIELDS = ("std_idx", "std_sim", "open_idx", "open_sim")


def _fetch(out) -> dict:
    r = out.result
    return {"std_idx": np.asarray(r.std_idx), "std_sim": np.asarray(r.std_sim),
            "open_idx": np.asarray(r.open_idx),
            "open_sim": np.asarray(r.open_sim),
            "std_accept": np.asarray(out.std_fdr.accept),
            "open_accept": np.asarray(out.open_fdr.accept)}


def run(cell: harness.Cell) -> dict:
    from jax.profiler import TraceAnnotation

    cfg = cell.cfg
    n_q = cfg["library"]["queries_per_run"]
    ds = harness.make_data(cell, n_q)
    queries = ds.queries
    path = harness.ingest(cell, ds.refs)
    del ds
    try:
        pipe = harness.cold_start(cell, path)
    finally:
        shutil.rmtree(path, ignore_errors=True)
    search = faults.wrap_search(pipe.search_encoded, cell.faults)

    def one_run():
        with TraceAnnotation("bench.encode"):
            hvs, qp, qc = pipe.encode_queries(queries)
        with TraceAnnotation("bench.search"):
            out = search(hvs, qp, qc)
        with TraceAnnotation("bench.fetch"):
            return _fetch(out)

    with cell.phase("warmup"):
        one_run()

    runs = differing = 0
    first = None
    with harness.window(cell) as w:
        while True:
            got = one_run()
            runs += 1
            if first is None:
                first = got
            elif any(not np.array_equal(got[k], first[k]) for k in first):
                differing += 1
            if time.perf_counter() - w.t0 >= cell.seconds:
                break
        w.t1 = time.perf_counter()
    cell.layer.update(runs=runs, compiles_in_window=w.compiles)
    harness.log(f"window: {runs} searches of {n_q} queries in "
                f"{w.elapsed:.3f} s, {w.compiles} compiles in the window")
    peak = harness.memory_peak()

    qp_np = np.asarray(queries.pmz)
    qc_np = np.asarray(queries.charge)
    if cell.trace:
        from repro.core.search import scanned_rows

        params = pipe.search_params(qp_np, qc_np)
        cell.layer["scanned_pairs"] = scanned_rows(pipe.db, n_q, params)
        cell.layer["k_blocks"] = params.k_blocks
    del pipe, search, one_run

    with cell.phase("reference"):
        lib = _check(cell, queries, first)
    if cell.trace:
        cell.layer["work"] = roofline.window_work(
            lib.pmz, lib.charge, qp_np, qc_np, cfg["search"]["open_tol_da"])
    cell.check("repeat_mismatch", differing, 0)
    return {"attempted": runs * n_q, "failed": 0, "peak": peak,
            "e2e": {"batch_qps": runs * n_q / w.elapsed,
                    "setup_s": cell.phases["setup_total"]}}


def _check(cell: harness.Cell, queries, got: dict) -> reference.Library:
    """Sampled answers against the reference, and FDR over all queries."""
    s = cell.cfg["search"]
    n_q = queries.pmz.shape[0]
    n_s = min(cell.cfg["check"]["sample_queries"], n_q)
    sample = np.sort(np.random.default_rng(cell.sample_seed).choice(
        n_q, size=n_s, replace=False))
    want, lib = harness.reference_answers(cell, queries, sample)
    bad = sum(int((got[f][sample] != getattr(want, f)).sum()) for f in FIELDS)
    cell.check("sample_mismatch", bad, 0)
    fdr_bad = 0
    for w in ("std", "open"):
        acc = reference.fdr_accept(got[f"{w}_idx"], got[f"{w}_sim"],
                                   cell.cfg["library"]["n_targets"],
                                   s["fdr_threshold"])
        fdr_bad += int((acc != got[f"{w}_accept"]).sum())
    cell.check("fdr_mismatch", fdr_bad, 0)
    cell.info["sample_queries"] = n_s
    cell.info["accepted_open"] = int(got["open_accept"].sum())
    return lib
