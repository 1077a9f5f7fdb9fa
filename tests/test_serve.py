"""Streaming serve subsystem: slab layout, cross-slab top-k merging, the
micro-batching scheduler, and the ``resident=False`` pipeline wiring.

Tentpole guarantee under test: the streaming engine returns bit-identical
:class:`SearchResult`s to the resident ``oms_search`` at EVERY slab size —
1-row slabs, awkward-prime slabs, whole-store slab — on a target+decoy
store, including the adversarial merge cases (exact score ties straddling a
slab boundary, ``top_k`` larger than any single slab's matching rows, a
query whose precursor window touches zero slabs).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import OMSConfig, OMSPipeline
from repro.core.blocking import LibraryRun, build_reference_db_from_runs
from repro.core.search import SearchParams, oms_search
from repro.data.spectra import LibraryConfig, make_dataset
from repro.serve import (DeadlineExceeded, MicroBatcher, QuerySpec,
                         StoreLayout, StreamingEngine, coalesce_queries,
                         plan_slabs, slab_qblocks)

# n_queries=40 with charges {2,3} puts a charge boundary mid-q-block — the
# regression dataset for the plan_search charge-run-local grouping fix.
CFG = OMSConfig(dim=512, max_r=32, q_block=8, n_levels=16)
DS = dict(n_refs=500, n_queries=40, seed=5)


def _assert_result_equal(a, b, ctx=""):
    for f in a._fields:
        assert (np.asarray(getattr(a, f)) == np.asarray(getattr(b, f))).all(), \
            (ctx, f)


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    ds = make_dataset(LibraryConfig(**DS))
    pipe = OMSPipeline(CFG, ds.refs, chunk_rows=192)
    path = str(tmp_path_factory.mktemp("serve") / "store")
    store = OMSPipeline.ingest(CFG, ds.refs, path, chunk_rows=192)
    encoded = pipe.encode_queries(ds.queries)
    return ds, pipe, store, encoded


# ---------------------------------------------------------------------------
# Layout: the sidecar-only merged view must equal the resident DB
# ---------------------------------------------------------------------------


def test_layout_matches_resident_db(setup):
    ds, pipe, store, _ = setup
    layout = StoreLayout.from_store(store, max_r=CFG.max_r)
    for f in ("pmz", "charge", "is_decoy", "orig_idx",
              "block_min", "block_max", "block_charge"):
        assert (np.asarray(getattr(pipe.db, f))
                == np.asarray(getattr(layout, f))).all(), f
    # the HV gather plan reproduces the resident payload exactly
    assert (layout.read_hv_rows(0, layout.n_rows)
            == np.asarray(pipe.db.hvs)).all()
    # and a mid-stream window too (mmap slab read path)
    assert (layout.read_hv_rows(65, 131)
            == np.asarray(pipe.db.hvs)[65:131]).all()


# ---------------------------------------------------------------------------
# Bit-identity across slab sizes (acceptance: 1 row / awkward prime / whole)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("max_r,slab_rows", [
    (1, 1),            # 1-row blocks, 1-row slabs — maximally degenerate
    (1, 97),           # awkward prime slab size
    (32, 32),          # one block per slab
    (32, 96),          # several blocks per slab
    (32, 1 << 30),     # whole store in one slab
])
def test_streaming_bitidentical(setup, max_r, slab_rows):
    ds, pipe, store, _ = setup
    resident = OMSPipeline.from_store(store, CFG, max_r=max_r)
    hvs, qp, qc = resident.encode_queries(ds.queries)
    params = resident.search_params(qp, qc, top_k=3)
    want = oms_search(resident.db, hvs, qp, qc, params, dim=CFG.dim)

    eng = StreamingEngine(store, max_r=max_r, slab_rows=slab_rows)
    got = eng.search_encoded(hvs, qp, qc, params, dim=CFG.dim)
    _assert_result_equal(want, got, ctx=(max_r, slab_rows))
    if slab_rows >= eng.layout.n_rows:   # single-slab degenerate case
        assert eng.plan.n_slabs == 1


def test_streaming_exhaustive_matches(setup):
    ds, pipe, store, (hvs, qp, qc) = setup
    params = pipe.search_params(qp, qc, exhaustive=True, top_k=2)
    want = oms_search(pipe.db, hvs, qp, qc, params, dim=CFG.dim)
    eng = StreamingEngine(store, max_r=CFG.max_r, slab_rows=64)
    got = eng.search_encoded(hvs, qp, qc, params, dim=CFG.dim)
    _assert_result_equal(want, got)
    assert eng.last_stats.n_scanned == eng.plan.n_slabs  # baseline scans all


def test_pipeline_resident_false(setup):
    """from_store(resident=False) serves through the engine transparently:
    same SearchResult AND same FDR output as the resident pipeline."""
    ds, pipe, store, _ = setup
    stream = OMSPipeline.from_store(store, CFG, resident=False, slab_rows=96)
    assert stream.db is None and stream.engine is not None
    want = pipe.search(ds.queries, top_k=2)
    got = stream.search(ds.queries, top_k=2)
    _assert_result_equal(want.result, got.result)
    for w, g in ((want.open_fdr, got.open_fdr), (want.std_fdr, got.std_fdr)):
        assert int(w.n_accepted) == int(g.n_accepted)
        assert (np.asarray(w.accept) == np.asarray(g.accept)).all()
        assert np.allclose(np.asarray(w.q_values), np.asarray(g.q_values))


def test_streaming_never_materialises_library_on_device(setup, monkeypatch):
    """The engine must never device_put an array with as many rows as the
    library — only slab-, query- or winner-sized ones."""
    import jax

    ds, pipe, store, (hvs, qp, qc) = setup
    eng = StreamingEngine(store, max_r=CFG.max_r, slab_rows=64)
    n_rows = eng.layout.n_rows
    assert eng.plan.slab_rows < n_rows
    real = jax.device_put
    seen = []

    def spy(x, *a, **k):
        for leaf in jax.tree_util.tree_leaves(x):
            shape = getattr(leaf, "shape", ())
            if shape:
                seen.append(int(shape[0]))
        return real(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", spy)
    params = pipe.search_params(qp, qc)
    eng.search_encoded(hvs, qp, qc, params, dim=CFG.dim)
    assert seen and max(seen) <= max(eng.plan.slab_rows, hvs.shape[0] + 16)


# ---------------------------------------------------------------------------
# Cross-slab merge adversarial cases (hand-built runs: every HV identical,
# so every in-window candidate ties at sim == dim and the ranking is decided
# purely by the (sim desc, row asc) tie-break)
# ---------------------------------------------------------------------------


def _tie_fixture(n=40, w=16):
    rng = np.random.default_rng(0)
    hv = rng.integers(0, 2**32, size=(1, w), dtype=np.uint32)
    hvs = np.repeat(hv, n, axis=0)
    pmz = np.linspace(1000.0, 1010.0, n).astype(np.float32)  # one open window
    charge = np.full((n,), 2, np.int32)
    run = LibraryRun(hvs=hvs, pmz=pmz, charge=charge,
                     is_decoy=np.zeros((n,), bool),
                     orig_idx=np.arange(n, dtype=np.int32))
    q_hvs = jnp.asarray(hv)
    q_pmz = jnp.asarray([1005.0], jnp.float32)
    q_charge = jnp.asarray([2], jnp.int32)
    return run, q_hvs, q_pmz, q_charge


def test_exact_ties_straddling_slab_boundary():
    """top_k=6 with 4-row slabs: winners are rows 0..5 — they straddle the
    slab 0 / slab 1 boundary and must come out in global row order."""
    run, q_hvs, q_pmz, q_charge = _tie_fixture()
    max_r = 4
    db = build_reference_db_from_runs([run], max_r=max_r)
    params = SearchParams(q_block=4, k_blocks=db.n_blocks, top_k=6)
    want = oms_search(db, q_hvs, q_pmz, q_charge, params, dim=512)

    layout = StoreLayout.from_runs([run], max_r=max_r)
    eng = StreamingEngine(layout, max_r=max_r, slab_rows=4)
    got = eng.search_encoded(q_hvs, q_pmz, q_charge, params, dim=512)
    _assert_result_equal(want, got)
    # every candidate ties, so the 6 winners are exactly rows 0..5
    assert np.asarray(got.open_row)[0].tolist() == [0, 1, 2, 3, 4, 5]
    assert (np.asarray(got.open_sim)[0] == 512).all()


def test_k_larger_than_any_single_slabs_matches():
    """No single 4-row slab can fill top_k=6 — the merge must accumulate
    valid winners across slabs instead of padding with -1."""
    run, q_hvs, q_pmz, q_charge = _tie_fixture()
    layout = StoreLayout.from_runs([run], max_r=4)
    eng = StreamingEngine(layout, max_r=4, slab_rows=4)
    assert eng.plan.slab_rows < 6    # the premise: a slab can't fill k
    # ppm window widened so the std list must also fill across slabs
    params = SearchParams(q_block=4, k_blocks=layout.n_blocks, top_k=6,
                          ppm_tol=1e5)
    got = eng.search_encoded(q_hvs, q_pmz, q_charge, params, dim=512)
    assert (np.asarray(got.open_idx)[0] >= 0).all()
    assert (np.asarray(got.std_idx)[0] >= 0).all()


def test_query_touching_zero_slabs(setup):
    """A query whose (charge, pmz) window intersects no slab must scan
    nothing and report all -1 — bit-identical to the resident scan."""
    ds, pipe, store, _ = setup
    q_hvs = jnp.asarray(np.zeros((1, CFG.n_words), np.uint32))
    q_pmz = jnp.asarray([900.0], jnp.float32)
    q_charge = jnp.asarray([9], jnp.int32)       # charge absent from library
    params = pipe.search_params(q_pmz, q_charge, top_k=2)
    want = oms_search(pipe.db, q_hvs, q_pmz, q_charge, params, dim=CFG.dim)
    eng = StreamingEngine(store, max_r=CFG.max_r, slab_rows=64)
    got = eng.search_encoded(q_hvs, q_pmz, q_charge, params, dim=CFG.dim)
    _assert_result_equal(want, got)
    assert (np.asarray(got.open_idx) == -1).all()
    assert eng.last_stats.n_scanned == 0          # zero slabs streamed


def test_slab_pruning_is_window_exact():
    """slab_qblocks selects exactly the slabs holding a row of a query's
    window; out-of-range and wrong-charge queries hit nothing."""
    run, *_ = _tie_fixture()
    layout = StoreLayout.from_runs([run], max_r=4)
    plan = plan_slabs(layout.n_blocks, max_r=4, slab_rows=8)

    def hit(qp, qc):
        first, stop = slab_qblocks(layout, np.asarray(qp), np.asarray(qc),
                                   q_block=1, open_tol_da=0.2, plan=plan)
        return stop > first

    h = hit([1000.0], [2])
    assert h[0] and not h[1:].any()               # only the first slab
    for qp, qc in (([5000.0], [2]), ([1005.0], [3])):
        assert not hit(qp, qc).any()


# ---------------------------------------------------------------------------
# Micro-batching scheduler
# ---------------------------------------------------------------------------


def _spec(pmz, n_peaks=3):
    return QuerySpec(mz=np.full((n_peaks,), 500.0, np.float32),
                     intensity=np.ones((n_peaks,), np.float32),
                     pmz=float(pmz), charge=2)


def test_microbatcher_coalesces_and_routes_results():
    batches = []

    def run_batch(spectra):
        batches.append(spectra.pmz.shape[0])
        return [float(p) * 2 for p in np.asarray(spectra.pmz)]

    with MicroBatcher(run_batch, max_batch=4, max_wait_s=0.05) as mb:
        futs = [mb.submit(_spec(100.0 + i)) for i in range(10)]
        results = [f.result(timeout=30) for f in futs]
    assert results == [pytest.approx(2 * (100.0 + i)) for i in range(10)]
    assert sum(batches) == 10 and max(batches) <= 4   # coalesced, capped
    assert mb.n_queries == 10 and mb.n_batches == len(batches)


def test_microbatcher_propagates_errors_and_recovers():
    calls = []

    def run_batch(spectra):
        calls.append(spectra.pmz.shape[0])
        if len(calls) == 1:
            raise RuntimeError("scan exploded")
        return list(range(spectra.pmz.shape[0]))

    with MicroBatcher(run_batch, max_batch=8, max_wait_s=0.01) as mb:
        bad = mb.submit(_spec(1.0))
        with pytest.raises(RuntimeError, match="scan exploded"):
            bad.result(timeout=30)
        good = mb.submit(_spec(2.0))
        assert good.result(timeout=30) == 0        # scheduler still alive
    with pytest.raises(RuntimeError):
        mb.submit(_spec(3.0))                      # closed


def test_microbatcher_result_count_mismatch():
    with MicroBatcher(lambda spectra: [1, 2, 3], max_batch=1,
                      max_wait_s=0.0) as mb:
        fut = mb.submit(_spec(1.0))
        with pytest.raises(RuntimeError, match="returned 3 results"):
            fut.result(timeout=30)


def test_microbatcher_survives_cancelled_future():
    """A caller cancelling its future must not kill the worker thread
    (set_result on a cancelled future raises InvalidStateError)."""
    import threading

    release = threading.Event()

    def run_batch(spectra):
        release.wait(10)
        return list(np.asarray(spectra.pmz))

    with MicroBatcher(run_batch, max_batch=1, max_wait_s=0.0) as mb:
        doomed = mb.submit(_spec(1.0))
        assert doomed.cancel()          # cancelled before the batch finishes
        release.set()
        ok = mb.submit(_spec(7.0))
        assert ok.result(timeout=30) == pytest.approx(7.0)  # worker alive


def test_microbatcher_submit_after_close_raises():
    with MicroBatcher(lambda s: list(np.asarray(s.pmz)), max_batch=2,
                      max_wait_s=0.0) as mb:
        assert mb.submit(_spec(1.0)).result(timeout=30) == pytest.approx(1.0)
    with pytest.raises(RuntimeError, match="closed"):
        mb.submit(_spec(2.0))


def test_microbatcher_coalescing_independence(setup):
    """Stress the serve invariant that coalescing NEVER changes an answer:
    the same query set submitted under max_batch ∈ {1, 3, whole-set} and
    randomized submit orderings must produce byte-identical responses per
    query id (the real search path, serialized exactly like the launcher's
    JSON-lines loop)."""
    import json

    ds, pipe, store, _ = setup
    n = 10
    mz = np.asarray(ds.queries.mz)[:n]
    inten = np.asarray(ds.queries.intensity)[:n]
    pmz = np.asarray(ds.queries.pmz)[:n]
    charge = np.asarray(ds.queries.charge)[:n]

    def run_batch(spectra):
        r = pipe.search(spectra).result
        std_i = np.asarray(r.std_idx); std_s = np.asarray(r.std_sim)
        opn_i = np.asarray(r.open_idx); opn_s = np.asarray(r.open_sim)
        return [json.dumps(
            {"std": {"idx": std_i[i].tolist(), "sim": std_s[i].tolist()},
             "open": {"idx": opn_i[i].tolist(), "sim": opn_s[i].tolist()}},
            sort_keys=True, separators=(",", ":"))
            for i in range(std_i.shape[0])]

    def spec_for(i):
        keep = inten[i] > 0
        return QuerySpec(mz=mz[i][keep], intensity=inten[i][keep],
                         pmz=float(pmz[i]), charge=int(charge[i]))

    rng = np.random.default_rng(11)
    responses = {}            # (max_batch, order_tag) -> {qid: bytes}
    for max_batch in (1, 3, n):
        for tag in range(2):  # two randomized submit orderings each
            order = rng.permutation(n) if tag else np.arange(n)
            with MicroBatcher(run_batch, max_batch=max_batch,
                              max_wait_s=0.02) as mb:
                futs = {int(q): mb.submit(spec_for(int(q))) for q in order}
                responses[(max_batch, tag)] = {
                    q: f.result(timeout=60).encode() for q, f in futs.items()}

    base = responses[(1, 0)]
    assert len(base) == n
    for key, got in responses.items():
        for q in range(n):
            assert got[q] == base[q], (key, q)


def test_microbatcher_cancelled_future_records_latency_once():
    """A future the caller cancelled still records its e2e latency —
    exactly once — and so does everyone else in the batch: the histogram
    count must equal the submit count, cancellations notwithstanding."""
    import threading

    release = threading.Event()

    def run_batch(spectra):
        release.wait(10)
        return list(np.asarray(spectra.pmz))

    with MicroBatcher(run_batch, max_batch=1, max_wait_s=0.0) as mb:
        doomed = mb.submit(_spec(1.0))
        assert doomed.cancel()
        release.set()
        ok = mb.submit(_spec(7.0))
        assert ok.result(timeout=30) == pytest.approx(7.0)
    assert mb.e2e_latency.count == 2          # doomed AND ok, once each
    assert mb.queue_wait.count == 2
    assert mb.queue_depth.value == 0


def test_microbatcher_error_batch_records_latency():
    """Batches that error resolve every future with the exception AND
    still record each request's e2e latency exactly once."""
    def run_batch(spectra):
        raise RuntimeError("scan exploded")

    with MicroBatcher(run_batch, max_batch=4, max_wait_s=0.01) as mb:
        futs = [mb.submit(_spec(float(i))) for i in range(3)]
        for f in futs:
            with pytest.raises(RuntimeError, match="scan exploded"):
                f.result(timeout=30)
    assert mb.e2e_latency.count == 3


def test_microbatcher_close_flushes_partial_batch_metrics():
    """close() mid-coalesce dispatches the final partial batch, and that
    batch's metrics (batch_size observation, per-request queue waits and
    latencies) land before close() returns."""
    def run_batch(spectra):
        return list(np.asarray(spectra.pmz))

    # max_wait long enough that the worker is still coalescing when
    # close() lands: the _CLOSE sentinel must flush, not drop, the batch.
    with MicroBatcher(run_batch, max_batch=64, max_wait_s=30.0) as mb:
        futs = [mb.submit(_spec(float(i))) for i in range(3)]
        mb.close()
        assert [f.result(timeout=30) for f in futs] == [0.0, 1.0, 2.0]
    assert mb.n_batches == 1 and mb.n_queries == 3
    assert mb.batch_sizes.count == 1
    assert mb.batch_sizes.sum == pytest.approx(3.0)   # the partial batch
    assert mb.e2e_latency.count == 3
    assert mb.queue_wait.count == 3
    assert mb.queue_depth.value == 0
    assert mb.queue_depth.max >= 3                    # high-water mark


def test_microbatcher_shared_metrics_registry():
    from repro.obs import Metrics

    reg = Metrics()
    with MicroBatcher(lambda s: list(np.asarray(s.pmz)), max_batch=2,
                      max_wait_s=0.0, metrics=reg) as mb:
        assert mb.submit(_spec(5.0)).result(timeout=30) == pytest.approx(5.0)
    snap = reg.snapshot()
    assert snap["e2e_latency_s"]["count"] == 1
    assert snap["batch_size"]["count"] == 1
    assert snap["queue_depth"]["value"] == 0.0


# ---------------------------------------------------------------------------
# StreamingEngine cumulative stats
# ---------------------------------------------------------------------------


def test_streaming_engine_total_stats_accumulate_and_reset(setup):
    ds, pipe, store, encoded = setup
    hvs, qp, qc = encoded
    streamed = OMSPipeline.from_store(store, CFG, resident=False,
                                      slab_rows=96)
    eng = streamed.engine
    assert eng.total_stats.n_scans == 0

    streamed.search_encoded(hvs, qp, qc)
    s1 = eng.last_stats
    assert eng.total_stats.n_scans == 1
    assert eng.total_stats.scanned_rows == s1.scanned_rows
    assert eng.total_stats.scanned_bytes == s1.scanned_bytes
    assert eng.total_stats.slabs_scanned == s1.n_scanned

    streamed.search_encoded(hvs, qp, qc)      # last_stats clobbers, totals add
    assert eng.last_stats.scanned_rows == s1.scanned_rows
    assert eng.total_stats.n_scans == 2
    assert eng.total_stats.scanned_rows == 2 * s1.scanned_rows
    assert eng.total_stats.slabs_scanned == 2 * s1.n_scanned

    eng.reset_stats()
    assert eng.last_stats is None
    assert eng.total_stats.n_scans == 0 and eng.total_stats.scanned_rows == 0


def test_coalesce_pads_variable_peak_lists():
    batch = coalesce_queries([_spec(10.0, n_peaks=2), _spec(20.0, n_peaks=5)])
    assert batch.mz.shape == (2, 5)
    assert (np.asarray(batch.intensity)[0, 2:] == 0).all()   # padding
    assert np.asarray(batch.pmz).tolist() == [10.0, 20.0]


# ---------------------------------------------------------------------------
# Prefetch lifecycle: a scan that dies mid-loop must not leak the in-flight
# double-buffer fetch (regression: the future was abandoned un-retrieved)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["full", "prefix"])
def test_scan_error_drains_inflight_prefetch(setup, monkeypatch, mode):
    """When the slab loop raises while slab j+1 is being prefetched,
    ``search_encoded`` must retrieve (or cancel) that future before
    propagating — no shard-reading thread may outlive the call and no
    fetch exception may go unretrieved. Pre-fix the exception propagated
    immediately with the fetch still running, so the observed order was
    raise-then-fetch-completion; post-fix it must be the reverse."""
    import threading

    from repro.serve import engine as engine_mod

    ds, pipe, store, (hvs, qp, qc) = setup
    eng = StreamingEngine(store, max_r=CFG.max_r, slab_rows=64)
    kw = {"top_k": 2} if mode == "full" else {"top_k": 2, "prefix_words": 4}
    params = pipe.search_params(qp, qc, **kw)

    real = engine_mod.slab_arrays
    release = threading.Event()
    started2 = threading.Event()
    state = {"fetches": 0}
    events = []                         # completion order: the regression

    def slow_slab_arrays(layout, s, plan, n_words=None):
        state["fetches"] += 1
        if state["fetches"] == 2:       # the prefetched (in-flight) slab
            started2.set()
            release.wait(10)
            events.append("fetch2_done")
        return real(layout, s, plan, n_words=n_words)

    def boom(layout, plan, s):
        assert started2.wait(10)        # prefetch provably in flight
        raise RuntimeError("mid-scan failure")

    monkeypatch.setattr(engine_mod, "slab_arrays", slow_slab_arrays)
    monkeypatch.setattr(StreamingEngine, "_slab_real_rows",
                        staticmethod(boom))

    def run_search():
        with pytest.raises(RuntimeError, match="mid-scan failure"):
            eng.search_encoded(hvs, qp, qc, params, dim=CFG.dim)
        events.append("raised")

    t = threading.Thread(target=run_search)
    t.start()
    assert started2.wait(10)
    # Pre-fix the error escapes while fetch 2 is still blocked: this join
    # succeeds and "raised" lands first. Post-fix search_encoded is parked
    # in _drain_prefetch waiting on the fetch, so the join times out.
    t.join(timeout=1.0)
    release.set()
    t.join(timeout=10.0)
    assert not t.is_alive()
    assert events == ["fetch2_done", "raised"]


# ---------------------------------------------------------------------------
# SLO-aware scheduling: deadlines, shedding, per-tenant fairness
# ---------------------------------------------------------------------------


def test_deadline_cold_batcher_admits_everything():
    """No latency history => estimate 0.0 => nothing is shed at admission,
    whatever the deadline."""
    with MicroBatcher(lambda s: list(np.asarray(s.pmz)), max_batch=4,
                      max_wait_s=0.0) as mb:
        assert mb.estimate_latency_s() == 0.0
        fut = mb.submit(_spec(3.0), deadline_s=5.0)
        assert fut.result(timeout=30) == pytest.approx(3.0)
    assert mb.shed_admit.value == 0 and mb.shed_expired.value == 0


def test_deadline_admission_shed():
    """With warmed latency history, a backlog, and an unmeetable deadline,
    submit fast-fails with DeadlineExceeded without reaching the engine —
    and the shed request is NOT observed into the e2e histogram (it never
    ran, so it must not drag the estimator toward zero)."""
    import threading

    release = threading.Event()
    ran = []

    def run_batch(spectra):
        release.wait(10)
        ran.append(spectra.pmz.shape[0])
        return list(np.asarray(spectra.pmz))

    with MicroBatcher(run_batch, max_batch=1, max_wait_s=0.0) as mb:
        blocker = mb.submit(_spec(1.0))     # occupies the worker
        queued = mb.submit(_spec(2.0))      # queue_depth now 1
        mb.e2e_latency.observe(0.5)         # warmed history: p50 bucket 0.5s
        assert mb.estimate_latency_s() >= 0.5
        doomed = mb.submit(_spec(3.0), deadline_s=0.01)
        with pytest.raises(DeadlineExceeded, match="shed at admission"):
            doomed.result(timeout=30)
        ok = mb.submit(_spec(4.0), deadline_s=60.0)   # meetable deadline
        release.set()
        assert blocker.result(timeout=30) == pytest.approx(1.0)
        assert queued.result(timeout=30) == pytest.approx(2.0)
        assert ok.result(timeout=30) == pytest.approx(4.0)
    assert mb.shed_admit.value == 1 and mb.shed_expired.value == 0
    assert ran == [1, 1, 1]             # only admitted requests ran
    assert mb.e2e_latency.count == 4    # manual warm-up + 3 served, not doomed


def test_admission_probe_on_empty_queue():
    """Half-open probe: however pessimistic the latency history, a request
    arriving at an EMPTY queue is always admitted. Shed requests are never
    observed into the histogram, so without this probe one slow batch (a
    cold compile, a GC pause) would lock the estimator into shedding
    forever — the probe runs immediately and refreshes the history."""
    with MicroBatcher(lambda s: list(np.asarray(s.pmz)), max_batch=4,
                      max_wait_s=0.0) as mb:
        mb.e2e_latency.observe(10.0)    # one awful batch in the history
        assert mb.estimate_latency_s() >= 10.0
        fut = mb.submit(_spec(7.0), deadline_s=0.05)   # est >> deadline
        assert fut.result(timeout=30) == pytest.approx(7.0)
    assert mb.shed_admit.value == 0 and mb.shed_expired.value == 0
    # ... and the probe's observation is in the histogram for recovery
    assert mb.e2e_latency.count == 2


def test_deadline_expired_in_queue_shed():
    """An admitted request whose deadline passes while it waits behind a
    slow batch is fast-failed at dispatch instead of burning a scan."""
    import threading
    import time

    release = threading.Event()
    served = []

    def run_batch(spectra):
        release.wait(10)
        served.extend(float(p) for p in np.asarray(spectra.pmz))
        return list(np.asarray(spectra.pmz))

    with MicroBatcher(run_batch, max_batch=1, max_wait_s=0.0) as mb:
        blocker = mb.submit(_spec(1.0))               # occupies the worker
        doomed = mb.submit(_spec(2.0), deadline_s=0.01)
        time.sleep(0.1)                               # deadline blows queued
        release.set()
        assert blocker.result(timeout=30) == pytest.approx(1.0)
        with pytest.raises(DeadlineExceeded, match="expired"):
            doomed.result(timeout=30)
    assert mb.shed_expired.value == 1 and mb.shed_admit.value == 0
    assert served == [1.0]              # the expired request never ran
    assert mb.e2e_latency.count == 1    # shed-at-dispatch not observed
    assert mb.queue_depth.value == 0


def test_tenant_round_robin_fairness():
    """Batches are assembled round-robin across tenants: a 4-deep "bulk"
    backlog submitted FIRST cannot starve a later 2-request "interactive"
    tenant, while per-tenant FIFO order is preserved."""
    import threading

    release = threading.Event()
    order = []

    def run_batch(spectra):
        release.wait(10)
        order.extend(float(p) for p in np.asarray(spectra.pmz))
        return list(np.asarray(spectra.pmz))

    with MicroBatcher(run_batch, max_batch=1, max_wait_s=0.0) as mb:
        futs = [mb.submit(_spec(0.0))]                # occupies the worker
        for i in range(4):
            futs.append(mb.submit(_spec(10.0 + i), tenant="bulk"))
        for i in range(2):
            futs.append(mb.submit(_spec(20.0 + i), tenant="interactive"))
        release.set()
        for f in futs:
            f.result(timeout=30)
    pos = {p: i for i, p in enumerate(order)}
    assert pos[10.0] < pos[11.0] < pos[12.0] < pos[13.0]   # FIFO per tenant
    assert pos[20.0] < pos[21.0]
    # round-robin: both interactive requests land before bulk's tail
    assert pos[21.0] < pos[13.0]
    assert mb.n_queries == 7


# ---------------------------------------------------------------------------
# HV-keyed result cache
# ---------------------------------------------------------------------------


def _payloads(r, n):
    """The launcher's per-query response payloads, serialized exactly like
    its JSON-lines loop (sorted keys, tight separators) — byte-comparable."""
    import json

    std_i = np.asarray(r.std_idx); std_s = np.asarray(r.std_sim)
    opn_i = np.asarray(r.open_idx); opn_s = np.asarray(r.open_sim)
    return [json.dumps(
        {"std": {"idx": std_i[i].tolist(), "sim": std_s[i].tolist()},
         "open": {"idx": opn_i[i].tolist(), "sim": opn_s[i].tolist()}},
        sort_keys=True, separators=(",", ":")).encode()
        for i in range(n)]


def test_result_cache_keys_lru_and_counters():
    from repro.obs import Metrics
    from repro.serve import ResultCache

    reg = Metrics()
    cache = ResultCache(2, metrics=reg)
    hv = np.arange(16, dtype=np.uint32)
    k1 = ResultCache.key(hv, 500.0, 2)
    assert k1 == ResultCache.key(hv.copy(), 500.0, 2)       # deterministic
    k2 = ResultCache.key(hv, 500.0, 3)                      # charge differs
    k3 = ResultCache.key(hv, 500.25, 2)                     # pmz differs
    k4 = ResultCache.key(hv, 500.0, 2, "cascade")           # params differ
    assert len({k1, k2, k3, k4}) == 4

    assert cache.get(k1) is None                            # miss
    cache.put(k1, b"r1")
    cache.put(k2, b"r2")
    assert cache.get(k1) == b"r1"                           # hit, LRU refresh
    cache.put(k3, b"r3")                                    # evicts k2
    assert cache.get(k2) is None                            # miss (evicted)
    assert cache.get(k1) == b"r1" and cache.get(k3) == b"r3"
    assert len(cache) == 2
    cache.clear()                                           # hot-reload path
    assert len(cache) == 0 and cache.get(k1) is None

    snap = reg.snapshot()
    assert snap["result_cache_hits"] == 3
    assert snap["result_cache_misses"] == 3

    with pytest.raises(ValueError, match="capacity"):
        ResultCache(0)


def test_result_cache_serve_byte_identity(setup):
    """The launcher's cache-in-the-loop batch flow: repeated queries hit
    the cache, new ones are searched as a SUBSET of the batch, and every
    response byte-matches a cache-bypass run — the in-process version of
    CI's ``--result-cache`` vs ``--no-result-cache`` comparison."""
    import jax.numpy as jnp

    from repro.obs import Metrics
    from repro.serve import ResultCache

    ds, pipe, store, _ = setup
    mz = np.asarray(ds.queries.mz)
    inten = np.asarray(ds.queries.intensity)
    pmz = np.asarray(ds.queries.pmz)
    charge = np.asarray(ds.queries.charge)

    def spec_for(i):
        keep = inten[i] > 0
        return QuerySpec(mz=mz[i][keep], intensity=inten[i][keep],
                         pmz=float(pmz[i]), charge=int(charge[i]))

    reg = Metrics()
    cache = ResultCache(64, metrics=reg)

    def run_cached(spectra):
        hvs, qp, qc = pipe.encode_queries(spectra)
        hv_np, qp_np, qc_np = (np.asarray(hvs), np.asarray(qp),
                               np.asarray(qc))
        keys = [ResultCache.key(hv_np[i], float(qp_np[i]), int(qc_np[i]),
                                "tok") for i in range(hv_np.shape[0])]
        out = [cache.get(k) for k in keys]
        miss = [i for i, p in enumerate(out) if p is None]
        if miss:
            sel = jnp.asarray(np.asarray(miss, np.int32))
            fresh = _payloads(
                pipe.search_encoded(hvs[sel], qp[sel], qc[sel],
                                    top_k=2).result, len(miss))
            for i, p in zip(miss, fresh):
                out[i] = p
                cache.put(keys[i], p)
        return out

    n = 8
    batch = coalesce_queries([spec_for(i) for i in range(n)])
    h0, p0, c0 = pipe.encode_queries(batch)
    baseline = _payloads(pipe.search_encoded(h0, p0, c0, top_k=2).result, n)

    assert run_cached(batch) == baseline                 # cold: all misses
    assert run_cached(batch) == baseline                 # warm: all hits
    # mixed batch — cached dupes interleaved with unseen queries: the miss
    # subset is searched alone and spliced in without changing a byte
    idx = [5, 8, 1, 9, 4]
    mixed = coalesce_queries([spec_for(i) for i in idx])
    h1, p1, c1 = pipe.encode_queries(mixed)
    want = _payloads(pipe.search_encoded(h1, p1, c1, top_k=2).result,
                     len(idx))
    assert run_cached(mixed) == want

    snap = reg.snapshot()
    assert snap["result_cache_hits"] == n + 3            # warm pass + dupes
    assert snap["result_cache_misses"] == n + 2          # cold pass + unseen


# ---------------------------------------------------------------------------
# Serve-mode cascade: per-query stage-1 gating is coalescing-independent
# ---------------------------------------------------------------------------


def test_cascade_serve_coalescing_independence(setup):
    """``--cascade`` serving gates stage 1 PER QUERY (each query's FDR
    decision sees only its own narrow matches), so batch composition can
    never change an answer. Regression for the corpus-pooled stage-1 FDR,
    which made a query's identification depend on its batch neighbours."""
    ds, pipe, store, _ = setup
    n = 10
    mz = np.asarray(ds.queries.mz)[:n]
    inten = np.asarray(ds.queries.intensity)[:n]
    pmz = np.asarray(ds.queries.pmz)[:n]
    charge = np.asarray(ds.queries.charge)[:n]

    def run_batch(spectra):
        out = pipe.search_cascade(spectra, narrow_tol_da=1.0, top_k=2,
                                  stage1_per_query=True)
        return _payloads(out.result, spectra.pmz.shape[0])

    def spec_for(i):
        keep = inten[i] > 0
        return QuerySpec(mz=mz[i][keep], intensity=inten[i][keep],
                         pmz=float(pmz[i]), charge=int(charge[i]))

    rng = np.random.default_rng(13)
    responses = {}
    for max_batch in (1, 3, n):
        for tag in range(2):
            order = rng.permutation(n) if tag else np.arange(n)
            with MicroBatcher(run_batch, max_batch=max_batch,
                              max_wait_s=0.02) as mb:
                futs = {int(q): mb.submit(spec_for(int(q))) for q in order}
                responses[(max_batch, tag)] = {
                    q: f.result(timeout=60) for q, f in futs.items()}

    base = responses[(1, 0)]
    assert len(base) == n
    for key, got in responses.items():
        for q in range(n):
            assert got[q] == base[q], (key, q)


# ---------------------------------------------------------------------------
# Hot-reload of appended shards
# ---------------------------------------------------------------------------


def test_hot_reload_bitidentical_to_cold_restart(tmp_path):
    """Grow the store with an appended shard, hot-reload the streaming
    pipeline, and require bit-identity with BOTH a cold restart on the
    grown store and the resident search — the acceptance criterion for
    live library growth."""
    from repro.store import LibraryStore

    ds = make_dataset(LibraryConfig(n_refs=300, n_queries=24, seed=7))
    grow = make_dataset(LibraryConfig(n_refs=180, n_queries=1, seed=8))
    path = str(tmp_path / "store")
    store = OMSPipeline.ingest(CFG, ds.refs, path, chunk_rows=128)
    tok0 = LibraryStore.manifest_token(path)

    stream = OMSPipeline.from_store(store, CFG, resident=False, slab_rows=96)
    stream.search(ds.queries, top_k=2)          # serving before the growth

    OMSPipeline.ingest(CFG, grow.refs, path, chunk_rows=128, append=True)
    assert LibraryStore.manifest_token(path) != tok0   # the watch signal
    n0, t0 = stream.engine.layout.n_rows, stream.n_targets
    stream.reload_store(path)
    assert stream.engine.layout.n_rows > n0
    assert stream.n_targets > t0

    got = stream.search(ds.queries, top_k=2)
    cold = OMSPipeline.from_store(path, CFG, resident=False, slab_rows=96)
    want = cold.search(ds.queries, top_k=2)
    _assert_result_equal(want.result, got.result, ctx="hot vs cold restart")
    resident = OMSPipeline.from_store(path, CFG).search(ds.queries, top_k=2)
    _assert_result_equal(resident.result, got.result, ctx="hot vs resident")


def test_hot_reload_requires_streaming_pipeline(setup):
    ds, pipe, store, _ = setup                 # `pipe` is resident
    with pytest.raises(RuntimeError, match="streaming"):
        pipe.reload_store(store)


def test_stats_threadsafe_under_concurrent_search_and_reload(setup):
    """Searches racing reload(): every in-flight query finishes on its
    entry snapshot (bit-identical answers, zero drops) and the cumulative
    stats counters lose no updates."""
    import threading

    ds, pipe, store, (hvs, qp, qc) = setup
    eng = StreamingEngine(store, max_r=CFG.max_r, slab_rows=96)
    params = pipe.search_params(qp, qc, top_k=2)
    want = oms_search(pipe.db, hvs, qp, qc, params, dim=CFG.dim)

    eng.search_encoded(hvs, qp, qc, params, dim=CFG.dim)   # warm-up
    per_rows = eng.last_stats.scanned_rows
    per_slabs = eng.last_stats.n_scanned
    assert per_rows > 0
    eng.reset_stats()

    K, M = 4, 3
    errs = []
    stop = threading.Event()

    def hammer():
        try:
            for _ in range(M):
                got = eng.search_encoded(hvs, qp, qc, params, dim=CFG.dim)
                _assert_result_equal(want, got, ctx="under reload")
        except BaseException as e:     # pragma: no cover - failure path
            errs.append(e)

    def reloader():
        while not stop.is_set():
            eng.reload(store)

    threads = [threading.Thread(target=hammer) for _ in range(K)]
    rl = threading.Thread(target=reloader)
    rl.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    rl.join()

    assert not errs
    assert eng.total_stats.n_scans == K * M
    assert eng.total_stats.scanned_rows == K * M * per_rows
    assert eng.total_stats.slabs_scanned == K * M * per_slabs
    assert eng.last_stats.scanned_rows == per_rows


# ---------------------------------------------------------------------------
# Launcher exit status: a failed micro-batch is not a clean run
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", ["ok", "malformed", "batch_fails"])
def test_serve_exit_status_flags_failed_batches(setup, monkeypatch, capsys,
                                               case):
    """``oms.py serve`` answers every request line, with an error object
    where it must. It exits 1 only when a micro-batch failed for a reason
    other than a shed deadline or a malformed line — here a search that
    raises — and a malformed line (mz/intensity lengths differ) is refused
    on its own instead of poisoning its micro-batch."""
    import io
    import json

    from repro.launch import oms

    ds, _, store, _ = setup
    served = type(ds.queries)(*(np.asarray(a)[:4] for a in ds.queries))
    lines = list(oms.request_lines(served))
    if case == "malformed":
        lines.insert(2, json.dumps({"id": "bad", "pmz": 500.0, "charge": 2,
                                    "mz": [300.0, 400.0],
                                    "intensity": [1.0]}) + "\n")
    if case == "batch_fails":
        def boom(*a, **k):
            raise RuntimeError("scan failed")
        monkeypatch.setattr(OMSPipeline, "search_encoded", boom)
    monkeypatch.setattr("sys.stdin", io.StringIO("".join(lines)))
    argv = ["--store", store.path, "--max-r", "32", "--q-block", "8",
            "--no-result-cache"]
    if case == "batch_fails":
        with pytest.raises(SystemExit) as exit_info:
            oms.cmd_serve(argv)
        assert exit_info.value.code == 1
    else:
        oms.cmd_serve(argv)
    out = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert len(out) == len(lines)
    errors = [r["id"] for r in out if "error" in r]
    want = {"ok": [], "malformed": ["bad"], "batch_fails": [0, 1, 2, 3]}
    assert errors == want[case]


def test_launcher_import_initialises_no_backend():
    """``oms.py queries`` pins JAX to the CPU inside ``main`` (a ``queries |
    serve`` pipe must leave the accelerator to ``serve``). That pin only
    takes effect if importing the launcher initialised no backend, so a
    platform set after the import must still be the one JAX tries."""
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = ("import jax, repro.launch.oms\n"
            "jax.config.update('jax_platforms', 'no_such_platform')\n"
            "try:\n"
            "    jax.devices()\n"
            "except RuntimeError as e:\n"
            "    print('PIN_HONOURED' if 'no_such_platform' in str(e) "
            "else e)\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env=dict(os.environ, PYTHONPATH=src))
    assert r.returncode == 0, r.stderr
    assert "PIN_HONOURED" in r.stdout, r.stdout + r.stderr
