"""Per-slab query-block selection in the streaming engine.

In each slab the engine scans only the query blocks whose open windows meet
the slab (``slab_qblocks``), padded to a ``qblock_bucket`` size, and folds
their winners into the running best. Under test: streamed
``from_store(resident=False)`` equals the resident ``oms_search`` and the
plain exhaustive search (``core.baselines.exhaustive_params``) on seeded
random spectra at slab sizes that split windows and charge boundaries, with
bucket padding and shifted ranges exercised, on the full-width and the
prefix path; and the engine's compared-pairs counter equals an independent
count of the selected (q-block, slab) pairs.
"""
import numpy as np
import pytest

from repro.core import OMSConfig, OMSPipeline
from repro.core.baselines import exhaustive_params
from repro.core.search import oms_search, sort_pad_plan
from repro.data.spectra import LibraryConfig, make_dataset
from repro.serve import StreamingEngine, qblock_bucket, slab_qblocks
from repro.serve import engine as engine_mod

# q_block 2 over 600 queries: about 300 q-blocks, so a slab selects dozens
# of them and the eight-per-octave buckets pad.
CFG = OMSConfig(dim=256, max_r=32, q_block=2, n_levels=8)
DS = dict(n_refs=400, n_queries=600, seed=11)


def _assert_result_equal(a, b, ctx=""):
    for f in a._fields:
        assert (np.asarray(getattr(a, f)) == np.asarray(getattr(b, f))).all(), \
            (ctx, f)


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    ds = make_dataset(LibraryConfig(**DS))
    path = str(tmp_path_factory.mktemp("select") / "store")
    store = OMSPipeline.ingest(CFG, ds.refs, path, chunk_rows=160)
    resident = OMSPipeline.from_store(store, CFG)
    encoded = resident.encode_queries(ds.queries)
    return ds, store, resident, encoded


def _slab_calls(monkeypatch):
    """Record (q0, n_qb) of every slab step."""
    calls = []
    real = engine_mod._search_sorted_padded_slab

    def spy(*a, **k):
        calls.append((int(a[5]), k["n_qb"]))
        return real(*a, **k)

    monkeypatch.setattr(engine_mod, "_search_sorted_padded_slab", spy)
    return calls


# 32: one block per slab, every window spans several slabs; 96 and 160
# split windows and put a charge boundary inside a slab; 1 << 30: one slab.
@pytest.mark.parametrize("slab_rows", [32, 96, 160, 1 << 30])
@pytest.mark.parametrize("top_k", [1, 3])
def test_streamed_equals_resident_and_exhaustive(setup, monkeypatch,
                                                 slab_rows, top_k):
    ds, store, resident, (hvs, qp, qc) = setup
    params = resident.search_params(qp, qc, top_k=top_k)
    want = oms_search(resident.db, hvs, qp, qc, params, dim=CFG.dim)
    plain = oms_search(resident.db, hvs, qp, qc, exhaustive_params(params),
                       dim=CFG.dim)
    _assert_result_equal(want, plain, ctx="resident vs exhaustive")

    stream = OMSPipeline.from_store(store, CFG, resident=False,
                                    slab_rows=slab_rows)
    calls = _slab_calls(monkeypatch)
    got = stream.search_encoded(hvs, qp, qc, top_k=top_k)
    _assert_result_equal(want, got.result, ctx=slab_rows)
    res = resident.search_encoded(hvs, qp, qc, top_k=top_k)
    for w, g in ((res.open_fdr, got.open_fdr), (res.std_fdr, got.std_fdr)):
        assert (np.asarray(w.accept) == np.asarray(g.accept)).all()

    gather, _ = sort_pad_plan(qp, qc, CFG.q_block)
    nqb = int(gather.shape[0]) // CFG.q_block
    assert len(calls) == stream.engine.last_stats.n_scanned
    if stream.engine.plan.n_slabs > 1:
        # selection is on: some slab scans fewer q-blocks than the batch has
        assert min(n for _, n in calls) < nqb


def test_bucket_padding_and_shifted_ranges_stay_exact(setup, monkeypatch):
    """Slabs whose selected count is not a bucket size scan padding
    q-blocks, and a range near the batch's end shifts down to fit its
    bucket; neither changes a result."""
    ds, store, resident, (hvs, qp, qc) = setup
    params = resident.search_params(qp, qc, top_k=2)
    want = oms_search(resident.db, hvs, qp, qc, params, dim=CFG.dim)
    eng = StreamingEngine(store, max_r=CFG.max_r, slab_rows=160)
    gather, _ = sort_pad_plan(qp, qc, CFG.q_block)
    g = np.asarray(gather)
    first, stop = slab_qblocks(eng.layout, np.asarray(qp)[g],
                               np.asarray(qc)[g], q_block=CFG.q_block,
                               open_tol_da=params.open_tol_da, plan=eng.plan)
    nqb = g.shape[0] // CFG.q_block
    calls = _slab_calls(monkeypatch)
    got = eng.search_encoded(hvs, qp, qc, params, dim=CFG.dim)
    _assert_result_equal(want, got)
    touched = np.flatnonzero(stop > first)
    assert len(calls) == len(touched)
    padded = shifted = 0
    for s, (q0, n_qb) in zip(touched, calls):
        assert n_qb == qblock_bucket(int(stop[s] - first[s]), nqb)
        assert q0 <= first[s] and stop[s] <= q0 + n_qb <= nqb
        padded += n_qb > stop[s] - first[s]
        shifted += q0 < first[s]
    assert padded and shifted


@pytest.mark.parametrize("slab_rows", [32, 160])
def test_streamed_prefix_path_equals_resident(setup, slab_rows):
    ds, store, resident, (hvs, qp, qc) = setup
    want = resident.search_encoded(hvs, qp, qc, top_k=2, prefix_words=2)
    stream = OMSPipeline.from_store(store, CFG, resident=False,
                                    slab_rows=slab_rows)
    got = stream.search_encoded(hvs, qp, qc, top_k=2, prefix_words=2)
    _assert_result_equal(want.result, got.result, ctx=slab_rows)
    assert stream.engine.last_stats.scanned_pairs > 0


@pytest.mark.parametrize("slab_rows", [32, 160])
def test_prefix_path_scans_the_selected_qblocks(setup, monkeypatch,
                                                slab_rows):
    """The prefix path's stage A scans, in each slab, the same padded
    q-block range as the full-width slab step."""
    ds, store, resident, (hvs, qp, qc) = setup
    stream = OMSPipeline.from_store(store, CFG, resident=False,
                                    slab_rows=slab_rows)
    full = _slab_calls(monkeypatch)
    stream.search_encoded(hvs, qp, qc, top_k=2)
    prefix = []
    real = engine_mod._prefix_flags_slab

    def spy(*a, **k):
        prefix.append((int(a[6]), k["n_qb"]))
        return real(*a, **k)

    monkeypatch.setattr(engine_mod, "_prefix_flags_slab", spy)
    stream.search_encoded(hvs, qp, qc, top_k=2, prefix_words=2)
    assert prefix == full
    gather, _ = sort_pad_plan(qp, qc, CFG.q_block)
    if stream.engine.plan.n_slabs > 1:
        assert min(n for _, n in prefix) < int(gather.shape[0]) // CFG.q_block


@pytest.mark.parametrize("slab_rows", [32, 96])
def test_compared_pairs_count_the_selected_qblocks(setup, slab_rows):
    """``StreamStats.scanned_pairs`` against a brute-force count: per slab,
    the q-blocks holding a query with an in-window row of the slab (every
    (query, row) pair tested in float64), their range, its bucket, times
    q_block x the rows each q-block scans."""
    ds, store, resident, (hvs, qp, qc) = setup
    eng = StreamingEngine(store, max_r=CFG.max_r, slab_rows=slab_rows)
    params = resident.search_params(qp, qc)
    eng.search_encoded(hvs, qp, qc, params, dim=CFG.dim)

    gather, _ = sort_pad_plan(qp, qc, CFG.q_block)
    g = np.asarray(gather)
    q_pmz = np.asarray(qp, np.float64)[g]
    q_charge = np.asarray(qc)[g]
    r_pmz = np.asarray(resident.db.pmz, np.float64)
    r_charge = np.asarray(resident.db.charge)
    # the engine's 1e-3 Da of slack against f32 rounding
    tol = params.open_tol_da + 1e-3
    in_window = ((q_charge[:, None] == r_charge[None, :])
                 & (np.abs(q_pmz[:, None] - r_pmz[None, :]) <= tol))
    plan = eng.plan
    nqb = g.shape[0] // CFG.q_block
    k_rows = min(params.k_blocks, plan.slab_blocks) * CFG.max_r
    want = 0
    for s in range(plan.n_slabs):
        cols = in_window[:, s * plan.slab_rows:(s + 1) * plan.slab_rows]
        qbs = np.flatnonzero(cols.any(axis=1)) // CFG.q_block
        if qbs.size:
            n = qblock_bucket(int(qbs.max() - qbs.min() + 1), nqb)
            want += n * CFG.q_block * k_rows
    assert want > 0
    assert eng.last_stats.scanned_pairs == want
    assert eng.total_stats.scanned_pairs == want


def test_qblock_bucket_bounds_padding_and_shapes():
    sizes = set()
    for n in range(1, 3000):
        b = qblock_bucket(n, 10_000)
        assert n <= b <= max(8, n + n // 8)
        sizes.add(b)
        assert qblock_bucket(n, 40) == min(b, 40)
    assert len(sizes) <= 8 + 8 * 9     # 1..8, then eight per octave


def test_merge_by_row_breaks_ties_by_lower_row():
    """Round-robin devices hold interleaved slabs, so their running bests
    merge by (sim desc, row asc), not by position: on equal sims the lower
    row wins whichever device holds it."""
    k = 3
    # device 0 saw rows 0-9 and 20-29, device 1 rows 10-19; -1 = empty
    d0 = (np.array([[7, 7, 5], [9, -1, -1]], np.int32),
          np.array([[3, 21, 4], [25, -1, -1]], np.int32))
    d1 = (np.array([[7, 6, 5], [9, 9, 2]], np.int32),
          np.array([[12, 11, 10], [14, 15, 16]], np.int32))
    runs = [d0 + d0, d1 + d1]            # std and open lists alike
    out = [np.asarray(x) for x in engine_mod._merge_by_row(runs, k)]
    for sims, rows in (out[:2], out[2:]):
        assert sims.tolist() == [[7, 7, 7], [9, 9, 9]]
        assert rows.tolist() == [[3, 12, 21], [14, 15, 25]]
