"""Pallas kernel sweeps: shapes × dtypes vs pure-jnp oracles (exact)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import OMSConfig, OMSPipeline
from repro.core.blocking import build_reference_db
from repro.core.encoding import (PreprocessedSpectra, encode_spectra,
                                 make_codebooks)
from repro.core.packing import hamming_matrix_packed
from repro.core.search import (SearchParams, _find_topk_dual,
                               _search_sorted_padded, oms_search,
                               plan_search)
from repro.kernels.hamming import hamming as hkern
from repro.kernels.hamming import ops as hops
from repro.kernels.hamming import ref as href
from repro.kernels.hamming_mxu import ops as mops
from repro.kernels.hamming_mxu import ref as mref
from repro.kernels.hdencode import ops as eops
from repro.store import LibraryStore
from repro.store.format import CONFIG_KEYS, TARGET


def _rand_packed(key, n, w):
    return jax.random.randint(key, (n, w), 0, 2**31 - 1,
                              dtype=jnp.int32).astype(jnp.uint32)


SHAPES = [
    (8, 16, 4),     # tiny
    (17, 33, 8),    # non-tile-aligned
    (16, 256, 16),  # tile-aligned
    (5, 700, 7),    # odd words
]


@pytest.mark.parametrize("Q,R,W", SHAPES)
def test_hamming_vpu_kernel_sweep(Q, R, W):
    k1, k2 = jax.random.split(jax.random.PRNGKey(Q * R))
    q, r = _rand_packed(k1, Q, W), _rand_packed(k2, R, W)
    assert (np.asarray(hops.hamming_matrix(q, r))
            == np.asarray(href.hamming_matrix(q, r))).all()


@pytest.mark.parametrize("Q,R,W", SHAPES)
def test_hamming_mxu_kernel_sweep(Q, R, W):
    k1, k2 = jax.random.split(jax.random.PRNGKey(Q + R))
    q, r = _rand_packed(k1, Q, W), _rand_packed(k2, R, W)
    assert (np.asarray(mops.hamming_matrix(q, r, W * 32))
            == np.asarray(mref.hamming_matrix(q, r, W * 32))).all()


@pytest.mark.parametrize("q_tile,r_tile,word_tile", [
    (8, 64, 4), (16, 128, 16), (4, 32, 2)])
def test_hamming_kernel_tiling_invariance(q_tile, r_tile, word_tile):
    """Block-shape knobs (the paper's Q_BLOCK/MAX_R/FACTOR) never change
    results — only the schedule."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    q, r = _rand_packed(k1, 24, 8), _rand_packed(k2, 300, 8)
    base = np.asarray(href.hamming_matrix(q, r))
    got = np.asarray(hops.hamming_matrix(
        q, r, q_tile=q_tile, r_tile=r_tile, word_tile=word_tile))
    assert (got == base).all()


@pytest.mark.parametrize("Q,R,W", [(8, 64, 4), (30, 260, 8)])
@pytest.mark.parametrize("k", [1, 3])
def test_fused_search_kernel_sweep(Q, R, W, k):
    """Pallas running-argmax top-k vs the lax.top_k XLA oracle — two
    independent reductions must agree exactly, including tie order."""
    key = jax.random.PRNGKey(Q)
    ks = jax.random.split(key, 4)
    q, r = _rand_packed(ks[0], Q, W), _rand_packed(ks[1], R, W)
    qp = jax.random.uniform(ks[2], (Q,), minval=400, maxval=1800)
    rp = jax.random.uniform(ks[3], (R,), minval=400, maxval=1800)
    qc = jnp.where(jnp.arange(Q) % 2 == 0, 2, 3).astype(jnp.int32)
    rc = jnp.where(jnp.arange(R) % 3 == 0, 3, 2).astype(jnp.int32)
    o = href.fused_search(q, r, qp, rp, qc, rc, dim=W * 32, k=k)
    g = hops.fused_search(q, r, qp, rp, qc, rc, dim=W * 32, k=k)
    for name, a, b in zip(("std_sim", "std_idx", "open_sim", "open_idx"), o, g):
        assert a.shape == (Q, k), name
        assert (np.asarray(a) == np.asarray(b)).all(), name


@pytest.mark.parametrize("Q,R,W", [(8, 64, 4), (30, 260, 8)])
@pytest.mark.parametrize("k", [1, 3])
def test_fused_search_mxu_kernel_sweep(Q, R, W, k):
    """MXU dot formulation of the fused kernel vs the same XLA oracle —
    exact integer math, so tie order must match bit-for-bit too."""
    key = jax.random.PRNGKey(Q + k)
    ks = jax.random.split(key, 4)
    q, r = _rand_packed(ks[0], Q, W), _rand_packed(ks[1], R, W)
    qp = jax.random.uniform(ks[2], (Q,), minval=400, maxval=1800)
    rp = jax.random.uniform(ks[3], (R,), minval=400, maxval=1800)
    qc = jnp.where(jnp.arange(Q) % 2 == 0, 2, 3).astype(jnp.int32)
    rc = jnp.where(jnp.arange(R) % 3 == 0, 3, 2).astype(jnp.int32)
    o = href.fused_search(q, r, qp, rp, qc, rc, dim=W * 32, k=k)
    g = mops.fused_search(q, r, qp, rp, qc, rc, dim=W * 32, k=k)
    for name, a, b in zip(("std_sim", "std_idx", "open_sim", "open_idx"), o, g):
        assert a.shape == (Q, k), name
        assert (np.asarray(a) == np.asarray(b)).all(), name


def _bits(key, n, w):
    return jax.random.bits(key, (n, w), jnp.uint32)


@pytest.mark.parametrize("W", [16, 128])
@pytest.mark.parametrize("start_block,n_blocks", [(0, 1), (5, 1), (0, 6),
                                                  (1, 5)],
                         ids=["first", "last", "all", "to_last"])
def test_scan_tile_kernel_in_place(W, start_block, n_blocks):
    """The vpu scan step's kernel reads a run of library rows in place and
    equals the XLA tile bit for bit: one row block and many (a partial last
    grid step included), runs at the library's first and last block, and
    words whose popcount is 0 or 32."""
    sr = hkern.SCAN_ROWS
    k1, k2 = jax.random.split(jax.random.PRNGKey(W + start_block))
    hvs = _bits(k1, 6 * sr, W).at[::7].set(0).at[3::7].set(0xFFFFFFFF)
    q = (_bits(k2, 16, W).at[0].set(0).at[1].set(0xFFFFFFFF)
         .at[2, ::2].set(0).at[3, 1::2].set(0xFFFFFFFF))
    got = hkern.scan_tile_pallas(q, hvs, jnp.int32(start_block),
                                 n_blocks=n_blocks, interpret=True)
    want = hamming_matrix_packed(
        q, hvs[start_block * sr:(start_block + n_blocks) * sr])
    assert got.shape == want.shape == (16, n_blocks * sr)
    assert (np.asarray(got) == np.asarray(want)).all()


# The scan step's windows, checked at their edges: 2**-16 of a precursor
# m/z, so 1024 Da's standard window ends at exactly 1024 + 2**-6 in f32.
EDGE_PPM = 15.2587890625
EDGE_PMZ = 1024.0


def _scan_case_library(w=8, lib_blocks=6, seed=21):
    """A library of ``lib_blocks`` scan row blocks and one query block
    that between them hold every case the scan step's top-k must get
    right (see ``SCAN_CASES``). Returns (lib, queries, planted)."""
    sr = hkern.SCAN_ROWS
    n = lib_blocks * sr
    rng = np.random.default_rng(seed)
    pool = rng.integers(0, 2 ** 32, (3, w), dtype=np.uint32)
    hvs = pool[rng.integers(0, 3, n)]
    hvs[::5] = rng.integers(0, 2 ** 32, (len(hvs[::5]), w), dtype=np.uint32)
    half = n // 2                           # charge 2 rows, then charge 3
    charge = np.where(np.arange(n) < half, 2, 3).astype(np.int32)
    pmz = np.tile(np.linspace(950.0, 1100.0, half), 2).astype(np.float32)
    pad = np.r_[half - 172:half, n - 144:n]
    pmz[pad] = hops.PAD_PMZ
    hvs[pad] = pool[0]                      # would win every pool query

    edge = rng.integers(0, 2 ** 32, (w,), dtype=np.uint32)
    std_edge = np.float32(EDGE_PMZ + 2 ** -6)
    up = np.float32(2000.0)
    # Each planted run, lowest row first: outside the standard window by
    # one ulp, on its edge, outside the open window, on its edge (below),
    # outside, on its edge (above). All score ``dim`` for the edge queries.
    edges = np.asarray([np.nextafter(std_edge, up), std_edge,
                        np.nextafter(np.float32(949.0), np.float32(0.0)),
                        np.float32(949.0),
                        np.nextafter(np.float32(1099.0), up),
                        np.float32(1099.0)], np.float32)
    planted = {}
    for c, r0 in ((2, 1100), (3, 4200)):
        rows = r0 + np.arange(len(edges))
        pmz[rows], hvs[rows], charge[rows] = edges, edge, c
        planted[c] = rows

    q = rng.integers(0, 2 ** 32, (16, w), dtype=np.uint32)
    qp = rng.uniform(950.0, 1100.0, 16).astype(np.float32)
    qc = rng.integers(2, 4, 16).astype(np.int32)
    q[0], qp[0], qc[0] = edge, EDGE_PMZ, 2          # window edges
    q[1], qp[1], qc[1] = edge, EDGE_PMZ, 3
    qp[2], qc[2] = 5000.0, 2                        # no row in any window
    qp[3], qc[3] = 1000.0, 4
    for i, row in ((4, 5500), (5, 4500), (6, 2600)):   # needles
        q[i], qp[i], qc[i] = hvs[row], pmz[row], charge[row]
    for i in (7, 8, 9):                             # ties everywhere
        q[i], qp[i], qc[i] = pool[i % 3], 1025.0, 2
    lib = tuple(map(jnp.asarray, (hvs, pmz, charge)))
    return lib, tuple(map(jnp.asarray, (q, qp, qc))), planted


# (start block, blocks) of each case's run over ``_scan_case_library``.
SCAN_CASES = {
    "ties": (0, 2),
    "charge_boundary": (2, 2),
    "pad_rows": (2, 1),
    "empty_window": (3, 2),
    "window_edges": (1, 4),
    "shifted_last_step": (1, 5),
    "partial_step": (0, 5),
    "exhaustive": (0, 6),
}


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("case", list(SCAN_CASES))
def test_scan_best_kernel_equals_tile_and_topk(case, k):
    """The vpu scan step with its top-k in VMEM returns, bit for bit, what
    the distance tile followed by ``_find_topk_dual`` returns, and what the
    ``lax.top_k`` oracle returns; each case also checks that its run holds
    what it is named for."""
    sr = hkern.SCAN_ROWS
    (hvs, pmz, charge), (q, qp, qc), planted = _scan_case_library()
    dim = 32 * hvs.shape[1]
    start, n_blocks = SCAN_CASES[case]
    rows = slice(start * sr, (start + n_blocks) * sr)
    p = SearchParams(ppm_tol=EDGE_PPM, top_k=k)
    got = hops.scan_best(q, qp, qc, hvs, pmz, charge, jnp.int32(start * sr),
                         rk=n_blocks * sr, dim=dim, ppm_tol=p.ppm_tol,
                         open_tol_da=p.open_tol_da, k=k, interpret=True)
    rp, rc = pmz[rows], charge[rows]
    sims = dim - hkern.scan_tile_pallas(q, hvs, jnp.int32(start),
                                        n_blocks=n_blocks, interpret=True)
    want = _find_topk_dual(sims, jnp.abs(qp[:, None] - rp[None, :]), qp, qc,
                           rc, rp, p)
    oracle = href.fused_search(q, hvs[rows], qp, rp, qc, rc, dim=dim, k=k,
                               ppm_tol=p.ppm_tol, open_tol_da=p.open_tol_da)
    names = ("std_sim", "std_col", "open_sim", "open_col")
    for name, g, a, b in zip(names, got, want, oracle):
        assert g.shape == (16, k), name
        assert (np.asarray(g) == np.asarray(a)).all(), name
        assert (np.asarray(g) == np.asarray(b)).all(), name

    std_col, open_col = np.asarray(got[1]), np.asarray(got[3])
    steps = -(-n_blocks // hkern.SCAN_BLOCKS_PER_STEP)
    last_step = (steps - 1) * hkern.SCAN_BLOCKS_PER_STEP * sr
    if case == "ties":
        s = np.asarray(sims)[7:10]
        best = s.max(axis=1, keepdims=True)
        tied = (s == best).reshape(3, n_blocks, sr)
        assert (tied.sum(axis=2) > 1).all()     # across lanes, every block
        assert (open_col[7:10, 0] >= 0).all()
    elif case == "charge_boundary":
        assert set(np.asarray(rc).tolist()) == {2, 3}
        assert {int(c) for c in np.asarray(qc)[open_col[:, 0] >= 0]} == {2, 3}
    elif case == "pad_rows":
        pad = np.asarray(rp) == hops.PAD_PMZ
        assert pad.any() and (np.asarray(sims)[7:10][:, pad] == dim).any()
        assert not pad[open_col[open_col >= 0]].any()
    elif case == "empty_window":
        for out in got:
            assert (np.asarray(out)[2:4] == -1).all()
    elif case == "window_edges":
        for i, c in ((0, 2), (1, 3)):
            rel = planted[c] - start * sr
            assert std_col[i, 0] == rel[1]
            assert open_col[i, 0] == rel[0]
            assert not np.isin(open_col[i], rel[[2, 4]]).any()
            if k == 4:
                assert set(open_col[i]) == set(rel[[0, 1, 3, 5]])
    else:                     # the last grid step holds a needle's winner
        assert steps > 1 or case == "exhaustive"
        assert (open_col[4:7, 0] >= last_step).any()
        assert n_blocks % hkern.SCAN_BLOCKS_PER_STEP or case == "exhaustive"


def _tied_library(n_rows=5000, n_queries=32, dim=512, store_path=None):
    """A library drawn from four distinct hypervectors, so that nearly
    every query has many equal-scoring candidates in its windows. With
    ``store_path``, the same rows are also written to a store there."""
    rng = np.random.default_rng(11)
    pool = np.asarray(_bits(jax.random.PRNGKey(11), 4, dim // 32))
    hvs = pool[rng.integers(0, 4, n_rows)]
    pmz = rng.uniform(400.0, 1400.0, n_rows).astype(np.float32)
    charge = np.where(np.arange(n_rows) % 2 == 0, 2, 3).astype(np.int32)
    db = build_reference_db(hvs, pmz, charge, np.zeros(n_rows, bool),
                            max_r=hkern.SCAN_ROWS)
    if store_path is not None:
        cfg = OMSConfig(dim=dim, add_decoys=False)
        store = LibraryStore.create(
            store_path, **{k: getattr(cfg, k) for k in CONFIG_KEYS})
        order = np.lexsort((pmz, charge))
        store.append_shard(TARGET, hvs[order], pmz[order], charge[order],
                           order.astype(np.int32))
    q_pmz = np.sort(rng.uniform(600.0, 1200.0, n_queries)).astype(np.float32)
    q_charge = np.full(n_queries, 2, np.int32)
    q_hvs = pool[rng.integers(0, 4, n_queries)]
    q_hvs[::3] ^= np.uint32(1 << 7)          # one bit off a pool vector
    return db, jnp.asarray(q_hvs), jnp.asarray(q_pmz), jnp.asarray(q_charge)


@pytest.mark.parametrize("top_k", [1, 4])
def test_scan_kernel_search_equals_xla_with_ties(request, tmp_path, top_k):
    """The blocked scan through the in-place kernel returns exactly what the
    XLA lowering returns, tie order (similarity desc, row asc) included;
    so do a whole ``oms_search`` and a search of the same rows streamed
    from their store slab by slab."""
    db, qh, qp, qc = _tied_library(store_path=str(tmp_path / "store"))
    params = SearchParams(q_block=16, top_k=top_k, k_blocks=plan_search(
        db, np.asarray(qp), np.asarray(qc), open_tol_da=75.0, q_block=16))
    streamed = OMSPipeline.from_store(
        str(tmp_path / "store"), resident=False,
        slab_rows=2 * hkern.SCAN_ROWS, max_r=hkern.SCAN_ROWS, top_k=top_k)

    def scan(d, a, b, c):
        return _search_sorted_padded(d, a, b, c, params=params, dim=512)

    def searches():
        return (scan(db, qh, qp, qc),
                oms_search(db, qh, qp, qc, params, dim=512),
                streamed.search_encoded(qh, qp, qc).result)

    assert "pallas_call" not in str(jax.make_jaxpr(scan)(db, qh, qp, qc))
    want = searches()
    request.getfixturevalue("force_scan_kernel")
    assert "pallas_call" in str(jax.make_jaxpr(scan)(db, qh, qp, qc))
    got = searches()
    assert streamed.engine.last_stats.n_scanned > 1
    sims = np.asarray(want[0][2])
    assert (sims[:, :top_k] >= 0).all()
    assert (np.diff(sims, axis=1) == 0).any() or top_k == 1
    for a, b in zip(want, got):
        names = getattr(a, "_fields", ("std_sim", "std_row", "open_sim",
                                       "open_row"))
        for name, x, y in zip(names, a, b):
            assert (np.asarray(x) == np.asarray(y)).all(), name


def test_mxu_effective_tiles_clamp():
    """Regression: the old clamp `min(q_tile, Q) if Q >= q_tile else q_tile`
    always returned q_tile, so small inputs paid full-tile padding. The
    shared `effective_tiles` must really clamp (and keep word_tile a
    divisor of W)."""
    qt, rt, wt = mops.effective_tiles(5, 70, 7)
    assert qt == 5 and rt == 70
    assert wt == 7 and 7 % wt == 0
    qt, rt, wt = mops.effective_tiles(64, 1024, 2 * mops.WORD_TILE)
    assert (qt, rt, wt) == (mops.Q_TILE, mops.R_TILE, mops.WORD_TILE)
    # word_tile that doesn't divide W steps down to the largest divisor
    assert mops.effective_tiles(8, 8, 6, word_tile=4)[2] == 3


@pytest.mark.parametrize("Q,R,W", [(3, 5, 2), (1, 1, 1), (7, 130, 3)])
def test_hamming_mxu_small_shape_clamp(Q, R, W):
    """Shapes far below the default tiles must still be exact (they now run
    at clamped launch tiles instead of padding to the full defaults)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(Q * 31 + R))
    q, r = _rand_packed(k1, Q, W), _rand_packed(k2, R, W)
    assert (np.asarray(mops.hamming_matrix(q, r, W * 32))
            == np.asarray(mref.hamming_matrix(q, r, W * 32))).all()


@pytest.mark.parametrize("B,P,F,L,W", [
    (4, 10, 50, 8, 4), (23, 40, 500, 16, 8), (16, 64, 100, 32, 2)])
def test_hdencode_kernel_sweep(B, P, F, L, W):
    D = W * 32
    cb = make_codebooks(jax.random.PRNGKey(5), n_bins=F, n_levels=L, dim=D)
    ks = jax.random.split(jax.random.PRNGKey(B * P), 3)
    bins = jax.random.randint(ks[0], (B, P), 0, F)
    levels = jax.random.randint(ks[1], (B, P), 0, L)
    mask = jax.random.bernoulli(ks[2], 0.8, (B, P))
    sp = PreprocessedSpectra(bins, levels, mask, None, None)
    oracle = np.asarray(encode_spectra(sp, cb))
    got = np.asarray(eops.hdencode(bins, levels, mask, cb.id_hvs,
                                   cb.level_hvs, cb.tiebreak))
    assert (oracle == got).all()


def test_hdencode_all_masked_spectrum():
    """A spectrum with zero surviving peaks must not crash (tie on 0 counts
    resolves to the tiebreak HV)."""
    D, F, L = 128, 20, 4
    cb = make_codebooks(jax.random.PRNGKey(0), n_bins=F, n_levels=L, dim=D)
    B, P = 3, 5
    bins = jnp.zeros((B, P), jnp.int32)
    levels = jnp.zeros((B, P), jnp.int32)
    mask = jnp.zeros((B, P), bool)
    sp = PreprocessedSpectra(bins, levels, mask, None, None)
    oracle = np.asarray(encode_spectra(sp, cb))
    got = np.asarray(eops.hdencode(bins, levels, mask, cb.id_hvs,
                                   cb.level_hvs, cb.tiebreak))
    assert (oracle == got).all()
    assert (oracle == np.asarray(cb.tiebreak)).all()
