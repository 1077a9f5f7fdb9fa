"""Pallas kernel sweeps: shapes × dtypes vs pure-jnp oracles (exact)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.blocking import build_reference_db
from repro.core.encoding import (PreprocessedSpectra, encode_spectra,
                                 make_codebooks)
from repro.core.packing import hamming_matrix_packed
from repro.core.search import SearchParams, _search_sorted_padded, plan_search
from repro.kernels.hamming import hamming as hkern
from repro.kernels.hamming import ops as hops
from repro.kernels.hamming import ref as href
from repro.kernels.hamming_mxu import ops as mops
from repro.kernels.hamming_mxu import ref as mref
from repro.kernels.hdencode import ops as eops


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
    got = hops.scan_tile(q, hvs, jnp.int32(start_block * sr),
                         rk=n_blocks * sr, interpret=True)
    want = hamming_matrix_packed(
        q, hvs[start_block * sr:(start_block + n_blocks) * sr])
    assert got.shape == want.shape == (16, n_blocks * sr)
    assert (np.asarray(got) == np.asarray(want)).all()


def _tied_library(n_rows=5000, n_queries=32, dim=512):
    """A library drawn from four distinct hypervectors, so that nearly
    every query has many equal-scoring candidates in its windows."""
    rng = np.random.default_rng(11)
    pool = np.asarray(_bits(jax.random.PRNGKey(11), 4, dim // 32))
    hvs = pool[rng.integers(0, 4, n_rows)]
    pmz = rng.uniform(400.0, 1400.0, n_rows).astype(np.float32)
    charge = np.where(np.arange(n_rows) % 2 == 0, 2, 3).astype(np.int32)
    db = build_reference_db(hvs, pmz, charge, np.zeros(n_rows, bool),
                            max_r=hkern.SCAN_ROWS)
    q_pmz = np.sort(rng.uniform(600.0, 1200.0, n_queries)).astype(np.float32)
    q_charge = np.full(n_queries, 2, np.int32)
    q_hvs = pool[rng.integers(0, 4, n_queries)]
    q_hvs[::3] ^= np.uint32(1 << 7)          # one bit off a pool vector
    return db, jnp.asarray(q_hvs), jnp.asarray(q_pmz), jnp.asarray(q_charge)


@pytest.mark.parametrize("top_k", [1, 4])
def test_scan_kernel_search_equals_xla_with_ties(request, top_k):
    """The blocked scan through the in-place kernel returns exactly what the
    XLA lowering returns, tie order (similarity desc, row asc) included."""
    db, qh, qp, qc = _tied_library()
    params = SearchParams(q_block=16, top_k=top_k, k_blocks=plan_search(
        db, np.asarray(qp), np.asarray(qc), open_tol_da=75.0, q_block=16))
    def scan(d, a, b, c):
        return _search_sorted_padded(d, a, b, c, params=params, dim=512)

    assert "pallas_call" not in str(jax.make_jaxpr(scan)(db, qh, qp, qc))
    want = scan(db, qh, qp, qc)
    request.getfixturevalue("force_scan_kernel")
    assert "pallas_call" in str(jax.make_jaxpr(scan)(db, qh, qp, qc))
    got = scan(db, qh, qp, qc)
    sims = np.asarray(want[2])
    assert (sims[:, :top_k] >= 0).all()
    assert (np.diff(sims, axis=1) == 0).any() or top_k == 1
    for name, a, b in zip(("std_sim", "std_row", "open_sim", "open_row"),
                          want, got):
        assert (np.asarray(a) == np.asarray(b)).all(), name


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
