"""Jitted wrappers for the hamming Pallas kernels (padding + dispatch)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.hamming import hamming as _k

PAD_PMZ = float(jnp.finfo(jnp.float32).max)

# Default launch tiles. Inputs are padded up to these multiples before the
# kernel call, so the padded copies (and the kernel's output tile) — not the
# raw (Q, Rk) extents — are what bounds device memory; the peak_intermediate
# contracts in repro.core.backends read these to stay honest about that.
# A 128-word chunk fills the TPU's 128 vector lanes; narrower chunks pad
# every vreg and multiply the VMEM footprint of the popcount intermediate.
Q_TILE = 16
R_TILE = 256
WORD_TILE = 128


def _pad_rows(x, mult, value=0):
    pad = (-x.shape[0]) % mult
    if pad == 0:
        return x
    cfg = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, cfg, constant_values=value)


@partial(jax.jit, static_argnames=("q_tile", "r_tile", "word_tile", "interpret"))
def hamming_matrix(q, r, *, q_tile: int = Q_TILE, r_tile: int = R_TILE,
                   word_tile: int = WORD_TILE, interpret: bool | None = None):
    if interpret is None:
        interpret = interpret_default()
    Q, R = q.shape[0], r.shape[0]
    W = q.shape[1]
    wt = min(word_tile, W)
    while W % wt:
        wt -= 1
    qp = _pad_rows(q, q_tile)
    rp = _pad_rows(r, r_tile)
    out = _k.hamming_matrix_pallas(
        qp, rp, q_tile=q_tile, r_tile=min(r_tile, rp.shape[0]),
        word_tile=wt, interpret=interpret)
    return out[:Q, :R]


@partial(jax.jit, static_argnames=("dim", "k", "ppm_tol", "open_tol_da",
                                   "q_tile", "r_tile", "word_tile",
                                   "interpret"))
def fused_search(q_hvs, r_hvs, q_pmz, r_pmz, q_charge, r_charge, *, dim: int,
                 k: int = 1, ppm_tol: float = 20.0, open_tol_da: float = 75.0,
                 q_tile: int = Q_TILE, r_tile: int = R_TILE,
                 word_tile: int = WORD_TILE, interpret: bool | None = None):
    """Fused dual-window top-k search; returns four (Q, k) int32 arrays."""
    if interpret is None:
        interpret = interpret_default()
    Q = q_hvs.shape[0]
    W = q_hvs.shape[1]
    wt = min(word_tile, W)
    while W % wt:
        wt -= 1
    rt = min(r_tile, r_hvs.shape[0])

    qh = _pad_rows(q_hvs, q_tile)
    qp = _pad_rows(q_pmz, q_tile)
    qc = _pad_rows(q_charge, q_tile, value=-(2 ** 30))
    rh = _pad_rows(r_hvs, rt)
    rp = _pad_rows(r_pmz, rt, value=PAD_PMZ)
    rc = _pad_rows(r_charge, rt, value=-1)

    std_sim, std_idx, open_sim, open_idx = _k.fused_search_pallas(
        qh, rh, qp, rp, qc, rc, dim=dim, k=k, ppm_tol=ppm_tol,
        open_tol_da=open_tol_da, q_tile=q_tile, r_tile=rt,
        word_tile=wt, pad_pmz=PAD_PMZ, interpret=interpret)
    return std_sim[:Q], std_idx[:Q], open_sim[:Q], open_idx[:Q]


def scan_tile_fits(max_r: int, n_words: int) -> bool:
    """Whether the in-place scan kernel takes a library blocked in ``max_r``
    rows: every scanned run then starts and ends on a kernel row block, and
    a row is whole 128-word lane chunks (``dim`` a multiple of 4096)."""
    return max_r % _k.SCAN_ROWS == 0 and n_words % 128 == 0


@partial(jax.jit, static_argnames=("rk", "dim", "ppm_tol", "open_tol_da",
                                   "k", "interpret"))
def scan_best(q, q_pmz, q_charge, hvs, pmz, charge, start_row, *, rk: int,
              dim: int, ppm_tol: float, open_tol_da: float, k: int,
              interpret: bool | None = None):
    """Dual-window top-k of the (QT, W) queries over rows ``[start_row,
    start_row + rk)`` of the library ``hvs`` / ``pmz`` / ``charge``, read in
    place -> (std_sim, std_col, open_sim, open_col), each (QT, k) int32
    with col the row's column in the run: what ``core.search.
    _find_topk_dual`` returns for those rows' distance tile, computed
    without one. ``start_row`` (traced) and ``rk`` are multiples of
    ``SCAN_ROWS``."""
    if interpret is None:
        interpret = interpret_default()
    return _k.scan_best_pallas(
        q, q_pmz, q_charge, q_pmz * (ppm_tol * 1e-6), hvs, pmz, charge,
        start_row // _k.SCAN_ROWS, n_blocks=rk // _k.SCAN_ROWS, dim=dim,
        k=k, open_tol_da=open_tol_da, pad_pmz=PAD_PMZ, interpret=interpret)
