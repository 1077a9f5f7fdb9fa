"""Jitted wrappers for the MXU hamming kernels (padding + dispatch)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.hamming_mxu import hamming_mxu as _k

PAD_PMZ = float(jnp.finfo(jnp.float32).max)

# Default launch tiles (see repro.kernels.hamming.ops): inputs pad up to
# these multiples, and the peak_intermediate contract bounds in
# repro.core.backends account for the padded extents via these constants
# (routed through repro.tune.tiles_for, which may substitute tuned tiles).
Q_TILE = 32
R_TILE = 256
WORD_TILE = 128   # one 128-lane chunk per matmul (see hamming.ops)


def effective_tiles(Q: int, R: int, W: int, *, q_tile: int = Q_TILE,
                    r_tile: int = R_TILE, word_tile: int = WORD_TILE
                    ) -> tuple[int, int, int]:
    """Launch tiles after clamping to the actual extents.

    A tile never exceeds its input's row count (a 5-query batch launches a
    5-row tile and pads nothing, instead of padding to a full Q_TILE), and
    the word tile shrinks to the largest divisor of W at or below the
    requested width. Shared by the wrappers here and by the
    peak_intermediate bounds in repro.core.backends, so the contract math
    and the launch math cannot diverge.
    """
    qt = min(q_tile, Q)
    rt = min(r_tile, R)
    wt = min(word_tile, W)
    while W % wt:
        wt -= 1
    return qt, rt, wt


def _pad_rows(x, mult, value=0):
    pad = (-x.shape[0]) % mult
    if pad == 0:
        return x
    cfg = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, cfg, constant_values=value)


@partial(jax.jit, static_argnames=("dim", "q_tile", "r_tile", "word_tile",
                                   "interpret"))
def hamming_matrix(q, r, dim: int, *, q_tile: int = Q_TILE,
                   r_tile: int = R_TILE,
                   word_tile: int = WORD_TILE, interpret: bool | None = None):
    if interpret is None:
        interpret = interpret_default()
    Q, W = q.shape
    R = r.shape[0]
    if dim != W * 32:
        raise ValueError("MXU kernel requires dim == 32*W (pad HVs to words)")
    qt, rt, wt = effective_tiles(Q, R, W, q_tile=q_tile, r_tile=r_tile,
                                 word_tile=word_tile)
    qp, rp = _pad_rows(q, qt), _pad_rows(r, rt)
    out = _k.hamming_matrix_mxu_pallas(
        qp, rp, dim=dim, q_tile=qt, r_tile=rt, word_tile=wt,
        interpret=interpret)
    return out[:Q, :R]


@partial(jax.jit, static_argnames=("dim", "k", "ppm_tol", "open_tol_da",
                                   "q_tile", "r_tile", "word_tile",
                                   "interpret"))
def fused_search(q_hvs, r_hvs, q_pmz, r_pmz, q_charge, r_charge, *, dim: int,
                 k: int = 1, ppm_tol: float = 20.0, open_tol_da: float = 75.0,
                 q_tile: int = Q_TILE, r_tile: int = R_TILE,
                 word_tile: int = WORD_TILE, interpret: bool | None = None):
    """Fused dual-window top-k search on the MXU; four (Q, k) int32 arrays.

    Padding discipline matches ``repro.kernels.hamming.ops.fused_search``:
    padded queries carry an impossible charge, padded references carry
    PAD_PMZ (masked out in-kernel), and the outputs slice back to Q rows.
    """
    if interpret is None:
        interpret = interpret_default()
    Q, W = q_hvs.shape
    R = r_hvs.shape[0]
    if dim != W * 32:
        raise ValueError("MXU kernel requires dim == 32*W (pad HVs to words)")
    qt, rt, wt = effective_tiles(Q, R, W, q_tile=q_tile, r_tile=r_tile,
                                 word_tile=word_tile)

    qh = _pad_rows(q_hvs, qt)
    qp = _pad_rows(q_pmz, qt)
    qc = _pad_rows(q_charge, qt, value=-(2 ** 30))
    rh = _pad_rows(r_hvs, rt)
    rp = _pad_rows(r_pmz, rt, value=PAD_PMZ)
    rc = _pad_rows(r_charge, rt, value=-1)

    std_sim, std_idx, open_sim, open_idx = _k.fused_search_mxu_pallas(
        qh, rh, qp, rp, qc, rc, dim=dim, k=k, ppm_tol=ppm_tol,
        open_tol_da=open_tol_da, q_tile=qt, r_tile=rt,
        word_tile=wt, pad_pmz=PAD_PMZ, interpret=interpret)
    return std_sim[:Q], std_idx[:Q], open_sim[:Q], open_idx[:Q]
