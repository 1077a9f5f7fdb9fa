"""Pallas TPU kernel: packed XOR + popcount Hamming search (paper §II-C).

This is the TPU-native port of RapidOMS's FPGA search kernel:

  FPGA concept                          TPU realisation
  ------------------------------------  -----------------------------------
  reference block cached in URAM        (RT, W) uint32 ref tile in VMEM
  Q_BLOCK queries / iteration           (QT, W) query tile in VMEM
  Dhv/FACTOR streaming FIFOs            static WT-word chunks of the tiles
  unrolled XOR + popcount modules       vectorised xor + lax.population_count
  parallel find_max_score (std + open)  fused dual-window running argmax
                                        accumulated across the ref-block grid

Two kernels:
  * ``hamming_matrix_kernel`` — all-pairs Hamming tile (building block,
    validated against the oracle over shape/dtype sweeps);
  * ``fused_search_kernel`` — the full paper kernel: Hamming + PMZ windows +
    dual running *top-k* winners (k static, default 1), one pass over the
    reference stream, no (Q, R) score matrix ever materialised in HBM. The
    MXU kernel (``repro.kernels.hamming_mxu``) reuses it with its own tile
    score.

TPU lowering rules the layout follows: word chunks are static ref slices
(the compiler refuses a dynamic lane offset that is not a multiple of 128,
and a 128-word chunk fills the lanes exactly); the precursor and charge
sidecars enter as 2-D blocks, (QT, 1) per query tile and (1, RT) per
reference tile, so every block keeps its last two dims tile-aligned.

Top-k semantics: per query and per window, the k highest-similarity
references ranked by (similarity desc, reference row asc) — i.e. the first
global maximum wins ties, matching ``jnp.argmax`` at k=1 bit-exactly.
Selection is an unrolled k-step running-argmax merge built from max/min
reductions (see :mod:`repro.kernels.topk`), shared with the orchestrator and
the sharded merge.

Grid iteration order on TPU is sequential over the last grid axis, so the
running-winner accumulation across reference blocks is race-free by
construction (same property the paper gets from its sequential block stream).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.topk import merge_topk, select_topk

# ---------------------------------------------------------------------------
# All-pairs Hamming tile kernel
# ---------------------------------------------------------------------------


def _hamming_tile(q_ref, r_ref, wt: int):
    """(QT, W) x (RT, W) uint32 refs -> (QT, RT) int32, in static wt-word
    chunks (the caller guarantees W % wt == 0)."""
    acc = 0
    for s in range(0, q_ref.shape[1], wt):
        x = jnp.bitwise_xor(q_ref[:, s:s + wt][:, None, :],
                            r_ref[:, s:s + wt][None, :, :])
        acc = acc + jnp.sum(jax.lax.population_count(x).astype(jnp.int32),
                            axis=-1)
    return acc


def hamming_matrix_kernel(q_ref, r_ref, out_ref, *, wt: int):
    out_ref[...] = _hamming_tile(q_ref, r_ref, wt)


def hamming_matrix_pallas(q: jax.Array, r: jax.Array, *, q_tile: int = 16,
                          r_tile: int = 256, word_tile: int = 128,
                          interpret: bool = True) -> jax.Array:
    """q (Q, W) x r (R, W) uint32 -> (Q, R) int32 Hamming distances.

    ``word_tile`` is the paper's Dhv/FACTOR streaming width: it bounds the
    (QT, RT, wt) popcount intermediate to VMEM scale.
    Caller guarantees Q % q_tile == R % r_tile == W % word_tile == 0
    (ops.py pads).
    """
    Q, W = q.shape
    R = r.shape[0]
    grid = (Q // q_tile, R // r_tile)
    return pl.pallas_call(
        functools.partial(hamming_matrix_kernel, wt=word_tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((q_tile, W), lambda i, j: (i, 0)),
            pl.BlockSpec((r_tile, W), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((q_tile, r_tile), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Q, R), jnp.int32),
        interpret=interpret,
    )(q, r)


# ---------------------------------------------------------------------------
# Fused dual-window search kernel (the paper's §II-C kernel)
# ---------------------------------------------------------------------------


def vpu_sims(q_ref, r_ref, *, dim: int, wt: int):
    """(QT, RT) int32 similarity ``dim - hamming`` from xor + popcount."""
    return dim - _hamming_tile(q_ref, r_ref, wt)


def fused_search_kernel(q_ref, r_ref, qp_ref, rp_ref, qc_ref, rc_ref,
                        std_sim_ref, std_idx_ref, open_sim_ref, open_idx_ref,
                        *, sims_fn, r_tile: int, k: int, ppm_tol: float,
                        open_tol_da: float, pad_pmz: float):
    j = pl.program_id(1)

    # init running winners on the first reference block
    @pl.when(j == 0)
    def _init():
        std_sim_ref[...] = jnp.full_like(std_sim_ref[...], -1)
        std_idx_ref[...] = jnp.full_like(std_idx_ref[...], -1)
        open_sim_ref[...] = jnp.full_like(open_sim_ref[...], -1)
        open_idx_ref[...] = jnp.full_like(open_idx_ref[...], -1)

    sims = sims_fn(q_ref, r_ref)                       # (QT, RT)

    qp = qp_ref[...]                                   # (QT, 1)
    rp = rp_ref[...]                                   # (1, RT)
    dpmz = jnp.abs(qp - rp)
    valid = (rp < pad_pmz) & (qc_ref[...] == rc_ref[...])
    std_mask = valid & (dpmz <= qp * (ppm_tol * 1e-6))
    open_mask = valid & (dpmz <= open_tol_da)

    base = (j * r_tile).astype(jnp.int32)

    def update(mask, sim_out, idx_out):
        ts, tc = select_topk(jnp.where(mask, sims, jnp.int32(-1)), k)
        ti = jnp.where(tc >= 0, base + tc, jnp.int32(-1))
        # running winners first: earlier blocks (lower idx) win sim ties
        ms, mi = merge_topk(sim_out[...], idx_out[...], ts, ti, k)
        sim_out[...] = ms
        idx_out[...] = mi

    update(std_mask, std_sim_ref, std_idx_ref)
    update(open_mask, open_sim_ref, open_idx_ref)


def fused_search_pallas(q_hvs, r_hvs, q_pmz, r_pmz, q_charge, r_charge, *,
                        dim: int, k: int = 1, ppm_tol: float = 20.0,
                        open_tol_da: float = 75.0,
                        q_tile: int = 16, r_tile: int = 256,
                        word_tile: int = 128, pad_pmz: float | None = None,
                        sims_fn=None, interpret: bool = True):
    """Returns (std_sim, std_idx, open_sim, open_idx), each (Q, k) int32.

    idx is the row in ``r_hvs`` (or -1); sim = dim - hamming (or -1); rank
    order is (sim desc, row asc). ``k`` is static. ``sims_fn(q_ref, r_ref)``
    scores one tile pair; the default is the VPU xor + popcount tile.
    """
    Q, W = q_hvs.shape
    R = r_hvs.shape[0]
    if pad_pmz is None:
        pad_pmz = float(jnp.finfo(jnp.float32).max)
    if sims_fn is None:
        sims_fn = functools.partial(vpu_sims, dim=dim, wt=word_tile)
    grid = (Q // q_tile, R // r_tile)

    kern = functools.partial(
        fused_search_kernel, sims_fn=sims_fn, r_tile=r_tile, k=k,
        ppm_tol=ppm_tol, open_tol_da=open_tol_da, pad_pmz=pad_pmz)

    q_col = pl.BlockSpec((q_tile, 1), lambda i, j: (i, 0))
    r_row = pl.BlockSpec((1, r_tile), lambda i, j: (0, j))
    out2d = pl.BlockSpec((q_tile, k), lambda i, j: (i, 0))
    shapes = [jax.ShapeDtypeStruct((Q, k), jnp.int32)] * 4
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((q_tile, W), lambda i, j: (i, 0)),
            pl.BlockSpec((r_tile, W), lambda i, j: (j, 0)),
            q_col, r_row, q_col, r_row,
        ],
        out_specs=[out2d, out2d, out2d, out2d],
        out_shape=shapes,
        interpret=interpret,
    )(q_hvs, r_hvs, q_pmz.reshape(Q, 1), r_pmz.reshape(1, R),
      q_charge.reshape(Q, 1), r_charge.reshape(1, R))
