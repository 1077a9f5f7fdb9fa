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

Three kernels:
  * ``hamming_matrix_kernel`` — all-pairs Hamming tile (building block,
    validated against the oracle over shape/dtype sweeps);
  * ``scan_tile_kernel`` — the ``vpu`` backend's blocked-scan tile on TPU:
    one query block against library rows read in place, rows on lanes;
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
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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
# In-place scan step kernel (the vpu backend's blocked-scan tile on TPU)
# ---------------------------------------------------------------------------
#
# One query block against a contiguous run of rows of the resident library,
# read where they lie: the first row block arrives as a scalar-prefetch
# operand and the BlockSpec index map DMAs each grid step's rows straight
# from the library array, so no (rk, W) slice is ever materialised. Each row
# block is transposed once to word-major (W, SCAN_ROWS) — rows on lanes — in
# VMEM and shared by all queries of the block. A vreg then holds 8 words of
# 128 rows; XORed with the same 8 words of a query (broadcast along the
# lanes once per call, in VMEM), its popcounts add up elementwise over the
# W / 8 word groups, and only the last 8-sublane sum remains: nothing
# crosses lanes. The loops are register-blocked: SCAN_Q_GROUP queries x
# SCAN_CHUNK_GROUP 128-row chunks keep their accumulators in vregs, so each
# loaded vreg serves several.

SCAN_ROWS = 1024        # rows per row block; start row and length are multiples
SCAN_BLOCKS_PER_STEP = 4
SCAN_Q_GROUP = 2
SCAN_CHUNK_GROUP = 8


def scan_tile_kernel(start_ref, q_ref, r_ref, out_ref, qb_ref, rt_ref,
                     row_ref, *, n_blocks: int, n_sub: int, lib_blocks: int,
                     q_group: int):
    """One grid step: ``n_sub`` row blocks of the run against the query
    block. ``q_ref`` is the (QT, W) int32 query block; ``r_ref`` holds the
    step's (n_sub * SCAN_ROWS, W) rows, read from ``step_first_block`` (see
    ``scan_tile_pallas``); ``out_ref`` is the step's (QT, n_sub *
    SCAN_ROWS) int32 output tile. VMEM scratch: ``qb_ref`` (QT, W, 128)
    holds each query word broadcast along the lanes, filled at the first
    step; ``rt_ref`` (W, SCAN_ROWS) holds a row block word-major;
    ``row_ref`` (QT, 1, SCAN_ROWS) its distances, one query per leading
    index (a store at a traced query row of ``out_ref`` itself would be
    unaligned)."""
    nq, w = q_ref.shape
    first = pl.program_id(0) * n_sub

    @pl.when(pl.program_id(0) == 0)
    def _():
        for q in range(nq):
            qb_ref[q] = lax.broadcast_in_dim(q_ref[q:q + 1, :], (128, w),
                                             (0, 1)).T

    # The last step's rows may have been read from an earlier block so that
    # they stay inside the library: skip that many blocks of the buffer.
    shift = start_ref[0] + first - step_first_block(
        start_ref[0], first, n_sub, lib_blocks)

    # The body below unrolls into about a thousand ops, traced in every
    # process that compiles or loads the scan: lax, not jnp (a jnp call
    # traces a nested jit), and one traced copy for all query groups.
    def group(q0, base):
        cols = [pl.ds(pl.multiple_of(base + 128 * c, 128), 128)
                for c in range(SCAN_CHUNK_GROUP)]
        acc = {}
        for g in range(0, w, 8):
            rows = [rt_ref[g:g + 8, col] for col in cols]
            for qq in range(q_group):
                qv = qb_ref[q0 + qq, g:g + 8, :]
                for c, r in enumerate(rows):
                    x = lax.population_count(lax.bitwise_xor(r, qv))
                    acc[qq, c] = x if g == 0 else lax.add(acc[qq, c], x)
        for (qq, c), a in acc.items():
            row_ref[q0 + qq, :, cols[c]] = lax.reduce_sum(a, (0,))[None, :]

    def row_block(i, carry):
        @pl.when(first + i < n_blocks)
        def _():
            off = pl.multiple_of((shift + i) * SCAN_ROWS, SCAN_ROWS)
            for c in range(0, SCAN_ROWS, 128):
                rt_ref[:, c:c + 128] = pltpu.bitcast(
                    r_ref[pl.ds(off + c, 128), :], jnp.int32).T
            n_cg = SCAN_ROWS // (128 * SCAN_CHUNK_GROUP)

            def chunks(t, carry):
                group((t // n_cg) * q_group, pl.multiple_of(
                    (t % n_cg) * 128 * SCAN_CHUNK_GROUP, 128))
                return carry

            lax.fori_loop(0, nq // q_group * n_cg, chunks, 0)
            block = pl.ds(pl.multiple_of(i * SCAN_ROWS, SCAN_ROWS), SCAN_ROWS)
            for q in range(nq):
                out_ref[q:q + 1, block] = row_ref[q]
        return carry

    lax.fori_loop(0, n_sub, row_block, 0)


def step_first_block(start_block, first, n_sub: int, lib_blocks: int):
    """Library block where a grid step's rows are read from: the step's
    first block of the run, moved back so that its ``n_sub`` blocks end
    inside the library."""
    return jnp.minimum(start_block + first, lib_blocks - n_sub)


def scan_tile_pallas(q: jax.Array, hvs: jax.Array, start_block: jax.Array, *,
                     n_blocks: int, interpret: bool = True) -> jax.Array:
    """q (QT, W) uint32 against rows ``[start_block, start_block + n_blocks)
    * SCAN_ROWS`` of ``hvs`` (R, W) uint32 -> (QT, n_blocks * SCAN_ROWS)
    int32 Hamming distances. ``start_block`` is a traced int32 scalar; the
    caller guarantees the rows lie inside ``hvs``, R % SCAN_ROWS == 0 and
    W % 8 == 0."""
    nq, w = q.shape
    lib_blocks = hvs.shape[0] // SCAN_ROWS
    n_sub = min(SCAN_BLOCKS_PER_STEP, n_blocks, lib_blocks)
    steps = -(-n_blocks // n_sub)
    q_group = math.gcd(SCAN_Q_GROUP, nq)

    def rows_map(j, start):
        return (step_first_block(start[0], j * n_sub, n_sub, lib_blocks)
                * SCAN_ROWS, 0)

    out = pl.pallas_call(
        functools.partial(scan_tile_kernel, n_blocks=n_blocks, n_sub=n_sub,
                          lib_blocks=lib_blocks, q_group=q_group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            in_specs=[pl.BlockSpec((nq, w), lambda j, start: (0, 0)),
                      pl.BlockSpec((pl.Element(n_sub * SCAN_ROWS),
                                    pl.Element(w)), rows_map)],
            out_specs=pl.BlockSpec((nq, n_sub * SCAN_ROWS),
                                   lambda j, start: (0, j)),
            scratch_shapes=[pltpu.VMEM((nq, w, 128), jnp.int32),
                            pltpu.VMEM((w, SCAN_ROWS), jnp.int32),
                            pltpu.VMEM((nq, 1, SCAN_ROWS), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((nq, steps * n_sub * SCAN_ROWS),
                                       jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(start_block.astype(jnp.int32).reshape(1),
      lax.bitcast_convert_type(q, jnp.int32), hvs)
    return out[:, :n_blocks * SCAN_ROWS]


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
