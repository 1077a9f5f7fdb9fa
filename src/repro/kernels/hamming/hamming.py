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

Four kernels:
  * ``hamming_matrix_kernel`` — all-pairs Hamming tile (building block,
    validated against the oracle over shape/dtype sweeps);
  * ``scan_best_kernel`` — the ``vpu`` backend's blocked-scan step on TPU:
    one query block against library rows read in place, rows on lanes,
    with the dual-window top-k kept in VMEM (``scan_tile_kernel`` is the
    same scan returning the whole distance tile);
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
# In-place scan step kernels (the vpu backend's blocked scan on TPU)
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
#
# Two kernels share that body. ``scan_best_kernel`` is the vpu backend's
# scan step: it masks each row block's distances by the precursor windows
# in VMEM and keeps per-lane running winners, so only the (QT, k) winners
# of each window leave the kernel. ``scan_tile_kernel`` writes the whole
# (QT, rk) distance tile instead (``scripts/scan_sweep.py`` times it).

SCAN_ROWS = 1024        # rows per row block; start row and length are multiples
SCAN_BLOCKS_PER_STEP = 4
SCAN_Q_GROUP = 2
SCAN_CHUNK_GROUP = 8


def _scan_row_blocks(start_ref, q_ref, r_ref, qb_ref, rt_ref, row_ref, emit,
                     *, n_blocks: int, n_sub: int, lib_blocks: int,
                     q_group: int):
    """One grid step: ``n_sub`` row blocks of the run against the query
    block. ``q_ref`` is the (QT, W) int32 query block; ``r_ref`` holds the
    step's (n_sub * SCAN_ROWS, W) rows, read from ``step_first_block`` (see
    ``scan_tile_pallas``). VMEM scratch: ``qb_ref`` (QT, W, 128) holds each
    query word broadcast along the lanes, filled at the first step;
    ``rt_ref`` (W, SCAN_ROWS) holds a row block word-major; ``row_ref``
    (QT, 1, SCAN_ROWS) its distances, one query per leading index (a store
    at a traced query row of a (QT, SCAN_ROWS) array would be unaligned).
    For each block ``i`` of the step inside the run, ``emit(i, b)`` takes
    ``row_ref``; ``b`` is the block's index in ``r_ref``."""
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
            emit(i, shift + i)
        return carry

    lax.fori_loop(0, n_sub, row_block, 0)


def scan_tile_kernel(start_ref, q_ref, r_ref, out_ref, qb_ref, rt_ref,
                     row_ref, **kw):
    """``out_ref`` is the step's (QT, n_sub * SCAN_ROWS) int32 output tile
    of Hamming distances (the rest: ``_scan_row_blocks``)."""
    def emit(i, _):
        block = pl.ds(pl.multiple_of(i * SCAN_ROWS, SCAN_ROWS), SCAN_ROWS)
        for q in range(q_ref.shape[0]):
            out_ref[q:q + 1, block] = row_ref[q]

    _scan_row_blocks(start_ref, q_ref, r_ref, qb_ref, rt_ref, row_ref, emit,
                     **kw)


def _insert_ranked(ranked, s, a):
    """Insert candidates (sim ``s``, column ``a``) into per-lane ranked
    lists ``[(sim, col)] * k``, best first. Only a strictly greater sim
    goes ahead of an entry: a lane's candidates arrive in ascending column
    order, so on ties the lowest column stays first."""
    out, moved = [], None
    for ls, la in ranked:
        take = s > ls if moved is None else moved | (s > ls)
        out.append((lax.select(take, s, ls), lax.select(take, a, la)))
        s, a, moved = lax.select(take, ls, s), lax.select(take, la, a), take
    return out


def _select_ranked(sims, cols, k: int):
    """The k best of per-lane ranked lists (each (QT, 128)), ranked by
    (sim desc, column asc) across the lanes: ((QT, 1) sims, (QT, 1)
    columns) per rank, -1 and -1 where the rank is empty."""
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    out = []
    for _ in range(k):
        best = functools.reduce(lax.max, [
            jnp.max(s, axis=1, keepdims=True) for s in sims])
        pick = functools.reduce(lax.min, [
            jnp.min(jnp.where(s == best, c, big), axis=1, keepdims=True)
            for s, c in zip(sims, cols)])
        out.append((jnp.maximum(best, -1), jnp.where(best >= 0, pick, -1)))
        sims = [jnp.where((s == best) & (c == pick), -2, s)
                for s, c in zip(sims, cols)]
    return out


def scan_best_kernel(start_ref, q_ref, r_ref, qp_ref, qc_ref, qt_ref, rp_ref,
                     rc_ref, std_sim_ref, std_col_ref, open_sim_ref,
                     open_col_ref, qb_ref, rt_ref, row_ref, dist_ref, sim_ref,
                     col_ref, *, dim: int, k: int, open_tol_da: float,
                     pad_pmz: float, **kw):
    """The scan step with the dual-window top-k in VMEM. Per query
    (QT, 1): ``qp_ref`` precursor m/z, ``qc_ref`` charge, ``qt_ref`` the
    standard window's half-width in m/z. ``rp_ref`` / ``rc_ref`` hold the
    step's rows' precursor m/z and charge as (n_sub * 8, 128): row block
    ``b``'s 128-row chunk ``c`` is row ``8 * b + c``. Each block's
    distances are assembled into ``dist_ref`` (QT, SCAN_ROWS) and masked
    128-row chunk by chunk; ``sim_ref`` / ``col_ref`` (2, k, QT, 128) keep
    each window's k best (sim, run column) per lane across the grid. The
    last step ranks them across lanes into the four (QT, k) outputs."""
    nq = q_ref.shape[0]
    first = pl.program_id(0) * kw["n_sub"]
    shape = (nq, 128)
    neg = jnp.full(shape, -1, jnp.int32)

    @pl.when(pl.program_id(0) == 0)
    def _():
        sim_ref[...] = jnp.full(sim_ref.shape, -1, jnp.int32)
        col_ref[...] = jnp.full(col_ref.shape, -1, jnp.int32)

    def lanes(x):
        return lax.broadcast_in_dim(x, shape, (0, 1))

    def emit(i, b):
        for q in range(nq):
            dist_ref[q:q + 1, :] = row_ref[q]
        qp, qc, qt = lanes(qp_ref[...]), lanes(qc_ref[...]), lanes(qt_ref[...])
        rows = pl.ds(pl.multiple_of(b * 8, 8), 8)
        rp8, rc8 = rp_ref[rows, :], rc_ref[rows, :]
        col0 = (first + i) * SCAN_ROWS
        lane = lax.broadcasted_iota(jnp.int32, shape, 1)
        ranked = [[(sim_ref[w, j], col_ref[w, j]) for j in range(k)]
                  for w in range(2)]
        for c in range(SCAN_ROWS // 128):
            rp, rc = lanes(rp8[c:c + 1, :]), lanes(rc8[c:c + 1, :])
            sim = dim - dist_ref[:, c * 128:(c + 1) * 128]
            dpmz = lax.abs(qp - rp)
            valid = (rp < pad_pmz) & (qc == rc)
            col = lane + (col0 + c * 128)
            for w, inside in enumerate((dpmz <= qt, dpmz <= open_tol_da)):
                ranked[w] = _insert_ranked(
                    ranked[w], lax.select(valid & inside, sim, neg), col)
        for w in range(2):
            for j, (s, a) in enumerate(ranked[w]):
                sim_ref[w, j] = s
                col_ref[w, j] = a

    _scan_row_blocks(start_ref, q_ref, r_ref, qb_ref, rt_ref, row_ref, emit,
                     **kw)

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _():
        for w, (s_out, c_out) in enumerate(((std_sim_ref, std_col_ref),
                                            (open_sim_ref, open_col_ref))):
            best = _select_ranked([sim_ref[w, j] for j in range(k)],
                                  [col_ref[w, j] for j in range(k)], k)
            for r, (s, c) in enumerate(best):
                s_out[:, r:r + 1] = s
                c_out[:, r:r + 1] = c


def step_first_block(start_block, first, n_sub: int, lib_blocks: int):
    """Library block where a grid step's rows are read from: the step's
    first block of the run, moved back so that its ``n_sub`` blocks end
    inside the library."""
    return jnp.minimum(start_block + first, lib_blocks - n_sub)


def _scan_grid(q, hvs, n_blocks: int):
    """What both scan kernels' launches share: their static parameters,
    the grid, the query block's and the rows' BlockSpecs, the scratch, and
    the map from a grid step to the library block its rows are read from."""
    nq, w = q.shape
    lib_blocks = hvs.shape[0] // SCAN_ROWS
    n_sub = min(SCAN_BLOCKS_PER_STEP, n_blocks, lib_blocks)

    def first_block(j, start):
        return step_first_block(start[0], j * n_sub, n_sub, lib_blocks)

    kw = dict(n_blocks=n_blocks, n_sub=n_sub, lib_blocks=lib_blocks,
              q_group=math.gcd(SCAN_Q_GROUP, nq))
    specs = [pl.BlockSpec((nq, w), lambda j, start: (0, 0)),
             pl.BlockSpec((pl.Element(n_sub * SCAN_ROWS), pl.Element(w)),
                          lambda j, start: (first_block(j, start)
                                            * SCAN_ROWS, 0))]
    scratch = [pltpu.VMEM((nq, w, 128), jnp.int32),
               pltpu.VMEM((w, SCAN_ROWS), jnp.int32),
               pltpu.VMEM((nq, 1, SCAN_ROWS), jnp.int32)]
    return kw, (-(-n_blocks // n_sub),), specs, scratch, first_block


def scan_tile_pallas(q: jax.Array, hvs: jax.Array, start_block: jax.Array, *,
                     n_blocks: int, interpret: bool = True) -> jax.Array:
    """q (QT, W) uint32 against rows ``[start_block, start_block + n_blocks)
    * SCAN_ROWS`` of ``hvs`` (R, W) uint32 -> (QT, n_blocks * SCAN_ROWS)
    int32 Hamming distances. ``start_block`` is a traced int32 scalar; the
    caller guarantees the rows lie inside ``hvs``, R % SCAN_ROWS == 0 and
    W % 8 == 0."""
    nq = q.shape[0]
    kw, grid, specs, scratch, _ = _scan_grid(q, hvs, n_blocks)
    n_sub = kw["n_sub"]
    out = pl.pallas_call(
        functools.partial(scan_tile_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=specs,
            out_specs=pl.BlockSpec((nq, n_sub * SCAN_ROWS),
                                   lambda j, start: (0, j)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((nq, grid[0] * n_sub * SCAN_ROWS),
                                       jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(start_block.astype(jnp.int32).reshape(1),
      lax.bitcast_convert_type(q, jnp.int32), hvs)
    return out[:, :n_blocks * SCAN_ROWS]


def scan_best_pallas(q, q_pmz, q_charge, q_std_tol, hvs, pmz, charge,
                     start_block, *, n_blocks: int, dim: int, k: int,
                     open_tol_da: float, pad_pmz: float,
                     interpret: bool = True):
    """The dual-window top-k of q (QT, W) uint32 over rows ``[start_block,
    start_block + n_blocks) * SCAN_ROWS`` of ``hvs``, read in place with
    their precursor m/z ``pmz`` (R,) f32 and ``charge`` (R,) int32. Per
    query: ``q_pmz``, ``q_charge`` and ``q_std_tol``, the standard window's
    half-width in m/z. A row is in a query's window where its pmz is below
    ``pad_pmz``, its charge equals the query's and ``|q_pmz - pmz|`` is at
    most ``q_std_tol`` (standard) or ``open_tol_da`` (open).

    Returns (std_sim, std_col, open_sim, open_col), each (QT, k) int32,
    ranked by (sim desc, col asc): sim = dim - hamming, col the row's
    column in the run; -1 and -1 past the window's candidates. Caller
    guarantees as for ``scan_tile_pallas``."""
    nq = q.shape[0]
    kw, grid, specs, scratch, first_block = _scan_grid(q, hvs, n_blocks)
    sub = SCAN_ROWS // 128
    per_query = pl.BlockSpec((nq, 1), lambda j, start: (0, 0))
    per_row = pl.BlockSpec(
        (pl.Element(kw["n_sub"] * sub), pl.Element(128)),
        lambda j, start: (first_block(j, start) * sub, 0))
    winners = pl.BlockSpec((nq, k), lambda j, start: (0, 0))
    return pl.pallas_call(
        functools.partial(scan_best_kernel, dim=dim, k=k,
                          open_tol_da=open_tol_da, pad_pmz=pad_pmz, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=specs + [per_query] * 3 + [per_row] * 2,
            out_specs=[winners] * 4,
            scratch_shapes=scratch + [
                pltpu.VMEM((nq, SCAN_ROWS), jnp.int32),
                pltpu.VMEM((2, k, nq, 128), jnp.int32),
                pltpu.VMEM((2, k, nq, 128), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((nq, k), jnp.int32)] * 4,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(start_block.astype(jnp.int32).reshape(1),
      lax.bitcast_convert_type(q, jnp.int32), hvs,
      q_pmz.reshape(nq, 1), q_charge.reshape(nq, 1),
      q_std_tol.reshape(nq, 1), pmz.reshape(-1, 128),
      charge.reshape(-1, 128))


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
