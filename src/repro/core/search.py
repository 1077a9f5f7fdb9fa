"""Blocked dual-window OMS search (paper §II-B orchestrator + §II-C kernel).

Semantics (paper-faithful):
  * Queries are processed in blocks of ``q_block`` (the paper's Q_BLOCK).
  * References stream block-by-block (MAX_R rows each); the orchestrator only
    feeds blocks whose [min_pmz, max_pmz] intersects the query block's
    precursor window (standard 20 ppm / open ±tol Da).
  * Per (query, reference) pair the score is Hamming similarity
    ``sim = Dhv - hamming`` on binary HVs; a fused ``find_max_score`` keeps
    TWO ranked winner lists per query — one under the standard-search ppm
    window and one under the open-search Da window — exactly the two result
    sets the paper's kernel emits, generalised to top-k.

Backends: dispatch goes through the registry in :mod:`repro.core.backends`.
``fused`` backends (the Pallas §II-C kernel and its XLA fallback) consume
the PMZ/charge windows and return ranked running winners directly, never
materialising the (Qb, Rk) similarity matrix — the paper's single-pass
streaming kernel. So does a ``matrix`` backend's in-place scan step (the
``vpu`` scan kernel on TPU), which reads the rows and their windows where
they lie and keeps the winners in VMEM. Elsewhere a ``matrix`` backend
(vpu / mxu / kernel_vpu / kernel_mxu) returns the (Qb, Rk) Hamming tile and
the orchestrator reduces it here (``_find_topk_dual``).

Top-k: ``SearchParams.top_k`` (static, default 1) selects how many winners
per query and window are kept. All :class:`SearchResult` arrays are
(Q, top_k)-shaped, ranked by (similarity desc, library row asc) — ties
resolve to the first global maximum, so rank 0 at ``top_k=1`` is bit-exact
with the historical best-1 search. Ranks past the number of in-window
candidates report idx/sim = -1.

JIT strategy: queries and references are both PMZ-sorted (per charge), so a
query block's candidate references are a *contiguous* run of blocks. We
``searchsorted`` the start block and scan a static cap of ``k_blocks`` blocks
(dynamic-sliced, edge-masked). ``k_blocks`` is chosen by the host-side
orchestrator (`plan_search`) from the DB's PMZ density — the analogue of the
paper's DRAM-level block planning. Exhaustive mode (= the HyperOMS baseline)
is the same loop with ``start = 0`` and ``k_blocks = n_blocks``.

The host-side query padding plan (charge groups padded to ``q_block``)
depends only on (q_block, per-charge counts), so it is memoized — repeated
serving batches with the same charge histogram skip the host round-trip
entirely; callers that already hold numpy pmz/charge can pass them via
``q_pmz_np``/``q_charge_np`` to avoid any device sync.
"""
from __future__ import annotations

import functools
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backends as backends_mod
from repro.core.blocking import PAD_PMZ, ReferenceDB
from repro.kernels.topk import select_topk as _select_topk
from repro.obs.metrics import Metrics
from repro.obs.trace import span

# Program counters of the resident search and the slab steps:
# ``lowering_pallas`` / ``lowering_xla`` count them by how their Hamming
# tiles were computed, ``epilogue_kernel`` / ``epilogue_xla`` by where their
# dual-window winners were chosen (``scan_path``).
METRICS = Metrics()

# Charge multiplier for building monotonic (charge, pmz) sort keys. PMZ values
# are clipped below this, so keys from different charges never interleave.
# Keys stay < ~2^16 where f32 spacing (<0.004) is far finer than a block span.
_CHARGE_KEY = 8192.0


class SearchParams(NamedTuple):
    ppm_tol: float = 20.0          # standard-search window, parts-per-million
    open_tol_da: float = 75.0      # open-search window, Daltons
    q_block: int = 16              # queries per kernel iteration (paper Q_BLOCK)
    k_blocks: int = 8              # static cap of ref blocks scanned per q-block
    min_sim: int = 0               # matches below this similarity report idx=-1
    backend: str = "vpu"           # any name in repro.core.backends.names()
    exhaustive: bool = False       # True = HyperOMS-style full scan (baseline)
    top_k: int = 1                 # ranked winners kept per query and window
    # -- dimension cascade (FeNOMS-style prefix-word pruning) ---------------
    prefix_words: int = 0          # stage-A packed words (0 = full-width scan)
    prefix_margin: int = -1        # survivor slack in bits; -1 = exact bound
    #                                (dim - 32*prefix_words: bit-identical)
    prefix_seed_da: float = 1.0    # seed-pass precursor window (Da) that
    #                                bootstraps per-query thresholds


class SearchResult(NamedTuple):
    """Per query: top-k standard-window and top-k open-window matches.

    All arrays are (Q, top_k) int32, ranked by (sim desc, row asc); empty
    ranks are -1.
    """

    std_idx: jax.Array     # (Q, k) — original library index, -1 if none
    std_sim: jax.Array     # (Q, k) — Hamming similarity (Dhv - distance)
    open_idx: jax.Array    # (Q, k)
    open_sim: jax.Array    # (Q, k)
    std_row: jax.Array     # (Q, k) — row in the sorted/padded DB (decoy lookup)
    open_row: jax.Array    # (Q, k)


# ---------------------------------------------------------------------------
# Top-k reduction (matrix backends) — selection itself lives in
# repro.kernels.topk, shared bit-exactly with the fused Pallas kernel.
# ---------------------------------------------------------------------------


def _find_topk_dual(sims, dpmz, q_pmz, q_charge, r_charge, r_pmz,
                    p: SearchParams):
    """Dual-window top-k find_max_score over one (Qb, Rk) tile.

    Returns per-query (std_sim, std_arg, open_sim, open_arg), each
    (Qb, top_k) with arg = column in the tile or -1.
    """
    valid = (r_pmz[None, :] < PAD_PMZ) & (q_charge[:, None] == r_charge[None, :])
    std_mask = valid & (dpmz <= q_pmz[:, None] * (p.ppm_tol * 1e-6))
    open_mask = valid & (dpmz <= p.open_tol_da)

    neg = jnp.int32(-1)
    std_s, std_a = _select_topk(jnp.where(std_mask, sims, neg), p.top_k)
    open_s, open_a = _select_topk(jnp.where(open_mask, sims, neg), p.top_k)
    return std_s, std_a, open_s, open_a


# ---------------------------------------------------------------------------
# Core blocked search
# ---------------------------------------------------------------------------


def scan_path(db: ReferenceDB, p: SearchParams) -> dict[str, str]:
    """How a search's scan runs, as span attributes, and counted in
    ``METRICS`` (``<attribute>_<value>``) once per call: ``lowering`` is
    ``"pallas"`` where a Pallas kernel computes the Hamming tiles, else
    ``"xla"``; ``epilogue`` is ``"kernel"`` where a kernel chooses the
    dual-window winners, else ``"xla"`` (``_find_topk_dual`` on the tile)."""
    if p.prefix_words:
        pallas, in_kernel = backends_mod.tile_backend(p.backend).pallas, False
    else:
        be = backends_mod.get(p.backend)
        in_place = be.scans_in_place(db.max_r, db.n_words)
        pallas = be.pallas or in_place
        in_kernel = in_place or (be.kind == backends_mod.FUSED and be.pallas)
    path = {"lowering": "pallas" if pallas else "xla",
            "epilogue": "kernel" if in_kernel else "xla"}
    for name, value in path.items():
        METRICS.counter(f"{name}_{value}").inc()
    return path


def _rows(db: ReferenceDB, start_row, rk: int):
    return jax.lax.dynamic_slice(db.hvs, (start_row, 0), (rk, db.n_words))


def _block_body(db: ReferenceDB, dim: int, p: SearchParams,
                q_hvs, q_pmz, q_charge, start_row):
    """Scan k_blocks*max_r contiguous reference rows for one query block."""
    rk = (p.k_blocks if not p.exhaustive else db.n_blocks) * db.max_r
    windows = dict(dim=dim, ppm_tol=p.ppm_tol, open_tol_da=p.open_tol_da,
                   k=p.top_k)

    be = backends_mod.get(p.backend)
    if be.scans_in_place(db.max_r, db.n_words):
        # The kernel reads the rows and their windows where they lie, and
        # returns the winners: no (rk, W) slice, no (Qb, rk) tile.
        std_b, std_a, open_b, open_a = be.scan(
            q_hvs, q_pmz, q_charge, db.hvs, db.pmz, db.charge, start_row,
            rk, **windows)
    else:
        r_pmz = jax.lax.dynamic_slice(db.pmz, (start_row,), (rk,))
        r_charge = jax.lax.dynamic_slice(db.charge, (start_row,), (rk,))
        if be.kind == backends_mod.FUSED:
            std_b, std_a, open_b, open_a = be.fn(
                q_hvs, _rows(db, start_row, rk), q_pmz, r_pmz, q_charge,
                r_charge, **windows)
        else:
            sims = dim - be.fn(q_hvs, _rows(db, start_row, rk), dim)
            dpmz = jnp.abs(q_pmz[:, None] - r_pmz[None, :])
            std_b, std_a, open_b, open_a = _find_topk_dual(
                sims, dpmz, q_pmz, q_charge, r_charge, r_pmz, p)

    std_row = jnp.where(std_b >= 0, start_row + std_a, -1)
    open_row = jnp.where(open_b >= 0, start_row + open_a, -1)
    return std_b, std_row, open_b, open_row


def _block_keys(db: ReferenceDB):
    """Monotonic block sort keys (block_max is per-charge ascending; adding a
    large per-charge offset makes the concatenation globally ascending)."""
    return jnp.where(
        jnp.isfinite(db.block_max),
        jnp.clip(db.block_max, 0.0, _CHARGE_KEY - 1.0) + db.block_charge * _CHARGE_KEY,
        db.block_charge * _CHARGE_KEY + (_CHARGE_KEY - 1.0),
    )


def _qblock_start_row(db: ReferenceDB, p: SearchParams, bkey, qp, qc):
    """First scanned row for one query block (searchsorted start pruning)."""
    if p.exhaustive:
        return jnp.int32(0)
    # Lowest key any query in this block can match: pmz - open_tol.
    lo = jnp.min(jnp.clip(qp - p.open_tol_da, 0.0, _CHARGE_KEY - 1.0)
                 + qc * _CHARGE_KEY)
    start_blk = jnp.searchsorted(bkey, lo)
    # one-block guard against key rounding at block boundaries
    start_blk = jnp.clip(start_blk - 1, 0, max(db.n_blocks - p.k_blocks, 0))
    return (start_blk * db.max_r).astype(jnp.int32)


@partial(jax.jit, static_argnames=("params", "dim"))
def _search_sorted_padded(db: ReferenceDB, q_hvs, q_pmz, q_charge,
                          *, params: SearchParams, dim: int):
    """Search with queries already (charge, pmz)-sorted and padded to q_block.

    Returns four (Qp, top_k) arrays: std_sim, std_row, open_sim, open_row.
    """
    p = params
    if p.top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {p.top_k}")
    QB = p.q_block
    nqb = q_hvs.shape[0] // QB
    bkey = _block_keys(db)

    def one_qblock(args):
        qh, qp, qc = args
        start_row = _qblock_start_row(db, p, bkey, qp, qc)
        return _block_body(db, dim, p, qh, qp, qc, start_row)

    qs = (q_hvs.reshape(nqb, QB, -1), q_pmz.reshape(nqb, QB), q_charge.reshape(nqb, QB))
    std_b, std_row, open_b, open_row = jax.lax.map(one_qblock, qs)
    K = p.top_k
    return (std_b.reshape(-1, K), std_row.reshape(-1, K),
            open_b.reshape(-1, K), open_row.reshape(-1, K))


# ---------------------------------------------------------------------------
# Dimension cascade (FeNOMS direction): prefix-word prune + full rescore
# ---------------------------------------------------------------------------
#
# Stage A scans every in-window candidate over only the first
# ``prefix_words`` (P) packed words: ``ham_p`` mismatches over 32*P bits.
# The remaining ``rest = dim - 32*P`` bits can add at most ``rest``
# mismatches, so the candidate's FULL similarity is bounded by
#
#     ub = (32*P - ham_p) + rest = dim - ham_p >= full_sim.
#
# A candidate row survives iff ``ub >= T`` for a per-(query, window)
# threshold T. In EXACT mode T is the k-th best full-width similarity over
# any SUBSET of that query's in-window candidates (the seed pass below, then
# tightened by the running winners): subset k-th <= true k-th, so every true
# top-k row has full_sim >= T, hence ub >= T — no true winner is ever
# pruned, and ties survive too (>=). Stage B gathers ONLY survivors at full
# width and rescores them with the standard masked top-k selection, which
# makes the final result bit-identical to the full-width scan.
#
# ``prefix_margin >= 0`` replaces the exact slack with a caller-chosen one:
# survive iff prefix_sim + margin >= T. margin == rest is the exact bound;
# smaller margins prune harder but may drop true winners (inexact, fast).

_NEG_THRESHOLD = -(1 << 30)     # "no threshold yet": everything in-window survives


def prefix_margin_bits(params: SearchParams, dim: int) -> int:
    """Effective stage-A slack in bits (the exact bound unless overridden)."""
    rest = dim - 32 * params.prefix_words
    if params.prefix_margin < 0:
        return rest
    return min(params.prefix_margin, rest)


@partial(jax.jit, static_argnames=("params", "dim"))
def _prefix_flags(db: ReferenceDB, q_hvs_p, q_pmz, q_charge, thr_std,
                  thr_open, *, params: SearchParams, dim: int):
    """Stage A: per-row survivor flags from a prefix-words Hamming scan.

    ``db.hvs`` carries ONLY ``params.prefix_words`` packed words (a prefix
    slab or a column-sliced resident view); pmz/charge/block sidecars are
    the usual full ones. ``thr_std``/``thr_open`` are per padded-query int32
    full-similarity thresholds (``_NEG_THRESHOLD`` where unknown). Returns
    (n_rows,) bool — the OR over queries of the bound-based keep decision.
    """
    p = params
    P = p.prefix_words
    pdim = 32 * P
    margin = prefix_margin_bits(p, dim)
    QB = p.q_block
    nqb = q_hvs_p.shape[0] // QB
    rk = (p.k_blocks if not p.exhaustive else db.n_blocks) * db.max_r
    tile = backends_mod.tile_backend(p.backend).fn
    bkey = _block_keys(db)

    def one_qblock(args):
        qh, qp, qc, ts, to = args
        start_row = _qblock_start_row(db, p, bkey, qp, qc)
        r_hvs = jax.lax.dynamic_slice(db.hvs, (start_row, 0), (rk, P))
        r_pmz = jax.lax.dynamic_slice(db.pmz, (start_row,), (rk,))
        r_charge = jax.lax.dynamic_slice(db.charge, (start_row,), (rk,))
        ham_p = tile(qh, r_hvs, pdim)                      # (QB, rk)
        ub = (pdim - ham_p) + margin                       # best-case full sim
        valid = (r_pmz[None, :] < PAD_PMZ) & (qc[:, None] == r_charge[None, :])
        dpmz = jnp.abs(qp[:, None] - r_pmz[None, :])
        std_m = valid & (dpmz <= qp[:, None] * (p.ppm_tol * 1e-6))
        open_m = valid & (dpmz <= p.open_tol_da)
        keep = (std_m & (ub >= ts[:, None])) | (open_m & (ub >= to[:, None]))
        return keep.any(axis=0), start_row

    qs = (q_hvs_p.reshape(nqb, QB, -1), q_pmz.reshape(nqb, QB),
          q_charge.reshape(nqb, QB), thr_std.reshape(nqb, QB),
          thr_open.reshape(nqb, QB))
    keep, starts = jax.lax.map(one_qblock, qs)             # (nqb, rk), (nqb,)
    n = db.pmz.shape[0]
    idx = (starts[:, None] + jnp.arange(rk, dtype=jnp.int32)[None, :])
    flags = jnp.zeros((n,), jnp.int32).at[idx.reshape(-1)].max(
        keep.reshape(-1).astype(jnp.int32))
    return flags > 0


@partial(jax.jit, static_argnames=("params", "dim"))
def _rescore_rows_padded(r_hvs, r_rows, r_pmz, r_charge, q_hvs, q_pmz,
                         q_charge, *, params: SearchParams, dim: int):
    """Stage B / seed pass: exact dual-window top-k over a gathered row set.

    ``r_*`` are (S,) padded candidate arrays — global padded-DB rows in
    ASCENDING order (selection ties resolve to the lowest row, matching the
    full scan), padding entries carrying ``r_pmz == PAD_PMZ`` /
    ``r_rows == -1``. Queries are the sorted/padded layout. Returns four
    (Qp, top_k) arrays: std_sim, std_row, open_sim, open_row — rows GLOBAL.
    """
    p = params
    QB = p.q_block
    nqb = q_hvs.shape[0] // QB
    S = r_rows.shape[0]
    tile = backends_mod.tile_backend(p.backend).fn

    def one_qblock(args):
        qh, qp, qc = args
        ham = tile(qh, r_hvs, dim)
        sims = dim - ham
        dpmz = jnp.abs(qp[:, None] - r_pmz[None, :])
        std_s, std_a, open_s, open_a = _find_topk_dual(
            sims, dpmz, qp, qc, r_charge, r_pmz, p)
        std_row = jnp.where(std_s >= 0,
                            r_rows[jnp.clip(std_a, 0, S - 1)], -1)
        open_row = jnp.where(open_s >= 0,
                             r_rows[jnp.clip(open_a, 0, S - 1)], -1)
        return std_s, std_row, open_s, open_row

    qs = (q_hvs.reshape(nqb, QB, -1), q_pmz.reshape(nqb, QB),
          q_charge.reshape(nqb, QB))
    std_b, std_row, open_b, open_row = jax.lax.map(one_qblock, qs)
    K = p.top_k
    return (std_b.reshape(-1, K), std_row.reshape(-1, K),
            open_b.reshape(-1, K), open_row.reshape(-1, K))


def kth_thresholds(run, k: int):
    """Per-query (thr_std, thr_open) int32 thresholds from (Qp, k) winner
    arrays ``run = (std_sim, std_row, open_sim, open_row)`` — the k-th sim
    where a k-th winner exists, ``_NEG_THRESHOLD`` otherwise. Any exact-
    rescored candidate subset yields a VALID exact-mode threshold (subset
    k-th <= true k-th)."""
    neg = jnp.int32(_NEG_THRESHOLD)
    thr_std = jnp.where(run[1][:, k - 1] >= 0, run[0][:, k - 1], neg)
    thr_open = jnp.where(run[3][:, k - 1] >= 0, run[2][:, k - 1], neg)
    return thr_std, thr_open


def plan_seed_rows(row_pmz: np.ndarray, row_charge: np.ndarray,
                   q_pmz_np: np.ndarray, q_charge_np: np.ndarray,
                   tol_da: float) -> np.ndarray:
    """Host seed plan: ascending padded-DB rows within ``tol_da`` Da (same
    charge) of ANY query precursor — the rows whose exact rescore bootstraps
    the per-query thresholds. Within one charge the layout's real rows are
    globally pmz-ascending, so per charge this is two searchsorteds."""
    n = row_pmz.shape[0]
    mark = np.zeros((n,), bool)
    for c in np.unique(q_charge_np):
        rows_c = np.flatnonzero((row_charge == c) & (row_pmz < np.float32(
            np.finfo(np.float32).max)))
        if rows_c.size == 0:
            continue
        pm = row_pmz[rows_c]
        q = np.sort(q_pmz_np[q_charge_np == c])
        lo = np.searchsorted(q, pm - tol_da, side="left")
        hi = np.searchsorted(q, pm + tol_da, side="right")
        mark[rows_c[hi > lo]] = True
    return np.flatnonzero(mark).astype(np.int64)


def row_bucket(n: int, *, lo: int | None = None) -> int:
    """Power-of-two padding bucket for dynamic candidate-set sizes, so the
    jitted rescore sees a bounded family of static shapes. The floor ``lo``
    defaults to the tuned per-device base (``repro.tune.row_bucket_lo``)."""
    if lo is None:
        from repro import tune
        lo = tune.row_bucket_lo()
    b = lo
    while b < max(n, 1):
        b <<= 1
    return b


def pad_candidate_rows(rows: np.ndarray, bucket: int):
    """(rows_padded, valid) host arrays for a candidate set: rows stay
    ascending, padding gathers row 0 but is masked out via PAD sidecars."""
    S = int(rows.shape[0])
    rows_pad = np.zeros((bucket,), np.int64)
    rows_pad[:S] = rows
    valid = np.zeros((bucket,), bool)
    valid[:S] = True
    return rows_pad, valid


def _prefix_search_padded(db: ReferenceDB, qh, qp, qc, *,
                          params: SearchParams, dim: int,
                          row_pmz_np: np.ndarray, row_charge_np: np.ndarray,
                          qp_np: np.ndarray, qc_np: np.ndarray):
    """Resident two-stage cascade over sorted/padded queries.

    Seed pass (exact thresholds) -> stage-A prefix flags over the whole DB
    -> stage-B exact rescore of survivors. Returns the same four (Qp, k)
    arrays as ``_search_sorted_padded`` — bit-identical in exact mode.
    """
    p = params
    K = p.top_k
    Qp = qh.shape[0]
    neg = jnp.full((Qp,), _NEG_THRESHOLD, jnp.int32)

    def gather_device(rows_np: np.ndarray):
        bucket = row_bucket(rows_np.shape[0])
        rows_pad, valid = pad_candidate_rows(rows_np, bucket)
        rows_j = jnp.asarray(rows_pad.astype(np.int32))
        valid_j = jnp.asarray(valid)
        r_hvs = db.hvs[rows_j]
        r_pmz = jnp.where(valid_j, db.pmz[rows_j], PAD_PMZ)
        r_charge = jnp.where(valid_j, db.charge[rows_j], -1)
        r_rows = jnp.where(valid_j, rows_j, -1)
        return r_hvs, r_rows, r_pmz, r_charge

    seed_rows = plan_seed_rows(row_pmz_np, row_charge_np, qp_np, qc_np,
                               p.prefix_seed_da)
    if seed_rows.size:
        thr_std, thr_open = kth_thresholds(
            _rescore_rows_padded(*gather_device(seed_rows), qh, qp, qc,
                                 params=p, dim=dim), K)
    else:
        thr_std, thr_open = neg, neg

    flags = _prefix_flags(
        ReferenceDB(hvs=db.hvs[:, :p.prefix_words], pmz=db.pmz,
                    charge=db.charge, is_decoy=db.is_decoy,
                    orig_idx=db.orig_idx, block_min=db.block_min,
                    block_max=db.block_max, block_charge=db.block_charge,
                    max_r=db.max_r),
        qh[:, :p.prefix_words], qp, qc, thr_std, thr_open,
        params=p, dim=dim)
    surv = np.flatnonzero(np.asarray(flags))
    if p.prefix_margin >= 0:
        # Margin mode may prune true winners; folding the seed rows back in
        # makes it no worse than the seed pass. Exact mode needs no union
        # (every potential winner is flagged) but extra ascending candidates
        # never change the exact selection, so one code path serves both.
        surv = np.union1d(surv, seed_rows)
    if surv.size == 0:
        z = jnp.full((Qp, K), -1, jnp.int32)
        return z, z, z, z
    return _rescore_rows_padded(*gather_device(surv), qh, qp, qc,
                                params=p, dim=dim)


@functools.lru_cache(maxsize=512)
def _padding_plan(q_block: int, group_sizes: tuple[int, ...]):
    """Row-selection plan for (charge, pmz)-sorted queries.

    Pads each charge group to a ``q_block`` multiple (repeating the last,
    highest-pmz row so the padded block stays in one PMZ neighbourhood) so no
    query block straddles a charge boundary. Depends only on the per-charge
    counts, hence the memoization: repeated serving batches with the same
    charge histogram reuse the plan with zero host work.
    """
    sel_rows, is_real = [], []
    start = 0
    for n in group_sizes:
        g = list(range(start, start + n))
        sel_rows.extend(g)
        is_real.extend([True] * n)
        padn = (-n) % q_block
        sel_rows.extend([g[-1]] * padn)
        is_real.extend([False] * padn)
        start += n
    sel = np.asarray(sel_rows, dtype=np.int32)
    real = np.asarray(is_real, dtype=bool)
    sel.setflags(write=False)
    real.setflags(write=False)
    return sel, real


def validate_search_params(params: SearchParams, n_rows: int | None = None) -> None:
    """Reject invalid static search settings with a clear error.

    ``top_k < 1`` and ``top_k > n_rows`` used to surface as opaque
    gather/shape failures deep inside jit; both entry points (resident
    :func:`oms_search` and the streaming serve engine) call this first.
    """
    if params.top_k < 1:
        raise ValueError(f"SearchParams.top_k must be >= 1, got {params.top_k}")
    if n_rows is not None and params.top_k > n_rows:
        raise ValueError(
            f"SearchParams.top_k={params.top_k} exceeds the reference DB's "
            f"{n_rows} rows — no query can have that many candidates; "
            f"lower top_k or grow the library")
    if params.prefix_words < 0:
        raise ValueError(
            f"SearchParams.prefix_words must be >= 0, got {params.prefix_words}")
    if params.prefix_words and params.prefix_seed_da <= 0.0:
        raise ValueError(
            f"SearchParams.prefix_seed_da must be > 0 when prefix_words is "
            f"set, got {params.prefix_seed_da!r}")


def validate_prefix_words(params: SearchParams, dim: int) -> None:
    """The prefix must leave at least one full-width word of headroom —
    ``prefix_words == n_words`` would be a slower full scan in disguise."""
    n_words = dim // 32
    if params.prefix_words >= n_words:
        raise ValueError(
            f"SearchParams.prefix_words={params.prefix_words} must be < "
            f"n_words={n_words} (dim={dim}); use prefix_words=0 for a "
            f"full-width scan")


def sort_pad_plan(q_pmz: jax.Array, q_charge: jax.Array, q_block: int, *,
                  q_charge_np: np.ndarray | None = None):
    """Composed sort+pad row-selection for a query batch.

    Sorts queries by (charge, pmz) and pads each charge group to a
    ``q_block`` multiple so no query block straddles a charge boundary. The
    plan needs only the per-charge counts (np.unique is ascending, matching
    the device sort key), so it is cached across calls (`_padding_plan`).

    Returns ``(gather, unpad)`` device index arrays: ``x[gather]`` maps raw
    query rows into the sorted/padded layout the blocked scan consumes (one
    gather per array — a single pass over the query HVs), and ``y[unpad]``
    inverts it on the way out (drops padding rows, restores input order).
    Shared by the resident ``oms_search`` and the streaming serve engine so
    both consume literally the same query layout.
    """
    with span("search.sort_pad"):
        Q = q_pmz.shape[0]
        key = jnp.clip(q_pmz, 0.0, _CHARGE_KEY - 1.0) + q_charge * _CHARGE_KEY
        order = jnp.argsort(key)
        qc_np = np.asarray(q_charge if q_charge_np is None else q_charge_np)
        counts = np.unique(qc_np, return_counts=True)[1]
        sel_np, real_np = _padding_plan(q_block,
                                        tuple(int(c) for c in counts))
        gather = order[jnp.asarray(sel_np)]
        keep = jnp.flatnonzero(jnp.asarray(real_np), size=Q)
        unpad = keep[jnp.argsort(order)]
    return gather, unpad


def narrow_search_params(block_meta, q_pmz, q_charge, params: SearchParams, *,
                         narrow_tol_da: float) -> SearchParams:
    """Stage-1 (narrow-window) variant of ``params`` for cascaded search.

    The open window shrinks to ``narrow_tol_da`` and ``k_blocks`` is
    re-planned for that window with the SAME pruning math (`plan_search`)
    the open pass uses — so the narrow scan touches only the handful of
    reference blocks a near-zero precursor shift can reach, which is where
    the cascade's speed win comes from. ``block_meta`` is anything exposing
    the block sidecars (a resident ReferenceDB or a serve StoreLayout).

    ``narrow_tol_da`` must sit strictly inside (0, params.open_tol_da]; it
    should also exceed the widest standard ppm window (default 1 Da vs
    20 ppm * 1800 Da ≈ 0.036 Da) so the narrow scan's block span still
    covers every ppm-window candidate.
    """
    if not 0.0 < narrow_tol_da <= params.open_tol_da:
        raise ValueError(
            f"narrow_tol_da must be in (0, open_tol_da={params.open_tol_da}]"
            f", got {narrow_tol_da!r}")
    k = plan_search(block_meta, np.asarray(q_pmz), np.asarray(q_charge),
                    open_tol_da=narrow_tol_da, q_block=params.q_block)
    return params._replace(open_tol_da=narrow_tol_da, k_blocks=k)


def oms_search(db: ReferenceDB, q_hvs: jax.Array, q_pmz: jax.Array,
               q_charge: jax.Array, params: SearchParams, *, dim: int,
               q_pmz_np: np.ndarray | None = None,
               q_charge_np: np.ndarray | None = None,
               row_pmz_np: np.ndarray | None = None,
               row_charge_np: np.ndarray | None = None) -> SearchResult:
    """Full OMS search: sort queries, run the blocked scan, unsort, map rows
    back to original library indices, apply the min-similarity threshold.

    ``q_pmz_np``/``q_charge_np`` are optional host copies of the query
    precursor arrays; pass them (the pipeline does) to avoid a device->host
    sync when the padding plan is already cached. With
    ``params.prefix_words > 0`` the scan runs as a two-stage dimension
    cascade (prefix-word prune + exact full-width rescore of survivors);
    ``row_pmz_np``/``row_charge_np`` are the matching host copies of the
    padded DB sidecars for its seed pass (pulled from the device if absent).
    """
    validate_search_params(params, db.n_rows)
    if params.prefix_words:
        validate_prefix_words(params, dim)
    gather, unpad = sort_pad_plan(q_pmz, q_charge, params.q_block,
                                  q_charge_np=q_charge_np)
    with span("search.gather"):
        qh = q_hvs[gather]
        qp = q_pmz[gather]
        qc = q_charge[gather]
    # Padding queries keep their charge (so the block is charge-pure) but are
    # discarded on output.

    with span("search.kernel", **scan_path(db, params)):
        if params.prefix_words:
            if row_pmz_np is None:
                row_pmz_np = np.asarray(db.pmz)
            if row_charge_np is None:
                row_charge_np = np.asarray(db.charge)
            if q_pmz_np is None:
                q_pmz_np = np.asarray(q_pmz)
            if q_charge_np is None:
                q_charge_np = np.asarray(q_charge)
            std_b, std_row, open_b, open_row = _prefix_search_padded(
                db, qh, qp, qc, params=params, dim=dim,
                row_pmz_np=row_pmz_np, row_charge_np=row_charge_np,
                qp_np=q_pmz_np, qc_np=q_charge_np)
        else:
            std_b, std_row, open_b, open_row = _search_sorted_padded(
                db, qh, qp, qc, params=params, dim=dim)

    # Drop padding rows, restore original query order.
    def _restore(x):
        return x[unpad]

    def _finalize(best, row):
        ok = (best >= params.min_sim) & (row >= 0)
        idx = jnp.where(ok, db.orig_idx[jnp.clip(row, 0, db.n_rows - 1)], -1)
        ok = ok & (idx >= 0)  # padding rows carry orig_idx == -1
        return jnp.where(ok, idx, -1), jnp.where(ok, best, -1), jnp.where(ok, row, -1)

    with span("search.restore"):
        std_b, std_row = _restore(std_b), _restore(std_row)
        open_b, open_row = _restore(open_b), _restore(open_row)
        std_idx, std_sim, std_row = _finalize(std_b, std_row)
        open_idx, open_sim, open_row = _finalize(open_b, open_row)
    return SearchResult(std_idx, std_sim, open_idx, open_sim, std_row, open_row)


# ---------------------------------------------------------------------------
# Orchestrator planning (host-side, one-time)
# ---------------------------------------------------------------------------


def plan_search(db: ReferenceDB, q_pmz, q_charge, *, open_tol_da: float,
                q_block: int, safety_blocks: int = 2) -> int:
    """Pick the static ``k_blocks`` cap: the max number of contiguous blocks
    any q_block-sized run of (charge, pmz)-sorted queries can touch under the
    open window, plus a guard. This is the paper's DRAM orchestrator planning
    step — done once per (DB, query batch) on host.
    """
    bmin = np.asarray(db.block_min); bmax = np.asarray(db.block_max)
    bch = np.asarray(db.block_charge)
    qp = np.asarray(q_pmz); qc = np.asarray(q_charge)
    Q = len(qp)
    if Q == 0:
        return min(1 + safety_blocks, db.n_blocks)
    order = np.lexsort((qp, qc))
    qp, qc = qp[order], qc[order]

    # Vectorised over (q-block, charge) segments: sorted order makes each
    # segment a contiguous run, so its pmz window is [first - tol, last + tol].
    # Grouping must mirror the runtime layout: each charge group is padded to
    # a q_block multiple (sort_pad_plan), so device q-blocks are aligned to
    # *charge-run-local* offsets, not to the global query index. Grouping on
    # the global index used to chop runs differently than the device does and
    # could understate the worst-case span (k_blocks too small -> silently
    # missed in-window candidates on charge-boundary-straddling batches).
    charge_starts = np.flatnonzero(np.r_[True, np.diff(qc) != 0])
    run_start = np.repeat(charge_starts,
                          np.diff(np.r_[charge_starts, Q]))
    group = (np.arange(Q) - run_start) // q_block
    starts = np.flatnonzero(
        np.r_[True, (np.diff(group) != 0) | (np.diff(qc) != 0)])
    ends = np.r_[starts[1:], Q]               # exclusive
    lo = qp[starts] - open_tol_da
    hi = qp[ends - 1] + open_tol_da
    seg_c = qc[starts]

    # Blocks of one charge are a contiguous run with bmin/bmax both ascending
    # (rows are pmz-sorted), so the hit set is the index interval
    # [first bmax >= lo, last bmin <= hi] — two searchsorteds per charge.
    worst = 1
    for c in np.unique(seg_c):
        blocks = np.flatnonzero(bch == c)
        if len(blocks) == 0:
            continue
        m = seg_c == c
        first = np.searchsorted(bmax[blocks], lo[m], side="left")
        last = np.searchsorted(bmin[blocks], hi[m], side="right") - 1
        spans = (last - first + 1)[first <= last]
        if len(spans):
            worst = max(worst, int(spans.max()))
    return min(worst + safety_blocks, db.n_blocks)


def scanned_rows(db: ReferenceDB, n_queries: int, params: SearchParams) -> int:
    """Static comparison count of a search call (for Fig. 6e-style benchmarks)."""
    nqb = -(-n_queries // params.q_block)
    k = db.n_blocks if params.exhaustive else params.k_blocks
    return nqb * k * db.max_r * params.q_block
