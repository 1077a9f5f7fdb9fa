"""PMZ-sorted, charge-partitioned block layout of the reference DB (paper §II-B).

The paper stores encoded reference HVs on the SmartSSD, cached into DRAM by
charge state, sorted by precursor m/z (PMZ) and arranged in blocks of MAX_R
with [min_pmz, max_pmz] metadata so the orchestrator can stream only blocks
that intersect a query's precursor window.

On TPU the same layout lives in (sharded) HBM: references are sorted by
(charge, pmz), padded to a multiple of ``max_r``, and block metadata is kept
as small host/device arrays. Because both references *and* queries are
PMZ-sorted, each query block's candidate references form a *contiguous* run
of blocks — which is what makes the pruning JIT-static (a fixed cap of
``k_blocks`` dynamic-sliced blocks per query block, masked at the edges).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Iterable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PAD_PMZ = np.float32(np.finfo(np.float32).max)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ReferenceDB:
    """Encoded reference library in search-ready (sorted, blocked) layout."""

    hvs: Any          # (Rp, W) uint32 — packed HVs, sorted by (charge, pmz), padded
    pmz: Any          # (Rp,) f32 — PAD_PMZ on padding rows
    charge: Any       # (Rp,) i32 — -1 on padding rows
    is_decoy: Any     # (Rp,) bool — target/decoy flag for FDR
    orig_idx: Any     # (Rp,) i32 — index into the caller's (unsorted) library; -1 pad
    block_min: Any    # (n_blocks,) f32 — per-block min pmz
    block_max: Any    # (n_blocks,) f32 — per-block max pmz (PAD rows excluded)
    block_charge: Any # (n_blocks,) i32 — charge of the block (blocks never mix charges)
    max_r: int = dataclasses.field(metadata={"static": True}, default=4096)

    # -- pytree plumbing ----------------------------------------------------
    def tree_flatten(self):
        children = (self.hvs, self.pmz, self.charge, self.is_decoy,
                    self.orig_idx, self.block_min, self.block_max,
                    self.block_charge)
        return children, self.max_r

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, max_r=aux)

    # -- convenience ---------------------------------------------------------
    @property
    def n_blocks(self) -> int:
        return self.block_min.shape[0]

    @property
    def n_rows(self) -> int:
        return self.hvs.shape[0]

    @property
    def n_words(self) -> int:
        return self.hvs.shape[-1]


def build_reference_db(
    hvs: jax.Array,        # (R, W) uint32
    pmz: jax.Array,        # (R,) f32
    charge: jax.Array,     # (R,) i32
    is_decoy: jax.Array,   # (R,) bool
    *,
    max_r: int = 4096,
) -> ReferenceDB:
    """Sort by (charge, pmz), pad each charge partition to a block boundary.

    Charge partitioning matters: the paper caches blocks "based on their
    charge states" so a block never straddles charges; we enforce the same by
    padding every charge partition independently to a multiple of ``max_r``.
    Runs on host (numpy) — DB construction is a one-time ingest step.
    """
    hvs_n = np.asarray(hvs)
    pmz_n = np.asarray(pmz, dtype=np.float32)
    charge_n = np.asarray(charge, dtype=np.int32)
    decoy_n = np.asarray(is_decoy, dtype=bool)

    order = np.lexsort((pmz_n, charge_n))
    return _layout_sorted(hvs_n[order], pmz_n[order], charge_n[order],
                          decoy_n[order], order.astype(np.int32), max_r=max_r)


def padded_partition_plan(charge_sorted: np.ndarray,
                          max_r: int) -> tuple[np.ndarray, np.ndarray]:
    """Row-selection plan that pads every charge partition to a ``max_r``
    multiple (paper: blocks never straddle charges).

    Input must be (charge, pmz)-sorted. Returns ``(sel, block_charge)``:
    ``sel`` is (Rp,) int64 source-row indices with -1 on padding rows, and
    ``block_charge`` is the per-block partition charge (Rp/max_r,) int32.
    Shared by the resident layout below and the serve-side
    :class:`repro.serve.StoreLayout`, so both pad identically by
    construction.
    """
    charge_sorted = np.asarray(charge_sorted)
    charges, counts = np.unique(charge_sorted, return_counts=True)
    sel_parts: list[np.ndarray] = []
    b_charge: list[int] = []
    start = 0
    for c, n in zip(charges, counts):
        n = int(n)
        n_pad = (-n) % max_r
        sel_parts.append(np.arange(start, start + n, dtype=np.int64))
        sel_parts.append(np.full((n_pad,), -1, dtype=np.int64))
        b_charge.extend([int(c)] * ((n + n_pad) // max_r))
        start += n
    sel = (np.concatenate(sel_parts) if sel_parts
           else np.zeros((0,), dtype=np.int64))
    return sel, np.asarray(b_charge, dtype=np.int32)


def block_pmz_ranges(pmz_padded: np.ndarray,
                     max_r: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-block [min, max] pmz over real rows (PAD rows excluded);
    (inf, -inf) for all-padding blocks. ``pmz_padded`` length must be a
    ``max_r`` multiple."""
    fmax = np.float32(np.finfo(np.float32).max)
    pb = np.asarray(pmz_padded).reshape(-1, max_r)
    real = pb < fmax
    any_real = real.any(axis=1)
    b_min = np.where(any_real, np.where(real, pb, np.inf).min(axis=1), np.inf)
    b_max = np.where(any_real, np.where(real, pb, -np.inf).max(axis=1), -np.inf)
    return b_min.astype(np.float32), b_max.astype(np.float32)


def _layout_sorted(hvs_n, pmz_n, charge_n, decoy_n, orig_n, *,
                   max_r: int) -> ReferenceDB:
    """Pad (charge, pmz)-sorted rows per charge partition, emit block metadata.

    Inputs must already be sorted by (charge, pmz); ``orig_n`` carries the
    caller's library index per row.
    """
    sel, b_charge = padded_partition_plan(charge_n, max_r)
    pad = sel < 0
    idx = np.where(pad, 0, sel)
    ph = np.ascontiguousarray(hvs_n[idx])
    ph[pad] = 0
    pp = pmz_n[idx].astype(np.float32, copy=True)
    pp[pad] = np.float32(np.finfo(np.float32).max)
    pc = charge_n[idx].astype(np.int32, copy=True)
    pc[pad] = -1
    pd = decoy_n[idx].astype(bool, copy=True)
    pd[pad] = False
    po = orig_n[idx].astype(np.int32, copy=True)
    po[pad] = -1
    b_min, b_max = block_pmz_ranges(pp, max_r)

    return ReferenceDB(
        hvs=jnp.asarray(ph),
        pmz=jnp.asarray(pp),
        charge=jnp.asarray(pc),
        is_decoy=jnp.asarray(pd),
        orig_idx=jnp.asarray(po),
        block_min=jnp.asarray(b_min),
        block_max=jnp.asarray(b_max),
        block_charge=jnp.asarray(b_charge),
        max_r=max_r,
    )


# ---------------------------------------------------------------------------
# Building from (charge, pmz)-sorted runs (store shards / ingest chunks)
# ---------------------------------------------------------------------------


class LibraryRun(NamedTuple):
    """One (charge, pmz)-sorted run of encoded references (a store shard or
    an in-memory ingest chunk). Arrays may be numpy or ``np.memmap``."""

    hvs: Any       # (n, W) uint32 packed HVs
    pmz: Any       # (n,) f32
    charge: Any    # (n,) i32
    is_decoy: Any  # (n,) bool
    orig_idx: Any  # (n,) i32 — caller's library index


def sort_key_offset(max_pmz: float) -> float:
    """Charge multiplier for :func:`composite_sort_key`: any value strictly
    above every pmz keeps the composite lexicographic."""
    return float(np.ceil(max(float(max_pmz), 1.0)) + 1.0)


def composite_sort_key(pmz, charge, *, off: float) -> np.ndarray:
    """Composite float64 (charge, pmz) sort key, ``charge * off + pmz``.

    Lexicographic for non-negative charges and pmz in ``[0, off)`` (both
    hold for real precursor data — validated here); the f64 mantissa keeps
    distinct float32 pmz values distinct at these scales. The single
    definition is shared by the run merge below and the store's shard
    sortedness check — keep them on the same key.
    """
    c = np.asarray(charge, dtype=np.float64)
    p = np.asarray(pmz, dtype=np.float64)
    if len(p) and (p.min() < 0.0 or c.min() < 0.0 or p.max() >= off):
        raise ValueError("composite_sort_key needs 0 <= pmz < off and charge >= 0")
    return c * off + p


def run_sort_keys(runs: Sequence[LibraryRun]) -> list[np.ndarray]:
    """Composite (charge, pmz) sort keys for each run, on a shared offset.
    Also used by the serve-side :class:`repro.serve.StoreLayout`."""
    hi = max((float(np.max(r.pmz)) for r in runs if len(r.pmz)), default=0.0)
    off = sort_key_offset(hi)
    return [composite_sort_key(r.pmz, r.charge, off=off) for r in runs]


_run_sort_keys = run_sort_keys  # historical internal name


def _merge_two(a, b):
    """Stable vectorised merge of two sorted (key, run, row) triples; rows
    of ``a`` (the earlier runs) win ties via the searchsorted sides."""
    ka, ra, wa = a
    kb, rb, wb = b
    pos_a = np.arange(len(ka), dtype=np.int64) + np.searchsorted(kb, ka, side="left")
    pos_b = np.arange(len(kb), dtype=np.int64) + np.searchsorted(ka, kb, side="right")
    n = len(ka) + len(kb)
    k = np.empty(n, dtype=np.float64)
    r = np.empty(n, dtype=np.int32)
    w = np.empty(n, dtype=np.int64)
    k[pos_a] = ka; k[pos_b] = kb
    r[pos_a] = ra; r[pos_b] = rb
    w[pos_a] = wa; w[pos_b] = wb
    return k, r, w


def merge_sorted_runs(keys: Sequence[np.ndarray]) -> tuple[np.ndarray, np.ndarray]:
    """Stable k-way merge of sorted key runs (tournament of two-run merges).

    Returns ``(run_id, row_in_run)`` of the merged order: equal keys keep
    earlier-run-first, earlier-row-first order — exactly what a stable
    ``np.lexsort`` over the runs' concatenation would produce, without ever
    concatenating (runs can be memory-mapped shards). Adjacent pairs merge
    round by round, so total work is O(N log S) over 8-byte keys even for
    stores grown shard-by-shard; the row payload is gathered once afterwards.
    """
    items = [(np.ascontiguousarray(k, dtype=np.float64),
              np.full(len(k), i, dtype=np.int32),
              np.arange(len(k), dtype=np.int64))
             for i, k in enumerate(keys)]
    if not items:
        return (np.zeros(0, np.int32), np.zeros(0, np.int64))
    while len(items) > 1:
        items = [_merge_two(items[j], items[j + 1])
                 if j + 1 < len(items) else items[j]
                 for j in range(0, len(items), 2)]
    _, run_id, row_in_run = items[0]
    return run_id, row_in_run


def build_reference_db_from_runs(runs: Iterable[LibraryRun], *,
                                 max_r: int = 4096) -> ReferenceDB:
    """Build the blocked DB by merging (charge, pmz)-sorted runs.

    Equivalent (bit-identical, including tie order) to
    ``build_reference_db`` over the runs' concatenation, but never performs
    a monolithic lexsort: the global order comes from a stable merge of the
    per-run sorted keys, and each run's payload — possibly a memory-mapped
    store shard — is gathered once, in ascending row order, into the final
    layout.
    """
    runs = [LibraryRun(*(np.asarray(a) if not isinstance(a, np.ndarray) else a
                         for a in r)) for r in runs]
    runs = [r for r in runs if len(r.pmz)]
    if not runs:
        raise ValueError("build_reference_db_from_runs: no rows")
    run_id, row_in_run = merge_sorted_runs(_run_sort_keys(runs))

    R = sum(len(r.pmz) for r in runs)
    W = runs[0].hvs.shape[1]
    hvs_s = np.empty((R, W), dtype=np.uint32)
    pmz_s = np.empty((R,), dtype=np.float32)
    charge_s = np.empty((R,), dtype=np.int32)
    decoy_s = np.empty((R,), dtype=bool)
    orig_s = np.empty((R,), dtype=np.int32)
    # One stable argsort groups output positions by run (rows stay ascending
    # within each group — the merge is stable), so the gather is a single
    # O(N log N) pass instead of S boolean scans of the merged arrays.
    pos = np.argsort(run_id, kind="stable")
    bounds = np.cumsum([0] + [len(r.pmz) for r in runs])
    for i, r in enumerate(runs):
        at = pos[bounds[i]:bounds[i + 1]]
        rows = row_in_run[at]          # ascending: sequential shard reads
        hvs_s[at] = r.hvs[rows]
        pmz_s[at] = r.pmz[rows]
        charge_s[at] = r.charge[rows]
        decoy_s[at] = r.is_decoy[rows]
        orig_s[at] = r.orig_idx[rows]
    return _layout_sorted(hvs_s, pmz_s, charge_s, decoy_s, orig_s, max_r=max_r)


def shard_reference_db(db: ReferenceDB, n_shards: int) -> ReferenceDB:
    """Pad the block dimension so the DB splits evenly into ``n_shards``
    contiguous slabs (each shard = a run of whole blocks). Used by the
    sharded search: shard s owns blocks [s*bps, (s+1)*bps).
    """
    nb = db.n_blocks
    nb_pad = (-nb) % n_shards
    if nb_pad == 0:
        return db
    W = db.n_words
    pad_rows = nb_pad * db.max_r
    return ReferenceDB(
        hvs=jnp.concatenate([db.hvs, jnp.zeros((pad_rows, W), db.hvs.dtype)]),
        pmz=jnp.concatenate([db.pmz, jnp.full((pad_rows,), PAD_PMZ)]),
        charge=jnp.concatenate([db.charge, jnp.full((pad_rows,), -1, jnp.int32)]),
        is_decoy=jnp.concatenate([db.is_decoy, jnp.zeros((pad_rows,), bool)]),
        orig_idx=jnp.concatenate([db.orig_idx, jnp.full((pad_rows,), -1, jnp.int32)]),
        block_min=jnp.concatenate([db.block_min, jnp.full((nb_pad,), jnp.inf)]),
        block_max=jnp.concatenate([db.block_max, jnp.full((nb_pad,), -jnp.inf)]),
        block_charge=jnp.concatenate([db.block_charge, jnp.full((nb_pad,), -1, jnp.int32)]),
        max_r=db.max_r,
    )


def candidate_block_stats(db: ReferenceDB, q_pmz: np.ndarray, q_charge: np.ndarray,
                          tol_da: float) -> dict:
    """Host-side orchestrator statistics: how many reference rows would be
    scanned under block pruning vs exhaustively (the paper's 5.5x comparison-
    reduction effect, Fig. 6e). Used by benchmarks, not the hot path.
    """
    bmin = np.asarray(db.block_min); bmax = np.asarray(db.block_max)
    bch = np.asarray(db.block_charge)
    q_pmz = np.asarray(q_pmz); q_charge = np.asarray(q_charge)
    # Vectorised over (query, block); chunked over queries so the boolean
    # intermediate stays ~a few MiB at any Q.
    n_blocks = len(bmin)
    chunk = max(1, (1 << 22) // max(n_blocks, 1))
    total = 0
    for s in range(0, len(q_pmz), chunk):
        qp = q_pmz[s:s + chunk, None]
        qc = q_charge[s:s + chunk, None]
        hit = ((bch[None, :] == qc) & (bmax[None, :] >= qp - tol_da)
               & (bmin[None, :] <= qp + tol_da))
        total += int(hit.sum())
    return {
        "scanned_rows": total * db.max_r,
        "exhaustive_rows": len(q_pmz) * db.n_rows,
        "reduction": (len(q_pmz) * db.n_rows) / max(total * db.max_r, 1),
    }
