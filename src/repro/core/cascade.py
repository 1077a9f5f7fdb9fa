"""Cascaded narrow→open OMS identification (HyperOMS-style two stages).

The paper's workload — like the HyperOMS and ANN-Solo baselines — runs
identification as a cascade: a cheap **narrow** pass (open window shrunk to
``narrow_tol_da``, so each query block touches only a couple of reference
blocks) identifies the unmodified spectra first, and only the survivors pay
for the expensive **open** scan over the full ±``open_tol_da`` window. On
the streaming serve engine the same shrinkage prunes at slab granularity:
stage 1's windows (``slab_qblocks``) are tiny, so far fewer slabs stream.

Orchestration lives here; the stages themselves are ordinary searches run
through a caller-supplied ``run_stage(sel, narrow=...)`` closure (the
pipeline wires it to the resident ``oms_search`` or the streaming engine),
which keeps two invariants trivially true and testable:

  * with stage 1 disabled (``CascadeParams.run_stage1=False``) the cascade
    output is bit-identical to a plain ``oms_search`` — stage 2 *is* that
    search, run on every query;
  * every stage-2 result is bit-identical to a pure open search restricted
    to the fall-through queries — stage 2 *is* that restricted search.

FDR is shift-grouped (:func:`repro.core.fdr.fdr_filter_grouped`): the
merged result set mixes a "standard" population (|Δpmz| ≤ narrow tol) with
an "open" (mass-shifted) one, and a pooled competition would let the strong
standard matches absorb the open population's decoys — per-subgroup
q-values keep the cascade's FDR calibrated, as ANN-Solo-style cascades
require. Stage-1 identification itself is a target-decoy filter over the
narrow matches: a query is identified when its rank-0 match is accepted at
``fdr_threshold``. The competition pool is selectable: batch-level (the
offline default — calibrated over the whole corpus being searched) or
**per query** (``CascadeParams.stage1_per_query``, the serve mode — each
query competes only against its own top-k narrow matches, so the decision
is independent of micro-batch composition and coalescing cannot change an
answer).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core.fdr import (FDRResult, fdr_filter, fdr_filter_grouped,
                            fdr_filter_per_query)
from repro.core.search import SearchResult


class CascadeParams(NamedTuple):
    """Static cascade settings (stage SearchParams are planned per stage)."""

    narrow_tol_da: float = 1.0   # stage-1 open window (and the FDR subgroup
    #                              boundary: |Δpmz| ≤ this → "standard")
    fdr_threshold: float = 0.01  # stage-1 identification + final filtering
    run_stage1: bool = True      # False = pure open search via the cascade
    #                              path (must be bit-identical to oms_search)
    stage1_per_query: bool = False  # gate stage 1 per query (serve mode):
    #                              each query's identification depends only
    #                              on its own narrow matches, so coalescing
    #                              micro-batches cannot change any answer


class StageOutput(NamedTuple):
    """Provenance of one cascade stage."""

    query_idx: np.ndarray   # (Qs,) i32 — original query positions searched
    result: SearchResult    # (Qs, k) — this stage's raw matches
    fdr: FDRResult          # stage-level FDR over its open-window matches
    scanned_rows: int       # static comparison-row count this stage paid
    stream_stats: Any       # serve StreamStats when streamed, else None


class CascadeOutput(NamedTuple):
    result: SearchResult       # (Q, k) merged: stage-1 rows where identified,
    #                            stage-2 rows for the fall-through queries
    open_fdr: FDRResult        # shift-grouped FDR over the merged open matches
    std_fdr: FDRResult         # FDR over the merged standard-window matches
    identified_stage1: np.ndarray  # (Q,) bool — accepted at stage 1
    stage1: StageOutput | None
    stage2: StageOutput | None

    @property
    def scanned_rows_total(self) -> int:
        return sum(s.scanned_rows for s in (self.stage1, self.stage2)
                   if s is not None)

    @property
    def scanned_bytes_total(self) -> int | None:
        """Measured packed-HV bytes streamed across both stages, or None on
        the resident path (only the serve engine meters real store reads)."""
        stages = [s for s in (self.stage1, self.stage2) if s is not None]
        if not stages or any(s.stream_stats is None for s in stages):
            return None
        return sum(s.stream_stats.scanned_bytes for s in stages)

    @property
    def fallthrough(self) -> np.ndarray:
        """(Q,) bool — queries that paid for the open scan."""
        return ~self.identified_stage1


# ``run_stage(sel, narrow=...)`` searches the query subset ``sel`` (i32
# positions into the batch) under the narrow or the full open window and
# returns (SearchResult, scanned_rows, stream_stats_or_None).
RunStage = Callable[..., tuple[SearchResult, int, Any]]


def row_match_flags(row, is_decoy_np: np.ndarray, n_rows: int):
    """Host (valid, is_decoy) flags for winner rows (-1 = no match).

    Shared by the cascade's FDR passes and the pipeline's streamed-serve
    FDR so the clip-to-row-0 padding convention lives in exactly one place.
    """
    row_h = np.asarray(row)
    valid = row_h >= 0
    isd = is_decoy_np[np.clip(row_h, 0, n_rows - 1)] & valid
    return valid, isd


def _stage_fdr(result: SearchResult, is_decoy_np, n_rows, threshold, *,
               per_query: bool = False) -> FDRResult:
    valid, isd = row_match_flags(result.open_row, is_decoy_np, n_rows)
    filt = fdr_filter_per_query if per_query else fdr_filter
    return filt(jnp.asarray(np.asarray(result.open_sim)).astype(jnp.float32),
                jnp.asarray(isd), jnp.asarray(valid),
                threshold=threshold)


def cascade_search(run_stage: RunStage, q_pmz_np: np.ndarray, *, top_k: int,
                   row_pmz: np.ndarray, row_is_decoy: np.ndarray, n_rows: int,
                   params: CascadeParams) -> CascadeOutput:
    """Run the two-stage cascade over one query batch.

    ``q_pmz_np`` is the host precursor-mass array (grouping needs it);
    ``row_pmz``/``row_is_decoy`` are the library's padded-row sidecars (host
    numpy — the device never sees library-sized arrays here, matching the
    streamed serve path's discipline).
    """
    if not params.narrow_tol_da > 0.0:
        raise ValueError(
            f"narrow_tol_da must be > 0, got {params.narrow_tol_da!r}")
    Q = int(np.asarray(q_pmz_np).shape[0])
    if Q == 0:
        empty = SearchResult(*(jnp.full((0, top_k), -1, jnp.int32),) * 6)
        z = jnp.zeros((0, top_k))
        no_fdr = FDRResult(z.astype(bool), z.astype(jnp.float32),
                           jnp.int32(0))
        return CascadeOutput(empty, no_fdr, no_fdr, np.zeros((0,), bool),
                             None, None)

    identified = np.zeros((Q,), bool)
    stage1 = None
    if params.run_stage1:
        all_idx = np.arange(Q, dtype=np.int32)
        res1, scanned1, stats1 = run_stage(all_idx, narrow=True)
        fdr1 = _stage_fdr(res1, row_is_decoy, n_rows, params.fdr_threshold,
                          per_query=params.stage1_per_query)
        accept1 = np.asarray(fdr1.accept)
        # A query is identified at stage 1 when its best (rank-0) narrow
        # match clears the FDR threshold; everyone else falls through.
        identified = accept1[:, 0] if accept1.ndim == 2 else accept1
        stage1 = StageOutput(all_idx, res1, fdr1, scanned1, stats1)

    fall_idx = np.flatnonzero(~identified).astype(np.int32)
    stage2 = None
    if fall_idx.size:
        res2, scanned2, stats2 = run_stage(fall_idx, narrow=False)
        fdr2 = _stage_fdr(res2, row_is_decoy, n_rows, params.fdr_threshold)
        stage2 = StageOutput(fall_idx, res2, fdr2, scanned2, stats2)

    # ---- merge: identified queries keep their stage-1 rows, fall-through
    # queries get their stage-2 rows scattered back into batch order. All
    # SearchResult fields are int32, so the host round-trip is lossless.
    merged = {}
    for f in SearchResult._fields:
        if stage1 is not None:
            base = np.array(np.asarray(getattr(stage1.result, f)))
        else:
            base = np.full((Q, top_k), -1, np.int32)
        if stage2 is not None:
            base[fall_idx] = np.asarray(getattr(stage2.result, f))
        merged[f] = jnp.asarray(base)
    result = SearchResult(**merged)

    # ---- shift-grouped FDR over the merged match lists: the subgroup of a
    # match is decided by its OWN precursor shift (|q_pmz - row_pmz| vs the
    # narrow tol), not by which stage produced it.
    def _grouped(row, sim):
        valid, isd = row_match_flags(row, row_is_decoy, n_rows)
        row_h = np.clip(np.asarray(row), 0, n_rows - 1)
        dpmz = np.abs(np.asarray(q_pmz_np, np.float32)[:, None]
                      - row_pmz[row_h])
        in_narrow = valid & (dpmz <= params.narrow_tol_da)
        return fdr_filter_grouped(
            jnp.asarray(np.asarray(sim)).astype(jnp.float32),
            jnp.asarray(isd), jnp.asarray(valid), jnp.asarray(in_narrow),
            threshold=params.fdr_threshold)

    def _plain(row, sim):
        valid, isd = row_match_flags(row, row_is_decoy, n_rows)
        return fdr_filter(jnp.asarray(np.asarray(sim)).astype(jnp.float32),
                          jnp.asarray(isd), jnp.asarray(valid),
                          threshold=params.fdr_threshold)

    return CascadeOutput(
        result=result,
        open_fdr=_grouped(result.open_row, result.open_sim),
        # standard-window matches are all |Δpmz| ≤ ppm ⊂ narrow: one group.
        std_fdr=_plain(result.std_row, result.std_sim),
        identified_stage1=identified,
        stage1=stage1, stage2=stage2)
