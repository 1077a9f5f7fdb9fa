"""Streaming shard-search executor: bounded-memory slab scans.

Runs the blocked dual-window OMS scan over a library one fixed-size slab at
a time. Device memory holds the coalesced query batch, at most TWO slabs
(the one being searched plus the one being uploaded), and the (Q, top_k)
running winners; it never holds the library. A background thread gathers
the next ``PREFETCH_SLABS`` slabs from the mmapped shards into host memory
while the device searches, so a slow read borrows from the slack of the
reads before it. That decouples servable library size from accelerator memory — the
paper's near-storage streaming, with the slab stream standing in for the
SmartSSD-to-kernel DMA.

Bit-identity with the resident ``oms_search`` at ANY slab size:

  * queries go through the same ``sort_pad_plan`` layout;
  * every slab is a contiguous run of whole blocks of the SAME padded
    global layout (see `slabs.py`), searched by the same jitted
    ``_search_sorted_padded`` with ``k_blocks`` capped to the slab — each
    query block's scan covers a superset of its in-window candidates in the
    slab, and masked selection keeps only in-window ones, exactly as the
    resident scan does;
  * in each slab only the query blocks whose open windows meet it are
    scanned (``slab_qblocks``, padded to a ``qblock_bucket`` size): any
    other q-block's results from that slab are empty;
  * per-slab winners, offset into the global row space, fold into those
    q-blocks' rows of the running (Q, k) best with ``merge_topk`` in
    ascending slab order — the same tie-stable (sim desc, row asc)
    discipline as the mesh-shard merge (`collectives._merge_best`), so on
    score ties the lower global row keeps winning;
  * slabs no query's open window touches are skipped (they cannot hold an
    in-window candidate).

With ``devices=[d0, d1, ...]`` the slab stream is dealt round-robin across
devices (the per-mesh-slab analogue of the paper's multi-SmartSSD scale-
out); async dispatch overlaps their scans, each device keeps the running
best of its own slabs, and the devices' winners merge on ``d0`` by (sim
desc, row asc).

Live library growth: :meth:`StreamingEngine.reload` re-plans the layout and
slab plan over a grown (append-only) store and swaps them in atomically.
A ``search_encoded`` call snapshots (layout, plan) once at entry, so an
in-flight scan finishes on the layout it started with — the old mmapped
shards stay valid because shard files are never rewritten — while the next
call sees the grown library, bit-identical to a cold start on it.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.registry import contract, declare
from repro.obs.trace import span
from repro.core.search import (SearchParams, SearchResult,
                               _NEG_THRESHOLD, _prefix_flags,
                               _rescore_rows_padded, _search_sorted_padded,
                               kth_thresholds, pad_candidate_rows,
                               plan_seed_rows, row_bucket, scan_path,
                               sort_pad_plan, validate_prefix_words,
                               validate_search_params)
from repro.kernels.topk import merge_topk, select_topk
from repro.serve.slabs import (SlabPlan, StoreLayout, plan_slabs,
                               qblock_bucket, slab_arrays, slab_qblocks)


# Slabs the prefetch thread reads ahead of the one being searched (host
# buffers; the device still holds at most two slabs).
PREFETCH_SLABS = 2


class StreamStats(NamedTuple):
    """Per-call scan accounting (exposed for logs/benchmarks).

    ``scanned_rows`` counts row-reads from the store shards (a survivor row
    re-read at full width counts again); ``scanned_bytes`` is the matching
    packed-HV byte count — prefix-stage rows contribute only their
    ``prefix_words * 4`` bytes, which is where the dimension cascade's
    bandwidth saving shows up. ``scanned_pairs`` counts the (query, row)
    pairs the device compares: per slab step, its q-blocks (selected and
    bucket padding) x ``q_block`` x the ``k_blocks * max_r`` rows each
    scans; on the prefix path, the prefix-width pairs of each slab's
    q-blocks, counted the same way, plus the full-width pairs of each
    rescore (its queries x its padded candidate rows).
    """

    n_slabs: int            # slabs in the plan
    n_scanned: int          # slabs actually streamed for this batch
    slab_rows: int          # rows per slab (the device-memory bound)
    scanned_rows: int = 0   # store row-reads (seed + scan + rescore)
    scanned_bytes: int = 0  # packed-HV bytes those reads pulled
    scanned_pairs: int = 0  # (query, row) pairs compared on the device


@dataclasses.dataclass
class TotalStats:
    """Cumulative scan accounting across ``search_encoded`` calls.

    ``last_stats`` is clobbered per call; this accumulates, so the serve
    loop's end-of-session summary and a benchmark's per-phase deltas can
    both read totals without stepping on each other. ``StreamingEngine.
    reset_stats()`` zeroes it (and clears ``last_stats``)."""

    n_scans: int = 0         # search_encoded calls that reached the slab loop
    slabs_scanned: int = 0   # slabs streamed, summed over calls
    scanned_rows: int = 0    # store row-reads, summed
    scanned_bytes: int = 0   # packed-HV bytes read, summed
    scanned_pairs: int = 0   # (query, row) pairs compared, summed

    def add(self, st: StreamStats) -> None:
        self.n_scans += 1
        self.slabs_scanned += st.n_scanned
        self.scanned_rows += st.scanned_rows
        self.scanned_bytes += st.scanned_bytes
        self.scanned_pairs += st.scanned_pairs


# The slab step is the streaming engine's entire device program (named for
# the blocked scan it runs, so a device trace shows it as a scan). Its
# contract is the engine's reason to exist: device bytes are determined by
# the SLAB (q_block * slab_rows * W words of xor tensor at worst), never by
# the library. `oms.py analyze` traces the step per search backend and
# checks these (see repro.analysis.runner).
@contract("serve:slab_step", "peak_intermediate", "no_host_transfer",
          "dtype_stability",
          bound=lambda c: (max(c["q_block"], 32)
                           * c["slab_rows"] * c["n_words"] * 4),
          note="slab-determined cap: worst backend per slab — vpu's "
               "(Qb, slab_rows, W) xor tensor or mxu's 32-lane "
               "(slab_rows, D) unpack; independent of library size")
@partial(jax.jit, static_argnames=("params", "dim", "n_qb"))
def _search_sorted_padded_slab(run, db, q_hvs, q_pmz, q_charge, q0, offset,
                               *, params: SearchParams, dim: int, n_qb: int):
    """One slab: scan q-blocks ``[q0, q0 + n_qb)`` of the sorted, padded
    queries over the slab ``db``, map the winners' slab-local rows into the
    global row space (``+ offset``) and fold them into those queries' rows
    of the running best ``run``. ``run`` holds earlier (lower-row) slabs, so
    it wins score ties — the merge_topk contract."""
    lo, n = q0 * params.q_block, n_qb * params.q_block
    std_b, std_row, open_b, open_row = _search_sorted_padded(
        db, *(jax.lax.dynamic_slice_in_dim(x, lo, n)
              for x in (q_hvs, q_pmz, q_charge)), params=params, dim=dim)
    part = (std_b, jnp.where(std_row >= 0, std_row + offset, -1),
            open_b, jnp.where(open_row >= 0, open_row + offset, -1))
    return _fold_rows(run, part, lo, params.top_k)


def _fold_rows(run, part, lo, k: int):
    """Fold ``part``, the winners of queries ``[lo, lo + len(part))``, into
    those rows of the running best ``run``, which wins score ties (it
    holds lower rows: the merge_topk contract)."""
    n = part[0].shape[0]
    merged = _merge_partials(
        tuple(jax.lax.dynamic_slice_in_dim(x, lo, n) for x in run), part, k)
    return tuple(jax.lax.dynamic_update_slice_in_dim(x, m, lo, 0)
                 for x, m in zip(run, merged))


@partial(jax.jit, static_argnames=("params", "dim", "n_qb"))
def _rescore_slab(run, r_hvs, r_rows, r_pmz, r_charge, q_hvs, q_pmz,
                  q_charge, q0, *, params: SearchParams, dim: int,
                  n_qb: int):
    """Stage B of the dimension cascade for one slab: exact rescore of its
    survivor rows against q-blocks ``[q0, q0 + n_qb)`` only (no other
    q-block has an in-window row in the slab), folded into those queries'
    rows of the running best ``run``."""
    lo, n = q0 * params.q_block, n_qb * params.q_block
    part = _rescore_rows_padded(
        r_hvs, r_rows, r_pmz, r_charge,
        *(jax.lax.dynamic_slice_in_dim(x, lo, n)
          for x in (q_hvs, q_pmz, q_charge)), params=params, dim=dim)
    return _fold_rows(run, part, lo, params.top_k)


@partial(jax.jit, static_argnames=("params", "dim", "n_qb"))
def _prefix_flags_slab(db, q_hvs_p, q_pmz, q_charge, thr_std, thr_open, q0,
                       *, params: SearchParams, dim: int, n_qb: int):
    """Stage A of the dimension cascade over one prefix slab, for q-blocks
    ``[q0, q0 + n_qb)`` only: the slab's survivor flags. A q-block outside
    the range has no in-window row in the slab, so it keeps none."""
    lo, n = q0 * params.q_block, n_qb * params.q_block
    return _prefix_flags(
        db, *(jax.lax.dynamic_slice_in_dim(x, lo, n)
              for x in (q_hvs_p, q_pmz, q_charge, thr_std, thr_open)),
        params=params, dim=dim)


def _qblock_range(first, stop, s: int, nqb: int) -> tuple[int, int]:
    """``(q0, n_qb)`` of slab ``s``'s step: its selected q-blocks
    ``[first[s], stop[s])`` padded to a ``qblock_bucket`` size, shifted
    down where the padded range would pass the batch's end."""
    n_qb = qblock_bucket(int(stop[s] - first[s]), nqb)
    return min(int(first[s]), nqb - n_qb), n_qb


def _empty_run(n_queries: int, k: int, device):
    """The running best before any slab: every rank empty (-1)."""
    return jax.device_put(tuple(np.full((n_queries, k), -1, np.int32)
                                for _ in range(4)), device)


@partial(jax.jit, static_argnames=("k",))
def _merge_by_row(runs, k: int):
    """Merge running bests whose slabs interleave (one per device of a
    round-robin stream) by (sim desc, row asc): candidates are put in row
    order first, so the selection's first-maximum tie rule picks the lower
    row."""
    out = []
    for w in (0, 2):
        sims = jnp.concatenate([r[w] for r in runs], axis=1)
        rows = jnp.concatenate([r[w + 1] for r in runs], axis=1)
        order = jnp.argsort(jnp.where(rows >= 0, rows,
                                      jnp.iinfo(jnp.int32).max), axis=1)
        out += select_topk(jnp.take_along_axis(sims, order, axis=1), k,
                           payload=jnp.take_along_axis(rows, order, axis=1))
    return tuple(out)


@partial(jax.jit, static_argnames=("k",))
def _merge_partials(run, part, k: int):
    """Fold one slab's winners into the running best. ``run`` holds earlier
    (lower-row) slabs, so it wins score ties — the merge_topk contract."""
    std_b, std_row = merge_topk(run[0], run[1], part[0], part[1], k)
    open_b, open_row = merge_topk(run[2], run[3], part[2], part[3], k)
    return std_b, std_row, open_b, open_row


# The serve loop's runtime contract: repeated same-shaped search_encoded
# calls must hit the jit caches (fixed slab shape + memoized padding plan =
# stable abstract signatures). The analyzer runs real repeat calls under a
# RecompileGuard; per-call cache growth here means every request pays an
# XLA compile.
declare("serve:loop", "recompile_guard",
        note="steady-state serving must not re-trace/re-compile per call")

# Hot-reload keeps the same requested slab_rows, so a reload re-plans to
# the SAME fixed slab shapes — the swap must not invalidate the jit cache
# (a reload that forced per-call recompiles would defeat live growth).
declare("serve:loop", "recompile_guard",
        note="hot-reload swap preserves slab shapes, hence the jit cache")

# The observability contract: the spans instrumenting this engine (and the
# pipeline stages above it) are host-side, strictly around the jit
# boundaries — installing a repro.obs tracer must leave every hot jaxpr
# byte-identical and change zero result bytes. The analyzer traces and
# runs the real search with and without a tracer installed and diffs both.
declare("serve:obs", "trace_transparency",
        note="tracing must not alter jaxprs or result bytes")


class StreamingEngine:
    """Executes OMS over a :class:`~repro.store.LibraryStore` (or a
    prebuilt :class:`StoreLayout`) one bounded slab at a time."""

    def __init__(self, store_or_layout, *, max_r: int, slab_rows: int = 1 << 18,
                 devices: Sequence | None = None, prefetch: bool = True):
        self.max_r = max_r
        self._slab_rows_req = slab_rows
        self.devices = list(devices) if devices else None
        self._prefetch = prefetch
        # _swap_lock makes the (layout, plan) pair swap atomically under
        # reload(); _stats_lock serialises the read-modify-write on the
        # cumulative totals when scheduler paths call search_encoded
        # concurrently.
        self._swap_lock = threading.Lock()
        self._stats_lock = threading.Lock()
        self.layout, self.plan = self._plan_for(store_or_layout)
        self.last_stats: StreamStats | None = None
        self.total_stats = TotalStats()

    def _plan_for(self, store_or_layout) -> tuple[StoreLayout, SlabPlan]:
        if isinstance(store_or_layout, StoreLayout):
            layout = store_or_layout
            if layout.max_r != self.max_r:
                raise ValueError(f"layout has max_r={layout.max_r}, "
                                 f"engine asked for {self.max_r}")
        else:
            layout = StoreLayout.from_store(store_or_layout, max_r=self.max_r)
        plan = plan_slabs(layout.n_blocks, max_r=self.max_r,
                          slab_rows=self._slab_rows_req)
        return layout, plan

    def reload(self, store_or_layout) -> None:
        """Re-plan over a grown store and swap (layout, plan) in atomically.

        In-flight ``search_encoded`` calls finish on the snapshot they took
        at entry (append-only shard files keep old mmaps valid); calls that
        start after the swap are bit-identical to a cold start on the grown
        store. Same requested ``slab_rows`` => same slab shapes => the jit
        cache survives the swap."""
        layout, plan = self._plan_for(store_or_layout)
        with self._swap_lock:
            self.layout = layout
            self.plan = plan

    def _snapshot(self) -> tuple[StoreLayout, SlabPlan]:
        with self._swap_lock:
            return self.layout, self.plan

    def _set_stats(self, st: StreamStats) -> None:
        with self._stats_lock:
            self.last_stats = st
            self.total_stats.add(st)

    def reset_stats(self) -> None:
        """Zero the cumulative totals and clear the per-call snapshot."""
        with self._stats_lock:
            self.last_stats = None
            self.total_stats = TotalStats()

    # ------------------------------------------------------------------
    def _device_for(self, j: int):
        return self.devices[j % len(self.devices)] if self.devices else None

    def _queries_on(self, cache: dict, device, qh, qp, qc):
        if device is None:
            return qh, qp, qc
        if device not in cache:
            cache[device] = tuple(jax.device_put(x, device)
                                  for x in (qh, qp, qc))
        return cache[device]

    # ------------------------------------------------------------------
    @staticmethod
    def _slab_real_rows(layout: StoreLayout, plan: SlabPlan, s: int) -> int:
        """Non-padding layout rows slab ``s`` reads from the store shards."""
        b0 = s * plan.slab_blocks
        b1 = min(b0 + plan.slab_blocks, layout.n_blocks)
        return layout.real_rows(b0 * plan.max_r, b1 * plan.max_r)

    def _stream(self, layout: StoreLayout, plan: SlabPlan, touched,
                read):
        """Yield ``(j, s, slab)`` for each touched slab ``s`` in order, the
        wait for each under a ``serve.slab.wait`` span. With prefetch, one
        background thread runs ``read(layout, s, plan)`` up to
        ``PREFETCH_SLABS`` slabs ahead of the slab being yielded. Closing
        the generator (``contextlib.closing``) cancels the reads not yet
        started and retrieves the running one's outcome, so no
        mmap-reading thread outlives the scan and no exception goes
        unretrieved."""
        if not (self._prefetch and len(touched) > 1):
            for j, s in enumerate(touched):
                with span("serve.slab.wait", slab=s):
                    slab = read(layout, s, plan)
                yield j, s, slab
            return
        pool = ThreadPoolExecutor(max_workers=1)
        pending = collections.deque(
            pool.submit(read, layout, s, plan)
            for s in touched[:PREFETCH_SLABS])
        try:
            for j, s in enumerate(touched):
                with span("serve.slab.wait", slab=s):
                    slab = pending.popleft().result()
                if j + PREFETCH_SLABS < len(touched):
                    pending.append(pool.submit(
                        read, layout, touched[j + PREFETCH_SLABS], plan))
                yield j, s, slab
        finally:
            for fut in pending:
                if not fut.cancel():
                    try:
                        fut.result()
                    except BaseException:
                        pass
            pool.shutdown(wait=False)

    @staticmethod
    def _gather(layout: StoreLayout, s: int, plan: SlabPlan, scan_span,
                n_words: int | None = None):
        """Slab ``s`` read from the store shards (``slab_arrays``), under a
        ``serve.slab.gather`` span that is a child of the call's
        ``serve.scan`` span on whichever thread runs it."""
        with span("serve.slab.gather", parent=scan_span, slab=s):
            return slab_arrays(layout, s, plan, n_words=n_words)

    def search_encoded(self, q_hvs, q_pmz, q_charge, params: SearchParams, *,
                       dim: int, q_pmz_np: np.ndarray | None = None,
                       q_charge_np: np.ndarray | None = None) -> SearchResult:
        """Streamed equivalent of :func:`repro.core.search.oms_search` —
        same inputs, bit-identical :class:`SearchResult`. With
        ``params.prefix_words > 0`` the slab scan runs as the two-stage
        dimension cascade (prefix-word slab reads + full-width survivor
        fetches) — still bit-identical in exact mode."""
        layout, plan = self._snapshot()
        validate_search_params(params, layout.n_rows)
        if params.prefix_words:
            validate_prefix_words(params, dim)
        Q, K = q_hvs.shape[0], params.top_k
        qp_np = np.asarray(q_pmz if q_pmz_np is None else q_pmz_np)
        qc_np = np.asarray(q_charge if q_charge_np is None else q_charge_np)

        gather, unpad = sort_pad_plan(q_pmz, q_charge, params.q_block,
                                      q_charge_np=qc_np)
        qh, qp, qc = q_hvs[gather], q_pmz[gather], q_charge[gather]
        if params.exhaustive:   # the HyperOMS baseline scans everything
            first = np.zeros((plan.n_slabs,), np.int64)
            stop = np.full((plan.n_slabs,), qh.shape[0] // params.q_block)
        else:
            g = np.asarray(gather)
            first, stop = slab_qblocks(
                layout, qp_np[g], qc_np[g], q_block=params.q_block,
                open_tol_da=params.open_tol_da, plan=plan)
        touched = np.flatnonzero(stop > first).tolist()

        with span("serve.scan", queries=Q, slabs=len(touched),
                  mode="prefix" if params.prefix_words else "full") as sp:
            if params.prefix_words:
                run, st = self._scan_prefix(layout, plan, touched, first,
                                            stop, qh, qp, qc, params, dim,
                                            qp_np, qc_np, sp)
            else:
                run, st = self._scan_full(layout, plan, touched, first, stop,
                                          qh, qp, qc, params, dim, sp)
            self._set_stats(st)
            sp.add(rows=st.scanned_rows, bytes=st.scanned_bytes,
                   pairs=st.scanned_pairs)

        if run is None:          # no slab intersects any query window
            z = np.full((Q, K), -1, np.int32)
            return SearchResult(*(jnp.asarray(z),) * 6)

        # Drop padding queries, restore input order, then finalize on host
        # (orig_idx/is_decoy sidecars never go to the device).
        unpad_np = np.asarray(unpad)
        std_b, std_row, open_b, open_row = (np.asarray(x)[unpad_np]
                                            for x in run)
        std = self._finalize(layout, std_b, std_row, params.min_sim)
        opn = self._finalize(layout, open_b, open_row, params.min_sim)
        return SearchResult(std_idx=std[0], std_sim=std[1],
                            open_idx=opn[0], open_sim=opn[1],
                            std_row=std[2], open_row=opn[2])

    def _scan_full(self, layout: StoreLayout, plan: SlabPlan, touched,
                   first, stop, qh, qp, qc, params: SearchParams, dim: int,
                   scan_span):
        """Full-width slab loop: one slab step
        (``_search_sorted_padded_slab``) per touched slab ``s``, over the
        q-blocks ``[first[s], stop[s])`` padded to a
        ``qblock_bucket`` size."""
        QB, K = params.q_block, params.top_k
        local = params._replace(
            k_blocks=min(params.k_blocks, plan.slab_blocks))
        nqb = qh.shape[0] // QB
        W = layout.n_words
        rows_per_qblock = (plan.slab_rows if params.exhaustive
                           else local.k_blocks * plan.max_r)
        rows_read = pairs = 0
        runs: dict = {}          # device -> running best of its slabs
        qcache: dict = {}
        gather = partial(self._gather, scan_span=scan_span)
        with contextlib.closing(
                self._stream(layout, plan, touched, gather)) as slabs:
            for j, s, db_np in slabs:
                n_real = self._slab_real_rows(layout, plan, s)
                rows_read += n_real
                q0, n_qb = _qblock_range(first, stop, s, nqb)
                pairs += n_qb * QB * rows_per_qblock
                with span("serve.slab.search", slab=s, rows=n_real,
                          bytes=n_real * W * 4, qblocks=n_qb,
                          **scan_path(db_np, local)):
                    dev = self._device_for(j)
                    prev = runs.get(dev)
                    if prev is None:
                        prev = _empty_run(qh.shape[0], K, dev)
                    runs[dev] = _search_sorted_padded_slab(
                        prev, jax.device_put(db_np, dev),
                        *self._queries_on(qcache, dev, qh, qp, qc),
                        np.int32(q0), np.int32(s * plan.slab_rows),
                        params=local, dim=dim, n_qb=n_qb)
                    # Keep at most two of the device's slabs alive: the
                    # one just dispatched and the one before it.
                    jax.block_until_ready(prev)
        if len(runs) > 1:
            run = _merge_by_row(jax.device_put(list(runs.values()),
                                               self.devices[0]), K)
        else:
            run = next(iter(runs.values()), None)
        st = StreamStats(plan.n_slabs, len(touched), plan.slab_rows,
                         scanned_rows=rows_read,
                         scanned_bytes=rows_read * W * 4,
                         scanned_pairs=pairs)
        return run, st

    def _scan_prefix(self, layout: StoreLayout, plan: SlabPlan, touched,
                     first, stop, qh, qp, qc, params: SearchParams, dim: int,
                     qp_np, qc_np, scan_span):
        """Dimension-cascade slab loop: seed pass for exact thresholds, a
        prefix-words read+scan per touched slab over its q-blocks
        ``[first[s], stop[s])`` (padded as the full-width loop pads them),
        full-width fetch + exact rescore of the survivors against the same
        q-blocks, fold into their rows of the running winners.

        Runs on the default device (the multi-device round-robin applies to
        the full-width path only — the cascade's per-slab survivor sync is
        inherently sequential)."""
        p = params
        K, P, W = p.top_k, p.prefix_words, layout.n_words
        local = p._replace(k_blocks=min(p.k_blocks, plan.slab_blocks))
        Qp = qh.shape[0]
        nqb = Qp // p.q_block
        rows_per_qblock = (plan.slab_rows if p.exhaustive
                           else local.k_blocks * plan.max_r)
        qh_p = qh[:, :P]
        rows_read = bytes_read = pairs = 0

        def candidates(rows_np: np.ndarray, n_queries: int):
            """Global layout rows (full width) as the padded candidate
            arrays of a rescore against ``n_queries`` queries.

            Only the REAL candidate rows are gathered from the store; the
            pow2 bucket padding is zero-filled host-side (padding rows are
            masked out via the PAD sidecars, so their HV content never
            reaches a selected result) — the store reads are therefore
            exactly the rows the byte meter charges for."""
            nonlocal pairs
            n = rows_np.shape[0]
            bucket = row_bucket(n)
            pairs += n_queries * bucket
            rows_pad, valid = pad_candidate_rows(rows_np, bucket)
            hv = np.zeros((bucket, W), np.uint32)
            hv[:n] = layout.gather_rows(rows_np)
            r_hvs = jnp.asarray(hv)
            r_pmz = jnp.asarray(np.where(valid, layout.pmz[rows_pad],
                                         np.float32(np.finfo(np.float32).max)))
            r_charge = jnp.asarray(np.where(
                valid, layout.charge[rows_pad], -1).astype(np.int32))
            r_rows = jnp.asarray(np.where(valid, rows_pad, -1).astype(np.int32))
            return r_hvs, r_rows, r_pmz, r_charge

        def rescore(rows_np: np.ndarray):
            """Exact dual-window top-k of every query over global layout
            rows."""
            return _rescore_rows_padded(*candidates(rows_np, Qp), qh, qp, qc,
                                        params=p, dim=dim)

        neg = jnp.full((Qp,), _NEG_THRESHOLD, jnp.int32)
        seed_rows = plan_seed_rows(layout.pmz, layout.charge,
                                   qp_np, qc_np, p.prefix_seed_da)
        if seed_rows.size:
            with span("serve.seed", rows=int(seed_rows.size),
                      bytes=int(seed_rows.size) * W * 4):
                thr_std, thr_open = kth_thresholds(rescore(seed_rows), K)
            rows_read += seed_rows.size
            bytes_read += seed_rows.size * W * 4
        else:
            thr_std, thr_open = neg, neg

        run = None
        slab_p = partial(self._gather, scan_span=scan_span, n_words=P)
        with contextlib.closing(
                self._stream(layout, plan, touched, slab_p)) as slabs:
            for _, s, db_np in slabs:
                n_real = self._slab_real_rows(layout, plan, s)
                rows_read += n_real
                bytes_read += n_real * P * 4
                q0, n_qb = _qblock_range(first, stop, s, nqb)
                pairs += n_qb * p.q_block * rows_per_qblock
                with span("serve.slab.search", slab=s, rows=n_real,
                          bytes=n_real * P * 4, qblocks=n_qb):
                    if run is not None:
                        # Tighten with the running k-th — still a subset
                        # k-th, so the exact-mode guarantee is untouched.
                        rs, ro = kth_thresholds(run, K)
                        ts = jnp.maximum(thr_std, rs)
                        to = jnp.maximum(thr_open, ro)
                    else:
                        ts, to = thr_std, thr_open
                    flags = _prefix_flags_slab(
                        jax.device_put(db_np), qh_p, qp, qc, ts, to,
                        np.int32(q0), params=local, dim=dim, n_qb=n_qb)
                    surv = np.flatnonzero(np.asarray(flags))
                if surv.size == 0:
                    continue
                surv_global = surv + s * plan.slab_rows
                rows_read += surv.size
                bytes_read += surv.size * W * 4
                with span("serve.slab.merge", slab=s,
                          rows=int(surv.size), bytes=int(surv.size) * W * 4):
                    if run is None:
                        run = _empty_run(Qp, K, None)
                    run = _rescore_slab(
                        run, *candidates(surv_global, n_qb * p.q_block),
                        qh, qp, qc, np.int32(q0), params=p, dim=dim,
                        n_qb=n_qb)

        if p.prefix_margin >= 0 and seed_rows.size:
            # Margin mode may prune true winners; folding the seed-pass
            # winners back in makes it no worse than the seed pass. (Exact
            # mode re-finds every seed winner as a survivor, and merging
            # seed results here would let a seed winner beat an equal-sim
            # LOWER row from an earlier slab — so exact mode must not.)
            part = rescore(seed_rows)
            run = part if run is None else _merge_partials(run, part, K)
            rows_read += seed_rows.size
            bytes_read += seed_rows.size * W * 4

        st = StreamStats(plan.n_slabs, len(touched), plan.slab_rows,
                         scanned_rows=rows_read, scanned_bytes=bytes_read,
                         scanned_pairs=pairs)
        return run, st

    @staticmethod
    def _finalize(layout: StoreLayout, best, row, min_sim):
        """Host mirror of ``oms_search``'s finalize: min-sim threshold, map
        padded rows to original library indices (padding rows carry -1)."""
        orig, n = layout.orig_idx, layout.n_rows
        ok = (best >= min_sim) & (row >= 0)
        idx = np.where(ok, orig[np.clip(row, 0, n - 1)], -1)
        ok = ok & (idx >= 0)
        return (jnp.asarray(np.where(ok, idx, -1).astype(np.int32)),
                jnp.asarray(np.where(ok, best, -1).astype(np.int32)),
                jnp.asarray(np.where(ok, row, -1).astype(np.int32)))
