"""Slab planning over the store's merged layout (serve-side, host).

RapidOMS streams the packed reference library past the compute engine from
near-storage; the library is never resident. The pieces that make that work
on this repro live here:

  * :class:`StoreLayout` — the (charge, pmz)-merged, block-padded layout of
    a :class:`~repro.store.LibraryStore` computed as *sidecars only*
    (pmz/charge/decoy/orig + block metadata, ~13 bytes/row) plus an HV
    gather plan. The packed-HV payload — the dominant term, dim/8 bytes per
    row — stays in the memory-mapped shard files and is read one bounded
    slab at a time (:meth:`StoreLayout.read_hv_rows`).
  * :func:`plan_slabs` — cuts the layout's block dimension into fixed-size
    slabs of ``slab_blocks`` whole blocks. Every slab has the same device
    shape (the tail slab is padded), so the per-slab search compiles once.
  * :func:`slab_qblocks` — intersects a coalesced query batch's open
    precursor windows with each slab's rows: the streaming executor skips
    slabs no window meets, and in each slab it scans only the query blocks
    whose windows meet it (the paper's DRAM-orchestrator pruning, lifted to
    slab granularity); :func:`qblock_bucket` pads their number to a few
    sizes, so a run compiles a bounded set of slab steps.
  * :func:`slab_arrays` — assembles slab ``s`` as a host-side
    :class:`~repro.core.blocking.ReferenceDB` ready for ``device_put``.

Row-space invariant: slab ``s`` covers padded rows
``[s*slab_blocks*max_r, (s+1)*slab_blocks*max_r)`` of the SAME layout the
resident ``ReferenceDB`` uses (the padding plan is shared code —
``blocking.padded_partition_plan``). Per-slab search rows offset by the
slab's start row therefore land in the identical global row space, which is
what makes the cross-slab top-k merge bit-identical to a resident scan.
"""
from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Any, NamedTuple, Sequence

import numpy as np

from repro.core.blocking import (LibraryRun, ReferenceDB, block_pmz_ranges,
                                 merge_sorted_runs, padded_partition_plan,
                                 run_sort_keys)

_F32_MAX = np.float32(np.finfo(np.float32).max)
_READERS = min(8, os.cpu_count() or 1)   # threads reading one slab's runs


@functools.cache
def _reader_pool() -> ThreadPoolExecutor:
    """The process's store-reading threads, shared by every read."""
    return ThreadPoolExecutor(_READERS, thread_name_prefix="store-read")

# Sorts after every real block key in core.search's monotonic bkey space
# (charge * _CHARGE_KEY + clipped pmz): real charges are small ints, so tail
# padding blocks keyed by this charge never break the slab's ascending key
# order that `searchsorted` start-block pruning relies on.
PAD_BLOCK_CHARGE = 1023


class StoreLayout:
    """Host-side merged+padded layout of a library: every ReferenceDB
    sidecar as numpy, plus a per-row (run, row) gather plan for the packed
    HVs, which stay memory-mapped in the store shards until a slab needs
    them."""

    def __init__(self, *, pmz, charge, is_decoy, orig_idx, block_min,
                 block_max, block_charge, src_run, src_row, hv_runs,
                 max_r: int):
        self.pmz = pmz                    # (Rp,) f32, PAD_PMZ on padding
        self.charge = charge              # (Rp,) i32, -1 on padding
        self.is_decoy = is_decoy          # (Rp,) bool
        self.orig_idx = orig_idx          # (Rp,) i32, -1 on padding
        self.block_min = block_min        # (nb,) f32
        self.block_max = block_max        # (nb,) f32
        self.block_charge = block_charge  # (nb,) i32
        self.src_run = src_run            # (Rp,) i32 — source run, -1 pad
        self.src_row = src_row            # (Rp,) i64 — row within the run
        self._hv_runs = hv_runs           # per-run (n, W) uint32, may be mmap
        self.max_r = max_r

    # -- construction -------------------------------------------------------
    @classmethod
    def from_runs(cls, runs: Sequence[LibraryRun], *,
                  max_r: int) -> "StoreLayout":
        """Merge (charge, pmz)-sorted runs into the padded blocked layout —
        the sidecar half of ``build_reference_db_from_runs`` — without ever
        touching the runs' HV payload."""
        runs = [LibraryRun(*(a if isinstance(a, np.ndarray) else np.asarray(a)
                             for a in r)) for r in runs]
        runs = [r for r in runs if len(r.pmz)]
        if not runs:
            raise ValueError("StoreLayout: no rows")
        run_id, row_in_run = merge_sorted_runs(run_sort_keys(runs))

        R = sum(len(r.pmz) for r in runs)
        pmz = np.empty((R,), np.float32)
        charge = np.empty((R,), np.int32)
        decoy = np.empty((R,), bool)
        orig = np.empty((R,), np.int32)
        # Same stable grouped gather as build_reference_db_from_runs: one
        # argsort groups output positions by run, rows stay ascending.
        pos = np.argsort(run_id, kind="stable")
        bounds = np.cumsum([0] + [len(r.pmz) for r in runs])
        for i, r in enumerate(runs):
            at = pos[bounds[i]:bounds[i + 1]]
            rows = row_in_run[at]
            pmz[at] = np.asarray(r.pmz)[rows]
            charge[at] = np.asarray(r.charge)[rows]
            decoy[at] = np.asarray(r.is_decoy)[rows]
            orig[at] = np.asarray(r.orig_idx)[rows]

        sel, b_charge = padded_partition_plan(charge, max_r)
        pad = sel < 0
        idx = np.where(pad, 0, sel)
        pp = pmz[idx]
        pp[pad] = _F32_MAX
        pc = charge[idx]
        pc[pad] = -1
        pd = decoy[idx]
        pd[pad] = False
        po = orig[idx]
        po[pad] = -1
        b_min, b_max = block_pmz_ranges(pp, max_r)
        return cls(
            pmz=pp, charge=pc, is_decoy=pd, orig_idx=po,
            block_min=b_min, block_max=b_max, block_charge=b_charge,
            src_run=np.where(pad, -1, run_id[idx]).astype(np.int32),
            src_row=np.where(pad, 0, row_in_run[idx]).astype(np.int64),
            hv_runs=[r.hvs for r in runs], max_r=max_r)

    @classmethod
    def from_store(cls, store: Any, *, max_r: int) -> "StoreLayout":
        """Layout of a :class:`~repro.store.LibraryStore`: shard sidecars
        are read (small), shard HVs stay memory-mapped."""
        return cls.from_runs(list(store.iter_runs()), max_r=max_r)

    # -- introspection ------------------------------------------------------
    @property
    def n_rows(self) -> int:
        return self.pmz.shape[0]

    @property
    def n_blocks(self) -> int:
        return self.block_min.shape[0]

    @property
    def n_words(self) -> int:
        return self._hv_runs[0].shape[1]

    def sidecar_nbytes(self) -> int:
        """Host bytes held per row-sidecar (the part that is NOT slabbed)."""
        return sum(a.nbytes for a in (self.pmz, self.charge, self.is_decoy,
                                      self.orig_idx, self.src_run,
                                      self.src_row))

    # -- HV payload ---------------------------------------------------------
    def read_hv_rows(self, lo: int, hi: int,
                     n_words: int | None = None) -> np.ndarray:
        """Gather padded rows [lo, hi) of the packed HVs from the mmapped
        runs (zeros on padding rows). ``n_words`` < the full width reads
        only that word prefix per row — the dimension cascade's stage-A
        scanned-bytes saving."""
        return self._read(self.src_run[lo:hi], self.src_row[lo:hi], n_words)

    def gather_rows(self, rows_padded: np.ndarray,
                    n_words: int | None = None) -> np.ndarray:
        """Gather an ARBITRARY ascending set of padded-layout rows (the
        cascade's seed / survivor fetches). Padding rows come back zero."""
        return self._read(self.src_run[rows_padded],
                          self.src_row[rows_padded], n_words)

    def _read(self, src: np.ndarray, rows: np.ndarray,
              n_words: int | None) -> np.ndarray:
        """The packed HVs of layout rows whose sources are (run ``src``, row
        ``rows``). One fancy-index read per run, its rows ascending (the
        merge is stable, so shard reads stay sequential); up to
        ``_READERS`` runs are read at once on the shared reader pool, since
        NumPy copies without the GIL."""
        W = self.n_words if n_words is None else n_words
        out = np.empty((src.shape[0], W), np.uint32)
        order = np.argsort(src, kind="stable")
        segs = np.split(order, np.flatnonzero(np.diff(src[order])) + 1)

        def read(seg):
            run = src[seg[0]]
            out[seg] = 0 if run < 0 else self._hv_runs[run][rows[seg], :W]

        if len(segs) > 1:
            list(_reader_pool().map(read, segs))
        elif segs[0].size:
            read(segs[0])
        return out

    def real_rows(self, lo: int, hi: int) -> int:
        """Count of non-padding layout rows in [lo, hi) — the rows whose
        bytes a slab read actually pulls from the store shards."""
        return int((self.src_run[lo:hi] >= 0).sum())


# ---------------------------------------------------------------------------
# Slab planning
# ---------------------------------------------------------------------------


class SlabPlan(NamedTuple):
    """Fixed-size slab cut of a layout's block dimension."""

    slab_blocks: int   # whole blocks per slab (every slab, tail padded)
    n_slabs: int
    max_r: int

    @property
    def slab_rows(self) -> int:
        return self.slab_blocks * self.max_r


def plan_slabs(n_blocks: int, *, max_r: int, slab_rows: int) -> SlabPlan:
    """Round ``slab_rows`` up to whole blocks and cap at the whole store."""
    if slab_rows < 1:
        raise ValueError(f"slab_rows must be >= 1, got {slab_rows}")
    if n_blocks < 1:
        raise ValueError("plan_slabs: empty layout")
    slab_blocks = min(max(1, -(-slab_rows // max_r)), n_blocks)
    return SlabPlan(slab_blocks=slab_blocks,
                    n_slabs=-(-n_blocks // slab_blocks), max_r=max_r)


# Widening of every host-side window test: it covers the f32 rounding of
# the needles below and of the device's |q_pmz - r_pmz| for precursors in
# the scan's key range (< 8192 Da), so the rows the host finds in a window
# are a superset of those the device counts as in it.
_WINDOW_SLACK_DA = 1e-3


def _window_rows(layout, q_pmz: np.ndarray, q_charge: np.ndarray, *,
                 open_tol_da: float) -> tuple[np.ndarray, np.ndarray]:
    """Per query, the ``[lo, hi)`` layout rows of its charge whose precursor
    lies in its open window (``lo == hi`` where none does). A charge's rows
    are one run of whole blocks, pmz ascending with its padding rows last
    (at the f32 maximum), so each bound is one ``searchsorted``."""
    qp = np.asarray(q_pmz, np.float64)
    qc = np.asarray(q_charge)
    lo = np.zeros(qp.shape, np.int64)
    hi = np.zeros(qp.shape, np.int64)
    bch = np.asarray(layout.block_charge)
    tol = open_tol_da + _WINDOW_SLACK_DA
    for c in np.unique(qc):
        blocks = np.flatnonzero(bch == c)
        if not blocks.size:
            continue
        r0 = int(blocks[0]) * layout.max_r
        pm = np.asarray(layout.pmz[r0:(int(blocks[-1]) + 1) * layout.max_r])
        m = qc == c
        lo[m] = r0 + np.searchsorted(pm, (qp[m] - tol).astype(np.float32),
                                     side="left")
        hi[m] = r0 + np.searchsorted(pm, (qp[m] + tol).astype(np.float32),
                                     side="right")
    return lo, hi


def _slab_cover(layout, q_pmz, q_charge, *, open_tol_da: float,
                plan: SlabPlan) -> np.ndarray:
    """(Q, n_slabs) bool: does slab ``s`` hold a row of query ``i``'s
    window?"""
    lo, hi = _window_rows(layout, q_pmz, q_charge, open_tol_da=open_tol_da)
    s = np.arange(plan.n_slabs)
    first = (lo // plan.slab_rows)[:, None]
    last = ((hi - 1) // plan.slab_rows)[:, None]
    return (hi > lo)[:, None] & (first <= s) & (s <= last)


def slab_qblocks(layout, q_pmz: np.ndarray, q_charge: np.ndarray, *,
                 q_block: int, open_tol_da: float,
                 plan: SlabPlan) -> tuple[np.ndarray, np.ndarray]:
    """Per slab, the ``[first, stop)`` range of query blocks that holds
    every q-block with a query whose open window meets the slab
    (``first == stop == 0`` for a slab no window meets). A slab no window
    meets cannot contain an in-window candidate (the std ppm window is
    nested inside the open window), so skipping it preserves bit-identity
    with a full scan.

    ``q_pmz``/``q_charge`` are the (charge, pmz)-sorted, ``q_block``-padded
    queries the slab scan runs on. A q-block's results from a slab its
    windows miss are all empty, so scanning only these ranges changes no
    result; q-blocks inside a range whose windows miss the slab are harmless
    for the same reason.
    """
    cover = _slab_cover(layout, q_pmz, q_charge, open_tol_da=open_tol_da,
                        plan=plan)
    nqb = cover.shape[0] // q_block
    hit = cover.reshape(nqb, q_block, plan.n_slabs).any(axis=1)
    touched = hit.any(axis=0)
    first = np.where(touched, hit.argmax(axis=0), 0)
    stop = np.where(touched, nqb - hit[::-1].argmax(axis=0), 0)
    return first, stop


def qblock_bucket(n: int, n_qblocks: int) -> int:
    """How many q-blocks a slab step scans for ``n`` selected ones: ``n`` up
    to 8, above that the next of eight steps per octave (9, 10, ..., 16,
    18, 20, ..., 32, 36, ...), at most ``n_qblocks``. A step scans at most
    1/8 more q-blocks than were selected, and a run compiles one slab step
    per bucket it meets, a few per octave of batch sizes."""
    if n > 8:
        e = (n - 1).bit_length() - 4
        n = -(-n >> e) << e
    return min(n, n_qblocks)


def slab_arrays(layout: StoreLayout, s: int, plan: SlabPlan,
                n_words: int | None = None) -> ReferenceDB:
    """Assemble slab ``s`` as a host-side ReferenceDB (numpy leaves): the
    slab's rows/blocks sliced from the padded layout, tail-padded to the
    fixed slab shape so every slab hits one jit cache entry. This is the
    only place the packed HV payload is materialised — one slab's worth.
    ``n_words`` builds a PREFIX slab (stage A of the dimension cascade):
    only that many packed words per row are read from the store.
    """
    b0 = s * plan.slab_blocks
    b1 = min(b0 + plan.slab_blocks, layout.n_blocks)
    if not b0 < b1:
        raise ValueError(f"slab {s} out of range (n_slabs={plan.n_slabs})")
    r0, r1 = b0 * plan.max_r, b1 * plan.max_r
    rows, nb = plan.slab_rows, plan.slab_blocks
    W = layout.n_words if n_words is None else n_words

    hvs = layout.read_hv_rows(r0, r1, n_words=W)
    if r1 - r0 < rows:
        hvs = np.concatenate([hvs, np.zeros((rows - (r1 - r0), W), np.uint32)])
    pmz = np.full((rows,), _F32_MAX, np.float32)
    pmz[:r1 - r0] = layout.pmz[r0:r1]
    charge = np.full((rows,), -1, np.int32)
    charge[:r1 - r0] = layout.charge[r0:r1]
    decoy = np.zeros((rows,), bool)
    decoy[:r1 - r0] = layout.is_decoy[r0:r1]
    orig = np.full((rows,), -1, np.int32)
    orig[:r1 - r0] = layout.orig_idx[r0:r1]
    b_min = np.full((nb,), np.inf, np.float32)
    b_min[:b1 - b0] = layout.block_min[b0:b1]
    b_max = np.full((nb,), -np.inf, np.float32)
    b_max[:b1 - b0] = layout.block_max[b0:b1]
    b_charge = np.full((nb,), PAD_BLOCK_CHARGE, np.int32)
    b_charge[:b1 - b0] = layout.block_charge[b0:b1]
    return ReferenceDB(hvs=hvs, pmz=pmz, charge=charge, is_decoy=decoy,
                       orig_idx=orig, block_min=b_min, block_max=b_max,
                       block_charge=b_charge, max_r=plan.max_r)
