"""End-to-end OMS pipeline: preprocess -> encode -> block -> search -> FDR.

This is the paper's Fig. 1b flow as a library object, split the way the
hardware splits it:

  * **ingest** (one-time, near-storage): encode the reference library
    (+ row-keyed decoys) in bounded-memory chunks and either build the
    blocked DB in RAM (``OMSPipeline(cfg, refs)``) or persist the chunks as
    sorted shards of an on-disk :class:`~repro.store.LibraryStore`
    (``OMSPipeline.ingest``);
  * **serve** (hot path): ``OMSPipeline.from_store`` cold-starts from the
    packed shards — codebooks regenerated from the manifest seed, blocked
    DB assembled by merging the shards' (charge, pmz)-sorted runs — with
    *zero* reference re-encoding, then ``search()`` encodes only queries.
    With ``resident=False`` the merged DB never lands on the device: the
    streaming engine (``repro.serve``) scans the store slab-by-slab with
    bit-identical results, bounding device memory by the slab size.

Both construction paths run the identical chunked encode, so a reloaded
store yields bit-identical search results to the in-memory build.
"""
from __future__ import annotations

import dataclasses
import os
from typing import TYPE_CHECKING, Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import decoys as decoys_mod
from repro.core import encode_backends, encoding
from repro.core.blocking import (LibraryRun, ReferenceDB,
                                 build_reference_db_from_runs)
from repro.core.cascade import (CascadeOutput, CascadeParams, cascade_search,
                                row_match_flags)
from repro.core.fdr import FDRResult, fdr_filter
from repro.core.search import (SearchParams, SearchResult,
                               narrow_search_params, oms_search, plan_search,
                               scanned_rows)
from repro.data.spectra import SpectraSet
from repro.obs.trace import span
# Only the dependency-free constants at module level: repro.store.library_store
# imports repro.core, so LibraryStore itself is imported lazily inside the
# ingest()/from_store() bodies to keep `import repro.store` cycle-free.
from repro.store.format import DECOY, TARGET

if TYPE_CHECKING:
    from repro.store import LibraryStore


@dataclasses.dataclass(frozen=True)
class OMSConfig:
    """Paper settings (Tables I & II)."""

    dim: int = 4096              # Dhv
    n_levels: int = 32           # intensity quantisation levels (Q in Fig. 3)
    bin_size: float = 0.05       # m/z bin width (Table I: 0.05 / 0.04)
    mz_min: float = 200.0
    mz_max: float = 2000.0
    max_r: int = 4096            # MAX_R reference block size
    q_block: int = 16            # Q_BLOCK
    ppm_tol: float = 20.0        # standard search window
    open_tol_da: float = 75.0    # open search window
    fdr_threshold: float = 0.01
    add_decoys: bool = True
    backend: str = "vpu"         # any name in repro.core.backends.names()
    top_k: int = 1               # ranked winners per query and window
    # Dimension cascade (FeNOMS direction): scan candidates over only the
    # first prefix_words packed words, rescore survivors at full width.
    # prefix_margin=-1 keeps the exact bound (bit-identical results);
    # prefix_seed_da is the seed pass's precursor window. See core.search.
    prefix_words: int = 0
    prefix_margin: int = -1
    prefix_seed_da: float = 1.0
    # Encoder hot path: any name in repro.core.encode_backends.names().
    # All encode backends are bit-identical; the knob only picks the
    # schedule (and its peak intermediate footprint / throughput).
    encode_backend: str = "word_tiled"
    encode_batch: int = 512      # spectra per encode chunk (memory bound)
    seed: int = 0

    @property
    def n_bins(self) -> int:
        return int(round((self.mz_max - self.mz_min) / self.bin_size))

    @property
    def n_words(self) -> int:
        return self.dim // 32

    @property
    def preprocess_params(self) -> encoding.PreprocessParams:
        return encoding.PreprocessParams(
            bin_size=self.bin_size, mz_min=self.mz_min, mz_max=self.mz_max,
            n_levels=self.n_levels)


class OMSOutput(NamedTuple):
    result: SearchResult       # raw dual-window matches (idx into target lib)
    open_fdr: FDRResult        # FDR filtering over the open-search matches
    std_fdr: FDRResult         # FDR filtering over the standard-search matches


# ---------------------------------------------------------------------------
# Shared ingest machinery (in-memory build and store writer both use this)
# ---------------------------------------------------------------------------


def _derive_keys(cfg: OMSConfig) -> tuple[jax.Array, jax.Array]:
    """(codebook key, decoy key) from the config seed — the only PRNG state;
    reproducing these from the manifest is what lets a store skip codebook
    persistence entirely."""
    k_cb, k_dec = jax.random.split(jax.random.PRNGKey(cfg.seed))
    return k_cb, k_dec


def _make_codebooks(cfg: OMSConfig) -> encoding.Codebooks:
    k_cb, _ = _derive_keys(cfg)
    return encoding.make_codebooks(
        k_cb, n_bins=cfg.n_bins, n_levels=cfg.n_levels, dim=cfg.dim)


def _encode_library_runs(
    cfg: OMSConfig, codebooks: encoding.Codebooks, k_dec: jax.Array,
    refs: SpectraSet, *, encode_batch: int, chunk_rows: int,
    tgt_offset: int = 0,
) -> Iterator[tuple[str, np.ndarray, np.ndarray, np.ndarray, np.ndarray]]:
    """Chunked/streaming library encode.

    Yields ``(kind, hvs, pmz, charge, tgt_idx)`` numpy chunks — every target
    chunk first, then (if ``cfg.add_decoys``) every decoy chunk — each
    sorted by (charge, pmz), i.e. ready to be a store shard or a merge run.
    Host memory is bounded by one chunk of encode intermediates at a time;
    preprocess+encode dispatch through ``cfg.encode_backend`` (bit-identical
    across backends, so shards are byte-identical no matter which wrote them).

    Per-row determinism (encoding touches only its own row; decoy peaks are
    keyed by global target index ``tgt_offset + row``) makes the output
    independent of ``chunk_rows``/``encode_batch`` boundaries — the property
    that makes ``append()``-grown stores match one-shot builds bit-for-bit.
    """
    n = refs.mz.shape[0]
    kinds = (TARGET, DECOY) if cfg.add_decoys else (TARGET,)
    for kind in kinds:
        for s in range(0, n, chunk_rows):
            e = min(s + chunk_rows, n)
            mz, inten = refs.mz[s:e], refs.intensity[s:e]
            if kind == DECOY:
                mz, inten = decoys_mod.make_decoy_peaks(
                    k_dec, mz, inten, cfg.mz_min, cfg.mz_max,
                    row_offset=tgt_offset + s)
            hvs_j, pmz_j, charge_j = encode_backends.preprocess_encode(
                mz, inten, refs.pmz[s:e], refs.charge[s:e], codebooks,
                cfg.preprocess_params, backend=cfg.encode_backend,
                batch=encode_batch)
            hvs = np.asarray(hvs_j)
            pmz = np.asarray(pmz_j, dtype=np.float32)
            charge = np.asarray(charge_j, dtype=np.int32)
            order = np.lexsort((pmz, charge))
            tgt_idx = (tgt_offset + s + order).astype(np.int32)
            yield kind, hvs[order], pmz[order], charge[order], tgt_idx


class OMSPipeline:
    """Stateful pipeline: holds codebooks + the blocked reference DB."""

    def __init__(self, cfg: OMSConfig, refs: SpectraSet, *,
                 encode_batch: int | None = None, chunk_rows: int = 4096):
        encode_batch = cfg.encode_batch if encode_batch is None else encode_batch
        self.cfg = cfg
        self.engine = None          # set by from_store(resident=False)
        _, k_dec = _derive_keys(cfg)
        self.codebooks = _make_codebooks(cfg)

        # --- ingest (in-memory): chunked encode -> sorted runs -> merged DB.
        # orig_idx in the DB refers to the concatenated (targets ++ decoys)
        # layout; targets keep their library index, decoys get n_targets + i.
        self.n_targets = int(refs.mz.shape[0])
        runs = []
        for kind, hvs, pmz, charge, tgt_idx in _encode_library_runs(
                cfg, self.codebooks, k_dec, refs,
                encode_batch=encode_batch, chunk_rows=chunk_rows):
            is_d = kind == DECOY
            orig = tgt_idx + (np.int32(self.n_targets) if is_d else np.int32(0))
            runs.append(LibraryRun(hvs, pmz, charge,
                                   np.full((len(pmz),), is_d), orig))
        self.db: ReferenceDB = build_reference_db_from_runs(
            runs, max_r=cfg.max_r)

    # ------------------------------------------------------------------
    # Ingest/serve split: persistent store paths
    # ------------------------------------------------------------------
    @classmethod
    def ingest(cls, cfg: OMSConfig, refs: SpectraSet, store_path: str, *,
               encode_batch: int | None = None, chunk_rows: int = 4096,
               append: bool = False) -> LibraryStore:
        """Encode ``refs`` chunk-by-chunk into an on-disk LibraryStore.

        Streams: each chunk's packed HVs are written as a sorted shard as
        soon as they are produced, so host memory stays bounded by one chunk
        regardless of library size; the manifest is committed once, after
        the last shard, so a crashed ingest leaves the store at its prior
        state (orphaned shard files are ignored and overwritten on retry).
        With ``append=True`` the store must already exist with a matching
        config; new references are added as new shards (existing shards are
        never rewritten) and their decoys are keyed by global index, so the
        grown store is bit-identical to a one-shot build of the full
        library.
        """
        from repro.store import LibraryStore
        if append:
            store = LibraryStore.open(store_path)
            store.check_config(cfg)
            tgt_offset = store.n_targets
        else:
            store = LibraryStore.create(
                store_path, dim=cfg.dim, n_levels=cfg.n_levels,
                bin_size=cfg.bin_size, mz_min=cfg.mz_min, mz_max=cfg.mz_max,
                seed=cfg.seed, add_decoys=cfg.add_decoys)
            tgt_offset = 0
        _, k_dec = _derive_keys(cfg)
        codebooks = _make_codebooks(cfg)
        if encode_batch is None:
            encode_batch = cfg.encode_batch
        for kind, hvs, pmz, charge, tgt_idx in _encode_library_runs(
                cfg, codebooks, k_dec, refs, encode_batch=encode_batch,
                chunk_rows=chunk_rows, tgt_offset=tgt_offset):
            store.append_shard(kind, hvs, pmz, charge, tgt_idx, commit=False)
        store.commit()
        return store

    @classmethod
    def from_store(cls, store: LibraryStore | str | os.PathLike,
                   cfg: OMSConfig | None = None, *,
                   resident: bool = True, slab_rows: int = 1 << 18,
                   stream_devices=None,
                   **overrides) -> "OMSPipeline":
        """Cold-start a serving pipeline from a persisted store.

        No reference encoding happens: codebooks are regenerated from the
        manifest seed and the blocked DB is assembled by stable-merging the
        shards' (charge, pmz)-sorted runs straight from the memory-mapped
        files. If ``cfg`` is given it must match the store's encoding
        fields (:class:`repro.store.StoreConfigError` otherwise); when
        omitted, a config is reconstructed from the manifest and
        ``overrides`` may set serving-side knobs (``backend``, ``top_k``,
        ``max_r``, ``encode_backend``, ``encode_batch``, ...) — encode
        backends are bit-identical, so query encoding stays
        search-compatible with any store.

        With ``resident=False`` the library is NOT loaded to the device:
        ``search``/``search_encoded`` transparently run through the
        streaming :class:`~repro.serve.StreamingEngine`, which scans the
        store ``slab_rows`` rows at a time (double-buffered slab uploads,
        cross-slab top-k merge) — bit-identical results, device memory
        bounded by the slab instead of the library. ``stream_devices``
        optionally deals the slab stream round-robin across several
        devices (see ``repro.distributed.collectives``).
        """
        from repro.store import LibraryStore
        if not isinstance(store, LibraryStore):
            store = LibraryStore.open(os.fspath(store))
        if cfg is None:
            cfg = OMSConfig(**{**store.config_fields(), **overrides})
        else:
            if overrides:
                cfg = dataclasses.replace(cfg, **overrides)
            store.check_config(cfg)
        self = cls.__new__(cls)
        self.cfg = cfg
        self.engine = None
        self.codebooks = _make_codebooks(cfg)
        self.n_targets = store.n_targets
        if resident:
            self.db = store.load_reference_db(max_r=cfg.max_r)
        else:
            from repro.serve import StreamingEngine
            self.db = None
            self.engine = StreamingEngine(store, max_r=cfg.max_r,
                                          slab_rows=slab_rows,
                                          devices=stream_devices)
        return self

    def reload_store(self, store) -> None:
        """Hot-reload a grown (append-only) store into a streaming pipeline.

        Re-plans the engine's layout/slabs over the new shard set (atomic
        swap — in-flight scans finish on their entry snapshot) and drops
        the host sidecar cache so cascade FDR grouping and seed planning see
        the grown library. Callers that interleave searches with reloads
        (the serve loop) must invoke this from the thread that runs the
        searches, so a single batch never mixes old sidecars with a new
        layout. Bit-identical to a cold start on the grown store."""
        from repro.store import LibraryStore
        if self.engine is None:
            raise RuntimeError(
                "reload_store needs the streaming path (resident=False): "
                "a resident DB cannot grow in place")
        if not isinstance(store, LibraryStore):
            store = LibraryStore.open(os.fspath(store))
        store.check_config(self.cfg)
        self.engine.reload(store)
        self.n_targets = store.n_targets
        self._host_sidecars_cache = None

    # ------------------------------------------------------------------
    def encode_queries(self, queries: SpectraSet) -> tuple[jax.Array, jax.Array, jax.Array]:
        with span("pipeline.encode", spectra=int(queries.mz.shape[0]),
                  backend=self.cfg.encode_backend):
            return encode_backends.preprocess_encode(
                queries.mz, queries.intensity, queries.pmz, queries.charge,
                self.codebooks, self.cfg.preprocess_params,
                backend=self.cfg.encode_backend, batch=self.cfg.encode_batch)

    @property
    def _block_meta(self):
        """Block metadata provider for host-side planning: the resident DB,
        or the streaming engine's host layout (same arrays, numpy)."""
        return self.db if self.db is not None else self.engine.layout

    @property
    def _host_sidecars(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(pmz, charge, is_decoy) row sidecars as host numpy, fetched once —
        the resident DB holds them on device and neither the cascade's FDR
        grouping nor the dimension cascade's seed planning should pay a
        library-sized D2H copy per call."""
        cached = getattr(self, "_host_sidecars_cache", None)
        if cached is None:
            meta = self._block_meta
            cached = (np.asarray(meta.pmz), np.asarray(meta.charge),
                      np.asarray(meta.is_decoy))
            self._host_sidecars_cache = cached
        return cached

    def search_params(self, q_pmz, q_charge, *, exhaustive=False,
                      open_tol_da=None, backend=None, top_k=None,
                      prefix_words=None, prefix_margin=None,
                      prefix_seed_da=None) -> SearchParams:
        tol = self.cfg.open_tol_da if open_tol_da is None else open_tol_da
        k = plan_search(self._block_meta, np.asarray(q_pmz),
                        np.asarray(q_charge),
                        open_tol_da=tol, q_block=self.cfg.q_block)
        return SearchParams(
            ppm_tol=self.cfg.ppm_tol, open_tol_da=tol,
            q_block=self.cfg.q_block, k_blocks=k,
            backend=backend or self.cfg.backend, exhaustive=exhaustive,
            top_k=self.cfg.top_k if top_k is None else top_k,
            prefix_words=(self.cfg.prefix_words if prefix_words is None
                          else prefix_words),
            prefix_margin=(self.cfg.prefix_margin if prefix_margin is None
                           else prefix_margin),
            prefix_seed_da=(self.cfg.prefix_seed_da if prefix_seed_da is None
                            else prefix_seed_da))

    def search_encoded(self, hvs: jax.Array, q_pmz: jax.Array,
                       q_charge: jax.Array, *, exhaustive: bool = False,
                       open_tol_da: float | None = None,
                       backend: str | None = None,
                       top_k: int | None = None,
                       prefix_words: int | None = None,
                       prefix_margin: int | None = None) -> OMSOutput:
        """Search already-encoded query HVs (callers that hold the encoded
        batch — the serving launcher, rescoring loops — avoid re-encoding)."""
        with span("pipeline.search"):
            # One host conversion, shared by plan_search and the padding
            # plan — oms_search itself never syncs device->host.
            with span("pipeline.precursors_to_host"):
                qp_np = np.asarray(q_pmz)
                qc_np = np.asarray(q_charge)
            with span("pipeline.plan", queries=int(qp_np.shape[0])):
                params = self.search_params(
                    qp_np, qc_np, exhaustive=exhaustive,
                    open_tol_da=open_tol_da, backend=backend, top_k=top_k,
                    prefix_words=prefix_words, prefix_margin=prefix_margin)
            scan_span = span("pipeline.scan", backend=params.backend,
                             path="streamed" if self.engine is not None
                             else "resident")
            if self.engine is not None:
                with scan_span:
                    result = self.engine.search_encoded(
                        hvs, q_pmz, q_charge, params, dim=self.cfg.dim,
                        q_pmz_np=qp_np, q_charge_np=qc_np)
                # Decoy flags come from the host layout sidecar — the
                # streamed serve path never uploads library-sized arrays to
                # the device.
                isd_np = self.engine.layout.is_decoy
                n_rows = self.engine.layout.n_rows

                def _fdr(row, sim):
                    valid, isd = row_match_flags(row, isd_np, n_rows)
                    return fdr_filter(jnp.asarray(sim).astype(jnp.float32),
                                      jnp.asarray(isd), jnp.asarray(valid),
                                      threshold=self.cfg.fdr_threshold)
            else:
                row_meta = {}
                if params.prefix_words:
                    row_pmz, row_charge, _ = self._host_sidecars
                    row_meta = dict(row_pmz_np=row_pmz,
                                    row_charge_np=row_charge)
                with scan_span:
                    result = oms_search(self.db, hvs, q_pmz, q_charge, params,
                                        dim=self.cfg.dim, q_pmz_np=qp_np,
                                        q_charge_np=qc_np, **row_meta)

                def _fdr(row, sim):
                    valid = row >= 0
                    isd = (self.db.is_decoy[
                        jnp.clip(row, 0, self.db.n_rows - 1)] & valid)
                    return fdr_filter(sim.astype(jnp.float32), isd, valid,
                                      threshold=self.cfg.fdr_threshold)

            with span("pipeline.fdr"):
                open_fdr = _fdr(result.open_row, result.open_sim)
                std_fdr = _fdr(result.std_row, result.std_sim)
            return OMSOutput(result=result, open_fdr=open_fdr,
                             std_fdr=std_fdr)

    # ------------------------------------------------------------------
    # Cascaded narrow→open identification (see repro.core.cascade)
    # ------------------------------------------------------------------
    def search_cascade_encoded(self, hvs: jax.Array, q_pmz: jax.Array,
                               q_charge: jax.Array, *,
                               narrow_tol_da: float = 1.0,
                               run_stage1: bool = True,
                               exhaustive: bool = False,
                               backend: str | None = None,
                               top_k: int | None = None,
                               prefix_words: int | None = None,
                               prefix_margin: int | None = None,
                               stage1_per_query: bool = False) -> CascadeOutput:
        """Two-stage cascade over an encoded query batch: a narrow-window
        pass identifies unmodified spectra at the configured FDR, and only
        the fall-through queries pay for the full open scan. Works on both
        the resident DB and the streaming engine (where stage 1's slab
        pruning windows are far narrower, so far fewer slabs stream).

        With ``run_stage1=False`` the output is bit-identical to
        :meth:`search_encoded`'s pure open search — the cascade's stage 2
        simply runs on every query.

        ``stage1_per_query=True`` gates stage-1 identification per query
        (see :class:`repro.core.cascade.CascadeParams`) — the serve loop
        uses it so coalesced micro-batch composition cannot change any
        query's answer. The offline default keeps the corpus-level
        competition.

        ``prefix_words`` composes the dimension cascade into the open stage
        (stage 2) — the 2x2 of (mass window x dimension) stages. The narrow
        stage always scans full-width: its window is already only a handful
        of blocks, so a prefix pass there would add a seed round-trip for
        near-zero byte savings.
        """
        qp_np = np.asarray(q_pmz)
        qc_np = np.asarray(q_charge)
        meta = self._block_meta
        k = self.cfg.top_k if top_k is None else top_k

        def run_stage(sel: np.ndarray, *, narrow: bool):
          with span("pipeline.stage", stage="narrow" if narrow else "open",
                    queries=int(len(sel))):
            qp_s, qc_s = qp_np[sel], qc_np[sel]
            if narrow:
                # one plan_search per stage: the base params carry a
                # placeholder k_blocks that narrow_search_params replaces
                base = SearchParams(
                    ppm_tol=self.cfg.ppm_tol,
                    open_tol_da=self.cfg.open_tol_da,
                    q_block=self.cfg.q_block, k_blocks=1,
                    backend=backend or self.cfg.backend,
                    exhaustive=exhaustive, top_k=k)
                params = narrow_search_params(meta, qp_s, qc_s, base,
                                              narrow_tol_da=narrow_tol_da)
            else:
                params = self.search_params(qp_s, qc_s, exhaustive=exhaustive,
                                            backend=backend, top_k=k,
                                            prefix_words=prefix_words,
                                            prefix_margin=prefix_margin)
            sel_j = jnp.asarray(sel)
            hv_s, qp_d, qc_d = hvs[sel_j], q_pmz[sel_j], q_charge[sel_j]
            if self.engine is not None:
                res = self.engine.search_encoded(
                    hv_s, qp_d, qc_d, params, dim=self.cfg.dim,
                    q_pmz_np=qp_s, q_charge_np=qc_s)
                stats = self.engine.last_stats
            else:
                row_meta = {}
                if params.prefix_words:
                    row_pmz, row_charge, _ = self._host_sidecars
                    row_meta = dict(row_pmz_np=row_pmz,
                                    row_charge_np=row_charge)
                res = oms_search(self.db, hv_s, qp_d, qc_d, params,
                                 dim=self.cfg.dim, q_pmz_np=qp_s,
                                 q_charge_np=qc_s, **row_meta)
                stats = None
            return res, scanned_rows(meta, len(sel), params), stats

        if run_stage1 and not narrow_tol_da < self.cfg.open_tol_da:
            raise ValueError(
                f"narrow_tol_da={narrow_tol_da!r} must be < the open window "
                f"({self.cfg.open_tol_da} Da) for the cascade to prune")
        cparams = CascadeParams(narrow_tol_da=narrow_tol_da,
                                fdr_threshold=self.cfg.fdr_threshold,
                                run_stage1=run_stage1,
                                stage1_per_query=stage1_per_query)
        row_pmz, _, row_isd = self._host_sidecars
        return cascade_search(
            run_stage, qp_np, top_k=k, row_pmz=row_pmz, row_is_decoy=row_isd,
            n_rows=meta.n_rows, params=cparams)

    def search_cascade(self, queries: SpectraSet, *,
                       narrow_tol_da: float = 1.0, run_stage1: bool = True,
                       exhaustive: bool = False, backend: str | None = None,
                       top_k: int | None = None,
                       stage1_per_query: bool = False) -> CascadeOutput:
        hvs, q_pmz, q_charge = self.encode_queries(queries)
        return self.search_cascade_encoded(
            hvs, q_pmz, q_charge, narrow_tol_da=narrow_tol_da,
            run_stage1=run_stage1, exhaustive=exhaustive, backend=backend,
            top_k=top_k, stage1_per_query=stage1_per_query)

    def pure_open_scanned_rows(self, n_queries: int, q_pmz, q_charge, *,
                               exhaustive: bool = False) -> int:
        """Static comparison-row count a single-stage open search of this
        batch would pay — the baseline the cascade's
        ``scanned_rows_total`` is measured against."""
        params = self.search_params(np.asarray(q_pmz), np.asarray(q_charge),
                                    exhaustive=exhaustive)
        return scanned_rows(self._block_meta, n_queries, params)

    def search(self, queries: SpectraSet, *, exhaustive: bool = False,
               open_tol_da: float | None = None,
               backend: str | None = None,
               top_k: int | None = None,
               prefix_words: int | None = None,
               prefix_margin: int | None = None) -> OMSOutput:
        hvs, q_pmz, q_charge = self.encode_queries(queries)
        return self.search_encoded(hvs, q_pmz, q_charge,
                                   exhaustive=exhaustive,
                                   open_tol_da=open_tol_da, backend=backend,
                                   top_k=top_k, prefix_words=prefix_words,
                                   prefix_margin=prefix_margin)

    # convenience for quality benchmarks -------------------------------
    def identifications(self, out: OMSOutput) -> int:
        return int(out.open_fdr.n_accepted)
