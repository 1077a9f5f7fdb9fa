"""Encoder-backend registry for the ID-Level HD encoder (paper §II-A).

Mirror of :mod:`repro.core.backends` (the search-backend registry) for the
other half of the paper's Fig. 1b hot path. Two backend kinds:

  * ``encode`` — consumes *preprocessed* spectra. Signature:
    ``fn(spectra: PreprocessedSpectra, cb: Codebooks) -> (B, W) uint32``
    packed HVs. The chunked batch loop lives in
    :func:`repro.core.encoding.encode_spectra_batched`.
  * ``fused`` — consumes *raw* peak arrays and runs preprocess + encode as
    one jitted chunk loop, so nothing round-trips through HBM between the
    stages. Signature: ``fn(mz, intensity, pmz, charge, cb, *, pp, batch)
    -> (hvs, pmz, charge)``.

Built-in backends:

  name        kind    engine / peak unpacked-bit intermediate
  ----------  ------  -----------------------------------------------------
  oracle      encode  pure-jnp reference; materialises (batch, P, D) bits
  word_tiled  encode  jnp, Dhv looped in word tiles; (batch, P, WT*32) bits
  pallas      encode  Pallas hdencode kernel (VMEM word tiles; interpret
                      mode only, refused where kernels compile);
                      (spectra_tile, P, WT*32) bits
  fused       fused   preprocess + word-tiled encode in ONE jit per chunk

Every backend is required — and tested (tests/test_encode_backends.py) — to
be bit-identical to ``oracle``, ties, masked rows and padding included, so
:class:`~repro.store.LibraryStore` ingests are byte-identical no matter
which backend wrote them. Register custom backends with :func:`register`;
kernels are imported lazily inside the backend fn so importing this module
stays cheap.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax

# Dependency-free registry (stdlib only) — safe at module level, checked by
# `oms.py analyze --imports`.
from repro.analysis.registry import declare as _declare
from repro.core import encoding
from repro.core.encoding import (Codebooks, PreprocessParams,
                                 PreprocessedSpectra)

ENCODE = "encode"
FUSED = "fused"


@dataclasses.dataclass(frozen=True)
class EncodeBackend:
    name: str
    kind: str          # ENCODE | FUSED
    fn: Callable


_REGISTRY: dict[str, EncodeBackend] = {}


def register(name: str, kind: str, fn: Callable) -> EncodeBackend:
    if kind not in (ENCODE, FUSED):
        raise ValueError(f"encode backend kind must be {ENCODE!r} or "
                         f"{FUSED!r}, got {kind!r}")
    be = EncodeBackend(name=name, kind=kind, fn=fn)
    _REGISTRY[name] = be
    return be


def get(name: str) -> EncodeBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown encode backend {name!r}; registered: {', '.join(names())}"
        ) from None


def names(kind: str | None = None) -> tuple[str, ...]:
    return tuple(n for n, b in _REGISTRY.items()
                 if kind is None or b.kind == kind)


# ---------------------------------------------------------------------------
# Top-level dispatch: raw peaks -> packed HVs (+ float32 pmz / int32 charge)
# ---------------------------------------------------------------------------


def _preprocess(mz, intensity, pmz, charge, pp: PreprocessParams
                ) -> PreprocessedSpectra:
    return encoding.preprocess_spectra(
        mz, intensity, pmz, charge, bin_size=pp.bin_size, mz_min=pp.mz_min,
        mz_max=pp.mz_max, n_levels=pp.n_levels,
        min_intensity_frac=pp.min_intensity_frac)


# Jitted-once copies for the serving/ingest hot path: without them every
# encode_queries call and ingest chunk would re-trace the chunk loop and
# pay per-op dispatch for preprocessing (~40x per-call overhead at small
# batches). The encode body is pure integer arithmetic, so jitting cannot
# change results; preprocessing is jit-safe since the bin reciprocal is
# host-hoisted (eager and jitted programs compile the same multiply — see
# preprocess_spectra, and the bin-boundary parity test). The eager
# `encoding` functions stay as the composable API.
_preprocess_jit = jax.jit(_preprocess, static_argnames=("pp",))
_encode_batched_jit = jax.jit(encoding.encode_spectra_batched,
                              static_argnames=("batch", "backend"))


def preprocess_encode(mz, intensity, pmz, charge, cb: Codebooks,
                      pp: PreprocessParams, *, backend: str = "oracle",
                      batch: int = 512):
    """Preprocess + encode a raw spectrum batch through ``backend``.

    The single entry point the pipeline uses for queries and library chunks
    alike. Returns ``(hvs, pmz, charge)`` with hvs packed (B, W) uint32.
    """
    be = get(backend)
    if be.kind == FUSED:
        return be.fn(mz, intensity, pmz, charge, cb, pp=pp, batch=batch)
    pre = _preprocess_jit(mz, intensity, pmz, charge, pp)
    hvs = _encode_batched_jit(pre, cb, batch=batch, backend=backend)
    return hvs, pre.pmz, pre.charge


# ---------------------------------------------------------------------------
# Built-ins
# ---------------------------------------------------------------------------


def _word_tiled(spectra: PreprocessedSpectra, cb: Codebooks):
    return encoding.encode_spectra_word_tiled(spectra, cb)


def _pallas(spectra: PreprocessedSpectra, cb: Codebooks):
    from repro.kernels.hdencode import ops as eops
    return eops.hdencode(spectra.bins, spectra.levels, spectra.mask,
                         cb.id_hvs, cb.level_hvs, cb.tiebreak)


@partial(jax.jit, static_argnames=("pp", "batch"))
def _fused_preprocess_encode(mz, intensity, pmz, charge, cb: Codebooks, *,
                             pp: PreprocessParams, batch: int):
    """One jit over the shared chunk loop (``encoding.chunked_batch_map``)
    with a fused preprocess -> word-tiled-encode chunk body. Padding rows
    (zero intensity) preprocess to all-masked spectra and are sliced off."""

    def one_chunk(args):
        m, i, p, c = args
        pre = _preprocess(m, i, p, c, pp)
        return (encoding.encode_spectra_word_tiled(pre, cb),
                pre.pmz, pre.charge)

    return encoding.chunked_batch_map(
        one_chunk, (mz, intensity, pmz, charge), batch)


register("oracle", ENCODE, encoding.encode_spectra)
register("word_tiled", ENCODE, _word_tiled)
register("pallas", ENCODE, _pallas)
register("fused", FUSED, _fused_preprocess_encode)


# ---------------------------------------------------------------------------
# Contracts — the encode hot path's memory/transfer/dtype story, declared
# next to the registrations and machine-checked by `oms.py analyze` (the
# runner traces preprocess_encode per backend; see repro.analysis).
# ---------------------------------------------------------------------------

for _t in ("encode:oracle", "encode:word_tiled", "encode:pallas",
           "encode:fused"):
    _declare(_t, "no_host_transfer")
    _declare(_t, "dtype_stability")

# Peak device intermediate of one encode chunk, over the trace context
# (batch = spectra per chunk, peaks, dim, word_tile, n_bins). The oracle is
# ALLOWED its (B, P, D) unpacked-bit tensor — that is what makes it the
# oracle; the production schedules must stay word-tile-bounded:
# (B, P, WT*32) int32. The word-tiled schedules also reshape the resident
# ID codebook into word tiles — an (n_bins, W/WT, WT) view of an INPUT, so
# the codebook's own footprint (already paid to hold it) is part of every
# word-tiled bound, never a schedule blowup.


def _codebook_bytes(c) -> int:
    return c["n_bins"] * c["n_words"] * 4


def _word_tile_bound(c):
    return max(c["batch"] * c["peaks"] * c["word_tile"] * 32 * 4,
               _codebook_bytes(c))


_declare("encode:oracle", "peak_intermediate",
         bound=lambda c: max(c["batch"] * c["peaks"] * c["dim"] * 4,
                             _codebook_bytes(c)),
         note="reference schedule: full (B, P, D) unpacked bits")
for _t in ("encode:word_tiled", "encode:fused"):
    _declare(_t, "peak_intermediate", bound=_word_tile_bound,
             note="word-tiled schedule: (B, P, WT*32) unpacked-bit tile "
                  "or the word-tiled codebook view")
_declare("encode:pallas", "peak_intermediate",
         bound=lambda c: max(c["batch"] * c["peaks"]
                             * c["word_tile"] * 32 * 4,
                             _codebook_bytes(c)),
         note="hdencode kernel: codebooks stream to VMEM word tiles; "
              "outside-kernel intermediates stay tile-bounded")
