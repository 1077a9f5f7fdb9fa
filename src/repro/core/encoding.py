"""ID–Level hyperdimensional encoding of mass spectra (paper §II-A, Fig. 3).

Pipeline (faithful to the paper):
  1. *Preprocess*: drop peaks < 1% of the base peak, bin m/z at ``bin_size``,
     merge intensities within a bin, sqrt-scale + renormalise, quantise
     intensity into ``n_levels`` discrete levels.
  2. *Encode*: for each surviving peak, bind (XOR) the bin's ID hypervector
     with the level's Level hypervector; bundle all bound peak HVs with a
     bitwise majority; binarise. Result: one Dhv-bit HV per spectrum.

Similarity between encoded spectra is Hamming distance (see packing.py).

Codebooks:
  * ID HVs: i.i.d. random binary — bins are unrelated, so their HVs are
    ~orthogonal.
  * Level HVs: linearly correlated chain — L[0] random, each next level flips
    a fresh slice of a fixed random permutation so adjacent intensities stay
    similar while L[0] ⟂ L[last] (standard ID-Level construction [VoiceHD]).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packing import pack_bits, unpack_bits

# ---------------------------------------------------------------------------
# Codebooks
# ---------------------------------------------------------------------------


class Codebooks(NamedTuple):
    """Packed codebooks + majority tie-break vector."""

    id_hvs: jax.Array      # (n_bins, W) uint32 — per-m/z-bin ID hypervectors
    level_hvs: jax.Array   # (n_levels, W) uint32 — intensity Level hypervectors
    tiebreak: jax.Array    # (W,) uint32 — random HV deciding even-count majority ties
    dim: int


def make_codebooks(key: jax.Array, n_bins: int, n_levels: int, dim: int) -> Codebooks:
    k_id, k_base, k_perm, k_tie = jax.random.split(key, 4)
    id_bits = jax.random.bernoulli(k_id, 0.5, (n_bins, dim)).astype(jnp.uint8)

    base = jax.random.bernoulli(k_base, 0.5, (dim,)).astype(jnp.uint8)
    perm = jax.random.permutation(k_perm, dim)
    # Level q flips the first q * dim/(2*(n_levels-1)) positions of `perm`
    # (cumulative), so L[0] and L[n_levels-1] differ in dim/2 bits.
    flips_per_level = dim // (2 * max(n_levels - 1, 1))
    qs = jnp.arange(n_levels)[:, None]                        # (L, 1)
    rank = jnp.argsort(perm)                                  # position -> rank in perm
    flip_mask = rank[None, :] < qs * flips_per_level          # (L, D)
    level_bits = jnp.bitwise_xor(base[None, :], flip_mask.astype(jnp.uint8))

    tie_bits = jax.random.bernoulli(k_tie, 0.5, (dim,)).astype(jnp.uint8)
    return Codebooks(
        id_hvs=pack_bits(id_bits),
        level_hvs=pack_bits(level_bits),
        tiebreak=pack_bits(tie_bits),
        dim=dim,
    )


# ---------------------------------------------------------------------------
# Preprocessing (peaks -> (bin, level, mask) triples)
# ---------------------------------------------------------------------------


class PreprocessParams(NamedTuple):
    """Static preprocessing knobs, hashable so fused preprocess->encode jits
    can take them as one static argument."""

    bin_size: float
    mz_min: float
    mz_max: float
    n_levels: int
    min_intensity_frac: float = 0.01


class PreprocessedSpectra(NamedTuple):
    bins: jax.Array    # (B, P) int32 — m/z bin index per peak (0 where masked)
    levels: jax.Array  # (B, P) int32 — intensity level per peak
    mask: jax.Array    # (B, P) bool — valid-peak mask
    pmz: jax.Array     # (B,) float32 — precursor m/z
    charge: jax.Array  # (B,) int32 — precursor charge


def preprocess_spectra(
    mz: jax.Array,           # (B, P) float32 — peak m/z (0 padded)
    intensity: jax.Array,    # (B, P) float32 — peak intensity (0 padded)
    pmz: jax.Array,          # (B,)
    charge: jax.Array,       # (B,)
    *,
    bin_size: float,
    mz_min: float,
    mz_max: float,
    n_levels: int,
    min_intensity_frac: float = 0.01,
) -> PreprocessedSpectra:
    """Vectorised spectrum preprocessing. Padded peaks carry intensity 0."""
    valid = (intensity > 0) & (mz >= mz_min) & (mz < mz_max)
    inten = jnp.where(valid, intensity, 0.0)

    # 1% base-peak noise filter (paper: "filtering out peaks with intensities
    # below 1% of the highest peak").
    base = jnp.max(inten, axis=-1, keepdims=True)
    valid = valid & (inten >= min_intensity_frac * base)
    inten = jnp.where(valid, inten, 0.0)

    # m/z binning. NOTE: intensities of peaks landing in the same bin are
    # combined implicitly at encode time (bound HVs of identical (bin, level)
    # bundle like a single heavier peak); for level assignment we use the
    # per-peak intensity, matching the HyperOMS-style vectorisation.
    # The reciprocal is hoisted to the host: a division by a non-constant-
    # folded literal compiles differently eager vs jitted (XLA strength-
    # reduces x/const to x*(1/const) only under jit), which flips peaks
    # sitting exactly on bin boundaries — encode backends must be bit-exact
    # whether preprocessing runs eagerly or inside a fused jit.
    n_bins = int(round((mz_max - mz_min) / bin_size))
    inv_bin = np.float32(1.0 / bin_size)
    bins = jnp.clip(((mz - mz_min) * inv_bin).astype(jnp.int32), 0, n_bins - 1)

    # sqrt scaling + per-spectrum max-normalisation, then quantise to levels.
    scaled = jnp.sqrt(inten)
    smax = jnp.maximum(jnp.max(scaled, axis=-1, keepdims=True), 1e-9)
    levels = jnp.clip(
        (scaled / smax * (n_levels - 1) + 0.5).astype(jnp.int32), 0, n_levels - 1
    )

    return PreprocessedSpectra(
        bins=jnp.where(valid, bins, 0),
        levels=jnp.where(valid, levels, 0),
        mask=valid,
        pmz=pmz.astype(jnp.float32),
        charge=charge.astype(jnp.int32),
    )


# ---------------------------------------------------------------------------
# Encoding (bind + bundle + binarise).
#
# ``encode_spectra`` is the bit-exact ORACLE; production dispatch goes through
# the backend registry in :mod:`repro.core.encode_backends`:
#   * ``word_tiled`` — :func:`encode_spectra_word_tiled`, bounded unpacked
#     intermediate (the default production path);
#   * ``pallas`` — the repro.kernels.hdencode Pallas kernel, dispatched from
#     :func:`encode_spectra_batched` (interpret mode on the CPU only);
#   * ``fused`` — one jitted preprocess->encode chunk loop.
# All backends are required (and tested) to be bit-identical to the oracle,
# ties, masked rows and padding included.
# ---------------------------------------------------------------------------


def _encode_counts(bins, levels, mask, cb: Codebooks) -> jax.Array:
    """Per-bit set-count over bound peak HVs. Returns (B, D) int32 + n (B,)."""
    id_rows = cb.id_hvs[bins]          # (B, P, W) uint32
    lvl_rows = cb.level_hvs[levels]    # (B, P, W)
    bound = jnp.bitwise_xor(id_rows, lvl_rows)
    bits = unpack_bits(bound)          # (B, P, D) uint8
    counts = jnp.sum(bits.astype(jnp.int32) * mask[..., None].astype(jnp.int32), axis=1)
    return counts


def _binarise_majority(counts, n, tie_bits) -> jax.Array:
    """Majority rule shared by every encode path: bit d is 1 iff
    2*counts_d > n; exact ties take the tiebreak bit. counts (B, D'),
    n (B, 1), tie_bits (1, D') int32 -> packed (B, D'/32) uint32. ONE copy
    on purpose — bit-identity across backends is a tested contract."""
    twice = 2 * counts
    bits = jnp.where(twice == n, tie_bits, (twice > n).astype(jnp.int32))
    return pack_bits(bits.astype(jnp.uint8))


def encode_spectra(spectra: PreprocessedSpectra, cb: Codebooks) -> jax.Array:
    """Encode a batch of preprocessed spectra into packed HVs (B, W) uint32.

    Majority rule: bit d is 1 iff 2*count_d > n_peaks; exact ties are broken
    by the codebook's fixed tie-break HV (deterministic, shared by queries and
    references).
    """
    counts = _encode_counts(spectra.bins, spectra.levels, spectra.mask, cb)
    n = jnp.sum(spectra.mask, axis=-1, dtype=jnp.int32)[:, None]
    tie = unpack_bits(cb.tiebreak)[None, :].astype(jnp.int32)  # (1, D)
    return _binarise_majority(counts, n, tie)


def encode_spectra_word_tiled(spectra: PreprocessedSpectra, cb: Codebooks,
                              *, word_tile: int = 8) -> jax.Array:
    """Bit-exact oracle rewrite that loops the Dhv word dimension in fixed
    tiles (the paper's FACTOR knob, as a schedule): the unpacked-bit
    intermediate is bounded by (B, P, word_tile*32) instead of (B, P, D).

    If W is not a multiple of ``word_tile`` the codebook columns are padded
    with zero words; the padded output columns are sliced off, so results are
    independent of the tile size.
    """
    W = cb.id_hvs.shape[1]
    wt = min(word_tile, W)
    padw = (-W) % wt

    def _padc(x):
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, padw)]) if padw else x

    nt = (W + padw) // wt
    ids = _padc(cb.id_hvs).reshape(-1, nt, wt).transpose(1, 0, 2)     # (nt, F, wt)
    lvls = _padc(cb.level_hvs).reshape(-1, nt, wt).transpose(1, 0, 2)  # (nt, L, wt)
    tie = _padc(cb.tiebreak).reshape(nt, wt)
    mask_i = spectra.mask.astype(jnp.int32)
    n = jnp.sum(mask_i, axis=-1)[:, None]                              # (B, 1)

    def one_tile(cols):
        idc, lvc, tic = cols
        bound = jnp.bitwise_xor(idc[spectra.bins], lvc[spectra.levels])
        bits = unpack_bits(bound).astype(jnp.int32)        # (B, P, wt*32)
        counts = jnp.sum(bits * mask_i[..., None], axis=1)
        tie_bits = unpack_bits(tic)[None, :].astype(jnp.int32)
        return _binarise_majority(counts, n, tie_bits)     # (B, wt)

    out = jax.lax.map(one_tile, (ids, lvls, tie))          # (nt, B, wt)
    B = spectra.bins.shape[0]
    return out.transpose(1, 0, 2).reshape(B, nt * wt)[:, :W]


def chunked_batch_map(fn, tree, batch: int):
    """Pad every leaf's leading dim to a ``batch`` multiple, ``lax.map``
    ``fn`` over the (n_chunks, batch, ...) reshape, slice outputs back to
    the true row count. The ONE copy of the chunking schedule that every
    encode path (batched and fused alike) shares — keeping it single is
    part of the backends' bit-exactness contract. ``None`` leaves (e.g.
    absent pmz/charge) pass through untouched.
    """
    B = jax.tree_util.tree_leaves(tree)[0].shape[0]
    pad = (-B) % batch

    def _pad(x):
        return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)) if pad else x

    chunks = jax.tree_util.tree_map(
        lambda x: _pad(x).reshape(-1, batch, *x.shape[1:]), tree)
    out = jax.lax.map(fn, chunks)
    return jax.tree_util.tree_map(
        lambda y: y.reshape(-1, *y.shape[2:])[:B], out)


def encode_spectra_batched(spectra: PreprocessedSpectra, cb: Codebooks,
                           batch: int = 512,
                           backend: str = "oracle") -> jax.Array:
    """Memory-bounded encode for large libraries (maps encode over chunks).

    ``backend`` selects a per-chunk encoder from
    :mod:`repro.core.encode_backends` (any ``ENCODE``-kind name: ``oracle``,
    ``word_tiled``, ``pallas``, ...); all are bit-identical, only the
    schedule and peak intermediate footprint differ.
    """
    from repro.core import encode_backends

    be = encode_backends.get(backend)
    if be.kind != encode_backends.ENCODE:
        raise ValueError(
            f"encode_spectra_batched needs an {encode_backends.ENCODE!r}-kind "
            f"backend (got {backend!r}, kind {be.kind!r}); fused backends "
            "start from raw peaks — use encode_backends.preprocess_encode")
    return chunked_batch_map(lambda s: be.fn(s, cb), spectra, batch)
