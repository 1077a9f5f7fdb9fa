"""Plain reference of what a cell's timed path produces.

It imports nothing of the program and takes nothing the program made. From
the benchmark's own seeded spectra it rebuilds the library the way the
configuration defines it, and answers each checked query by brute force:

* codebooks: ID hypervectors i.i.d. Bernoulli(1/2) per m/z bin; a chain of
  Level hypervectors in which each level flips a further ``dim / (2 (L-1))``
  positions of a random permutation; a random tie-break vector. All drawn
  from ``jax.random.PRNGKey(codebook_seed)`` split as (codebook, decoy) and
  then (id, base, permutation, tie-break);
* decoys: every target's peaks moved to uniform random m/z in
  [mz_min, mz_max), keyed by ``fold_in(decoy_key, target_index)``, with the
  target's intensities, precursor mass and charge;
* preprocessing: peaks outside [mz_min, mz_max) or under 1% of the base peak
  dropped, m/z binned at ``bin_size``, sqrt intensities scaled to the
  spectrum's maximum and rounded to ``n_levels`` levels;
* encoding: per position, a majority over the peaks of (ID XOR Level), with
  the tie-break bit on an exact tie;
* search: for each query, every library row of its charge whose precursor
  mass lies within ``open_tol_da`` (open window) or within ``ppm_tol`` parts
  per million of the query's (standard window), float32 arithmetic;
  similarity ``dim - hamming``; the ``top_k`` best per window ranked by
  (similarity desc, then library order: charge, precursor mass, library
  index with decoys after targets), -1 past the candidates;
* FDR: target-decoy competition over all (query, rank) matches of a window,
  q-value = running decoys / targets, clipped at 1 and made monotone from
  the bottom; a target match is accepted at q <= the threshold.

The float steps (decoy m/z, preprocessing, q-values) are written as the
configuration states them and run on the same device as the program, since
rounding can differ between devices. Everything else is integer arithmetic.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Library(NamedTuple):
    hvs: jax.Array        # (N, W) uint32, sorted in library order
    pmz: np.ndarray       # (N,) f32 host, sorted
    charge: np.ndarray    # (N,) i32 host, sorted
    idx: np.ndarray       # (N,) i32 host: target i -> i, decoy i -> n + i
    pmz_d: jax.Array
    charge_d: jax.Array


class Codebooks(NamedTuple):
    id_words: jax.Array     # (n_bins, W) uint32
    level_words: jax.Array  # (L, W) uint32
    tie_bits: jax.Array     # (W, 32) int32
    decoy_key: jax.Array


def _pack(bits):
    """(..., D) bool/int -> (..., D/32) uint32, position 32 w + j in bit j
    of word w."""
    b = bits.astype(jnp.uint32).reshape(*bits.shape[:-1], -1, 32)
    return jnp.sum(b << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


@partial(jax.jit, static_argnames=("n_bins", "n_levels", "dim"))
def _codebooks(seed, *, n_bins: int, n_levels: int, dim: int) -> Codebooks:
    k_cb, k_dec = jax.random.split(jax.random.PRNGKey(seed))
    k_id, k_base, k_perm, k_tie = jax.random.split(k_cb, 4)
    id_bits = jax.random.bernoulli(k_id, 0.5, (n_bins, dim))
    base = jax.random.bernoulli(k_base, 0.5, (dim,))
    perm = jax.random.permutation(k_perm, dim)
    flips = dim // (2 * max(n_levels - 1, 1))
    rank = jnp.argsort(perm)
    level_bits = base[None, :] ^ (rank[None, :]
                                  < jnp.arange(n_levels)[:, None] * flips)
    tie = jax.random.bernoulli(k_tie, 0.5, (dim,)).astype(jnp.int32)
    return Codebooks(_pack(id_bits), _pack(level_bits),
                     tie.reshape(dim // 32, 32), k_dec)


def codebooks(seed: int, enc: dict) -> Codebooks:
    n_bins = int(round((enc["mz_max"] - enc["mz_min"]) / enc["bin_size"]))
    return _codebooks(jnp.uint32(seed), n_bins=n_bins,
                      n_levels=enc["n_levels"], dim=enc["dim"])


def decoy_peaks(key, mz, intensity, mz_min: float, mz_max: float,
                row_offset: int):
    """Decoy peaks of targets ``row_offset ...``: run op by op, as stated."""
    B, P = mz.shape
    rows = jnp.arange(B, dtype=jnp.uint32) + jnp.uint32(row_offset)
    keys = jax.vmap(lambda r: jax.random.fold_in(key, r))(rows)
    new_mz = jax.vmap(
        lambda k: jax.random.uniform(k, (P,), minval=mz_min, maxval=mz_max,
                                     dtype=mz.dtype))(keys)
    return jnp.where(intensity > 0, new_mz, 0.0), intensity


@partial(jax.jit, static_argnames=("bin_size", "mz_min", "mz_max",
                                   "n_levels"))
def _bins_levels(mz, intensity, *, bin_size: float, mz_min: float,
                 mz_max: float, n_levels: int):
    valid = (intensity > 0) & (mz >= mz_min) & (mz < mz_max)
    inten = jnp.where(valid, intensity, 0.0)
    base = jnp.max(inten, axis=-1, keepdims=True)
    valid = valid & (inten >= 0.01 * base)
    inten = jnp.where(valid, inten, 0.0)
    n_bins = int(round((mz_max - mz_min) / bin_size))
    inv_bin = np.float32(1.0 / bin_size)
    bins = jnp.clip(((mz - mz_min) * inv_bin).astype(jnp.int32), 0,
                    n_bins - 1)
    scaled = jnp.sqrt(inten)
    smax = jnp.maximum(jnp.max(scaled, axis=-1, keepdims=True), 1e-9)
    levels = jnp.clip(
        (scaled / smax * (n_levels - 1) + 0.5).astype(jnp.int32), 0,
        n_levels - 1)
    return bins, levels, valid


@partial(jax.jit, static_argnames=("block",))
def _majority(bins, levels, valid, cb: Codebooks, *, block: int):
    """Packed hypervectors of spectra given as (bin, level, valid) peaks."""
    shifts = jnp.arange(32, dtype=jnp.uint32)

    def one(args):
        b, lv, m = args
        bound = cb.id_words[b] ^ cb.level_words[lv]              # (R, P, W)
        bits = ((bound[..., None] >> shifts) & 1).astype(jnp.int32)
        counts = jnp.sum(bits * m[:, :, None, None].astype(jnp.int32),
                         axis=1)                                 # (R, W, 32)
        n = jnp.sum(m, axis=-1, dtype=jnp.int32)[:, None, None]
        out = jnp.where(2 * counts == n, cb.tie_bits[None], 2 * counts > n)
        return _pack(out.reshape(out.shape[0], -1))

    B = bins.shape[0]
    split = lambda x: x.reshape(B // block, block, *x.shape[1:])  # noqa: E731
    out = jax.lax.map(one, (split(bins), split(levels), split(valid)))
    return out.reshape(B, -1)


def encode(mz, intensity, cb: Codebooks, enc: dict, *, block: int = 128):
    """Packed hypervectors (B, dim/32) of raw spectra (B, P)."""
    B = mz.shape[0]
    pad = (-B) % block
    if pad:
        mz = jnp.pad(mz, ((0, pad), (0, 0)))
        intensity = jnp.pad(intensity, ((0, pad), (0, 0)))
    bins, levels, valid = _bins_levels(
        mz, intensity, bin_size=enc["bin_size"], mz_min=enc["mz_min"],
        mz_max=enc["mz_max"], n_levels=enc["n_levels"])
    return _majority(bins, levels, valid, cb, block=block)[:B]


def build_library(refs, cb: Codebooks, enc: dict, *, add_decoys: bool,
                  chunk_rows: int) -> Library:
    """Targets, then decoys, encoded and put in library order."""
    n = int(refs.mz.shape[0])
    parts = []
    for decoy in ((False, True) if add_decoys else (False,)):
        for s in range(0, n, chunk_rows):
            e = min(s + chunk_rows, n)
            mz, inten = refs.mz[s:e], refs.intensity[s:e]
            if decoy:
                mz, inten = decoy_peaks(cb.decoy_key, mz, inten,
                                        enc["mz_min"], enc["mz_max"], s)
            parts.append(encode(mz, inten, cb, enc))
    hvs = jnp.concatenate(parts)
    del parts
    reps = 2 if add_decoys else 1
    pmz = np.tile(np.asarray(refs.pmz, np.float32), reps)
    charge = np.tile(np.asarray(refs.charge, np.int32), reps)
    idx = np.arange(reps * n, dtype=np.int32)
    order = np.lexsort((idx, pmz, charge))
    return Library(hvs=hvs[jnp.asarray(order)], pmz=pmz[order],
                   charge=charge[order], idx=idx[order],
                   pmz_d=jnp.asarray(pmz[order]),
                   charge_d=jnp.asarray(charge[order]))


@partial(jax.jit, static_argnames=("width", "dim", "ppm_tol", "open_tol_da",
                                   "top_k"))
def _scan(lib_hvs, lib_pmz, lib_charge, q_hvs, q_pmz, q_charge, starts, *,
          width: int, dim: int, ppm_tol: float, open_tol_da: float,
          top_k: int):
    def one(args):
        qh, qp, qc, s = args
        rows = jax.lax.dynamic_slice(lib_hvs, (s, 0), (width, qh.shape[0]))
        pmz = jax.lax.dynamic_slice(lib_pmz, (s,), (width,))
        charge = jax.lax.dynamic_slice(lib_charge, (s,), (width,))
        sim = dim - jnp.sum(jax.lax.population_count(rows ^ qh[None, :]),
                            axis=-1, dtype=jnp.int32)
        dp = jnp.abs(qp - pmz)
        same = charge == qc
        open_m = same & (dp <= np.float32(open_tol_da))
        std_m = same & (dp <= qp * np.float32(ppm_tol * 1e-6))
        out = []
        for m in (std_m, open_m):
            v, i = jax.lax.top_k(jnp.where(m, sim, -1), top_k)
            out += [v, jnp.where(v >= 0, s + i, -1)]
        return tuple(out)

    return jax.lax.map(one, (q_hvs, q_pmz, q_charge, starts))


class Answers(NamedTuple):
    """(S, top_k) int32: library index (-1 none) and similarity (-1 none)."""
    std_idx: np.ndarray
    std_sim: np.ndarray
    open_idx: np.ndarray
    open_sim: np.ndarray


def search(lib: Library, q_hvs, q_pmz, q_charge, *, dim: int,
           ppm_tol: float, open_tol_da: float, top_k: int,
           block: int = 256) -> Answers:
    q_pmz = np.asarray(q_pmz, np.float32)
    q_charge = np.asarray(q_charge, np.int32)
    N = lib.pmz.shape[0]
    # candidate row ranges: the open window widened by 1 Da; the exact
    # float32 tests inside the scan decide
    key = lib.charge.astype(np.float64) * 1e5 + lib.pmz
    qk = q_charge.astype(np.float64) * 1e5 + q_pmz
    lo = np.searchsorted(key, qk - open_tol_da - 1.0, side="left")
    hi = np.searchsorted(key, qk + open_tol_da + 1.0, side="right")
    width = int(max(1, (hi - lo).max(initial=1), top_k))
    width = min(-(-width // 1024) * 1024, N)
    starts = np.clip(lo, 0, N - width).astype(np.int32)
    outs = []
    for s in range(0, len(q_pmz), block):
        e = min(s + block, len(q_pmz))
        outs.append(jax.device_get(_scan(
            lib.hvs, lib.pmz_d, lib.charge_d, q_hvs[s:e],
            jnp.asarray(q_pmz[s:e]), jnp.asarray(q_charge[s:e]),
            jnp.asarray(starts[s:e]), width=width, dim=dim,
            ppm_tol=ppm_tol, open_tol_da=open_tol_da, top_k=top_k)))
    ss, sp, os_, op = (np.concatenate([o[i] for o in outs]) for i in range(4))

    def to_idx(pos):
        return np.where(pos >= 0, lib.idx[np.clip(pos, 0, N - 1)], -1)

    return Answers(to_idx(sp), ss, to_idx(op), os_)


@jax.jit
def _q_values(scores, is_decoy, valid):
    shape = scores.shape
    scores, is_decoy, valid = (x.reshape(-1) for x in
                               (scores, is_decoy, valid))
    s = jnp.where(valid, scores.astype(jnp.float32),
                  jnp.finfo(jnp.float32).min)
    order = jnp.argsort(-s, stable=True)
    d = is_decoy[order].astype(jnp.float32)
    v = valid[order].astype(jnp.float32)
    cum_decoy = jnp.cumsum(d * v)
    cum_target = jnp.cumsum((1.0 - d) * v)
    fdr = jnp.minimum(cum_decoy / jnp.maximum(cum_target, 1.0), 1.0)
    q_sorted = jnp.flip(jax.lax.cummin(jnp.flip(fdr)))
    q = jnp.zeros_like(fdr).at[order].set(q_sorted)
    return jnp.where(valid, q, 1.0).reshape(shape)


def fdr_accept(idx, sim, n_targets: int, threshold: float) -> np.ndarray:
    """Accepted matches of one window, given every query's (Q, k) matches."""
    idx = jnp.asarray(idx)
    valid = idx >= 0
    decoy = valid & (idx >= n_targets)
    q = _q_values(jnp.asarray(sim), decoy, valid)
    return np.asarray(valid & ~decoy & (q <= threshold))
