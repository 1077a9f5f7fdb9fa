"""Seeded synthetic spectral library and queries: the benchmark's own copy.

A copy of the generator in ``repro.data.spectra`` (``make_dataset``), kept
here so that a change to the program cannot change the benchmark's inputs.
It draws the same numbers as the original for the same parameters (a test
holds the two equal at a small size), and runs as one jitted call on the
device.

With a ``layout_seed`` the precursor layout, which sets the work of a
search, is drawn from that seed instead: every reference's precursor mass
and charge, and every query's source reference, modification and shift. The
run's seed still draws every peak and every noise term, and deals the
layout out in another order, permuting the references and the queries. So
every seed searches the same multiset of precursors with different spectra,
and the blocked scan's extent (``k_blocks``) does not change with the seed.

References are random fragment ladders (24-64 peaks over [mz_min, mz_max),
exponential intensities, precursor mass uniform in [pmz_min, pmz_max),
charge drawn from ``charges``). Queries are noisy replicas of random
references; ``modified_frac`` of them carry a precursor shift of up to
±open_tol Da that also moves the fragment peaks above a random breakpoint.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class LibraryParams:
    n_refs: int
    n_queries: int
    max_peaks: int = 64
    min_peaks: int = 24
    mz_min: float = 200.0
    mz_max: float = 2000.0
    pmz_min: float = 400.0
    pmz_max: float = 1800.0
    charges: tuple[int, ...] = (2, 3)
    modified_frac: float = 0.5
    open_tol_da: float = 75.0
    dropout: float = 0.15
    mz_jitter: float = 0.01
    intensity_jitter: float = 0.2


class Spectra(NamedTuple):
    mz: jax.Array          # (B, P) f32, 0 padded
    intensity: jax.Array   # (B, P) f32, 0 padded
    pmz: jax.Array         # (B,) f32 precursor mass (Da)
    charge: jax.Array      # (B,) i32


class Dataset(NamedTuple):
    refs: Spectra
    queries: Spectra
    query_source: jax.Array    # (Q,) i32 reference each query was drawn from
    query_modified: jax.Array  # (Q,) bool


def library_params(cfg: dict, n_queries: int) -> LibraryParams:
    """LibraryParams from a configuration file's ``library`` section."""
    lib = dict(cfg["library"])
    lib["charges"] = tuple(lib["charges"])
    lib.pop("n_targets")
    lib.pop("add_decoys")
    lib.pop("queries_per_run")
    return LibraryParams(n_refs=cfg["library"]["n_targets"],
                         n_queries=n_queries,
                         open_tol_da=cfg["search"]["open_tol_da"], **lib)


def _make_refs(key, p: LibraryParams, layout_key) -> Spectra:
    k1, k2, k3, _, _ = jax.random.split(key, 5)
    _, _, _, k4, k5 = jax.random.split(layout_key, 5)
    B, P = p.n_refs, p.max_peaks
    n_peaks = jax.random.randint(k1, (B,), p.min_peaks, p.max_peaks + 1)
    mask = jnp.arange(P)[None, :] < n_peaks[:, None]
    mz = jax.random.uniform(k2, (B, P), minval=p.mz_min, maxval=p.mz_max)
    inten = jax.random.exponential(k3, (B, P)) + 0.05
    pmz = jax.random.uniform(k4, (B,), minval=p.pmz_min, maxval=p.pmz_max)
    cidx = jax.random.randint(k5, (B,), 0, len(p.charges))
    charge = jnp.asarray(p.charges, jnp.int32)[cidx]
    return Spectra(mz=jnp.where(mask, mz, 0.0),
                   intensity=jnp.where(mask, inten, 0.0),
                   pmz=pmz, charge=charge)


def _make_queries(key, refs: Spectra, p: LibraryParams, layout_key,
                  ref_of=None, q_order=None):
    _, kd, kj, ki, _, _, kf = jax.random.split(key, 7)
    kq, _, _, _, km, ks, _ = jax.random.split(layout_key, 7)
    Q, P = p.n_queries, p.max_peaks
    src = jax.random.randint(kq, (Q,), 0, refs.mz.shape[0])
    modified = jax.random.bernoulli(km, p.modified_frac, (Q,))
    shift = jax.random.uniform(ks, (Q,), minval=-p.open_tol_da,
                               maxval=p.open_tol_da)
    if q_order is not None:
        src, modified, shift = src[q_order], modified[q_order], shift[q_order]
    if ref_of is not None:
        src = ref_of[src]
    mz = refs.mz[src]
    inten = refs.intensity[src]
    valid = inten > 0
    keep = jax.random.bernoulli(kd, 1.0 - p.dropout, (Q, P)) & valid
    mz = mz + jax.random.normal(kj, (Q, P)) * p.mz_jitter
    inten = inten * jnp.exp(jax.random.normal(ki, (Q, P)) * p.intensity_jitter)
    shift = jnp.where(jnp.abs(shift) < 2.0, jnp.sign(shift) * 2.0 + shift,
                      shift)
    shift = jnp.where(modified, shift, 0.0)
    breakpoint_mz = jax.random.uniform(kf, (Q,), minval=p.mz_min,
                                       maxval=p.mz_max)
    frag_shift = jnp.where((mz > breakpoint_mz[:, None]) & modified[:, None],
                           shift[:, None], 0.0)
    mz = mz + frag_shift
    queries = Spectra(
        mz=jnp.where(keep, jnp.clip(mz, p.mz_min, p.mz_max - 1e-3), 0.0),
        intensity=jnp.where(keep, inten, 0.0),
        pmz=refs.pmz[src] + shift,
        charge=refs.charge[src])
    return queries, src, modified


@partial(jax.jit, static_argnames=("p", "fixed_layout"))
def _generate(seed, layout_seed, p: LibraryParams,
              fixed_layout: bool) -> Dataset:
    kr, kq = jax.random.split(jax.random.PRNGKey(seed))
    if not fixed_layout:
        refs = _make_refs(kr, p, kr)
        queries, src, modified = _make_queries(kq, refs, p, kq)
        return Dataset(refs, queries, src, modified)
    lr, lq = jax.random.split(jax.random.PRNGKey(layout_seed))
    base = _make_refs(kr, p, lr)
    # Reference i carries the layout entry perm[i]; a query drawn from
    # layout entry j comes from the reference that carries it.
    perm = jax.random.permutation(jax.random.fold_in(kr, 1), p.n_refs)
    refs = base._replace(pmz=base.pmz[perm], charge=base.charge[perm])
    ref_of = jnp.zeros_like(perm).at[perm].set(jnp.arange(p.n_refs))
    q_order = jax.random.permutation(jax.random.fold_in(kq, 1), p.n_queries)
    queries, src, modified = _make_queries(kq, refs, p, lq, ref_of, q_order)
    return Dataset(refs, queries, src, modified)


def make_dataset(p: LibraryParams, seed: int,
                 layout_seed: int | None = None) -> Dataset:
    """The library and queries for ``seed`` (a 32-bit value), on the
    default device, from one jitted call; with ``layout_seed``, on the
    precursor layout of that seed (see the module's docstring)."""
    return _generate(jnp.uint32(seed), jnp.uint32(layout_seed or 0), p,
                     layout_seed is not None)
