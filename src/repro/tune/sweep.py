"""Tile-sweep harness: benchmark a backend's hot fn across a static grid.

For every candidate tile assignment the harness measures median-of-k wall
time of the real jitted hot fn at caller-supplied shapes, and (optionally)
compiles the same fn once to model its bytes/FLOPs with
``repro.utils.hlo_cost`` — from which ``repro.utils.roofline`` gives a
per-candidate roofline bound and the measured-vs-roofline fraction
(``t_bound / measured``; 1.0 would be a kernel running exactly at the
model's bandwidth/compute limit).

Winners are deterministic under fixed timings: candidates sort by
``(median_us, sorted(tiles))``, so ties break to the lexicographically
smallest tile assignment. Tests inject a fake ``timer(fn, args, tiles)``
to pin the timings.

Swept backends (``repro.tune.SWEPT_BACKENDS``):

  * ``kernel_vpu`` / ``kernel_mxu`` — the Pallas Hamming-tile kernels,
    grid over (q_tile, r_tile, word_tile);
  * ``fused`` / ``fused_mxu``      — the single-pass §II-C kernels, same
    grid (k rides in from the caller);
  * ``rescore``                    — the prefix-rescore path's
    ``row_bucket`` pow2 base (the padded survivor-bucket floor).

This module imports the kernels and the search orchestrator, so the CLI
loads it lazily; dispatch-side tile resolution lives in
``repro.tune.__init__`` and never touches this file.
"""
from __future__ import annotations

import dataclasses
import itertools
import time

import numpy as np

from repro.tune import cache as cache_mod
from repro.tune import device_kind

MATRIX_BACKENDS = ("kernel_vpu", "kernel_mxu")
FUSED_BACKENDS = ("fused", "fused_mxu")

# Named grids. "tiny" is the CI smoke grid (seconds, not minutes, in
# interpret mode); "default" is the real per-device sweep.
GRIDS: dict[str, dict[str, dict[str, tuple[int, ...]]]] = {
    "default": {
        "kernel": {"q_tile": (16, 32, 64), "r_tile": (128, 256, 512),
                   "word_tile": (64, 128)},
        "rescore": {"row_bucket": (32, 64, 128, 256)},
    },
    "tiny": {
        "kernel": {"q_tile": (16, 32), "r_tile": (128, 256),
                   "word_tile": (16,)},
        "rescore": {"row_bucket": (64, 128)},
    },
}


@dataclasses.dataclass
class SweepRow:
    backend: str
    tiles: dict[str, int]
    median_us: float
    model_flops: float = 0.0      # hlo_cost-modeled FLOPs (trip-weighted)
    model_bytes: float = 0.0      # hlo_cost-modeled HBM bytes
    # roofline bound from the modeled terms and t_bound / measured; None on
    # a device without published peaks (repro.utils.roofline.PEAKS)
    t_bound_us: float | None = None
    roofline_frac: float | None = None

    def tiles_str(self) -> str:
        return " ".join(f"{n}={v}" for n, v in sorted(self.tiles.items()))

    def sort_key(self):
        return (self.median_us, tuple(sorted(self.tiles.items())))


def grid_candidates(backend: str, grid: str = "default") -> list[dict]:
    """Deterministically ordered candidate tile dicts for one backend."""
    spec = GRIDS[grid]["rescore" if backend == "rescore" else "kernel"]
    names = sorted(spec)
    out = []
    for combo in itertools.product(*(spec[n] for n in names)):
        out.append(dict(zip(names, combo)))
    return out


# ---------------------------------------------------------------------------
# Hot-fn builders (one synthetic case per backend at caller shapes)
# ---------------------------------------------------------------------------


def _synth(dim: int, q_rows: int, r_rows: int, seed: int):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    W = dim // 32
    q = jnp.asarray(rng.integers(0, 2 ** 32, (q_rows, W), dtype=np.uint32))
    r = jnp.asarray(rng.integers(0, 2 ** 32, (r_rows, W), dtype=np.uint32))
    qp = jnp.asarray(rng.uniform(100.0, 1500.0, q_rows).astype(np.float32))
    rp = jnp.asarray(rng.uniform(100.0, 1500.0, r_rows).astype(np.float32))
    qc = jnp.asarray(rng.integers(1, 4, q_rows).astype(np.int32))
    rc = jnp.asarray(rng.integers(1, 4, r_rows).astype(np.int32))
    return q, r, qp, rp, qc, rc


def make_case(backend: str, *, dim: int, k: int, q_rows: int, r_rows: int,
              seed: int = 0):
    """-> ``case(tiles) -> (fn, args)``: the jitted hot fn + concrete args
    for one candidate. ``fn(*args)`` is what gets timed and modeled."""
    q, r, qp, rp, qc, rc = _synth(dim, q_rows, r_rows, seed)

    if backend == "kernel_vpu":
        from repro.kernels.hamming import ops as hops

        def case(tiles):
            def fn(a, b):
                return hops.hamming_matrix(
                    a, b, q_tile=tiles["q_tile"], r_tile=tiles["r_tile"],
                    word_tile=tiles["word_tile"])
            return fn, (q, r)
        return case

    if backend == "kernel_mxu":
        from repro.kernels.hamming_mxu import ops as mops

        def case(tiles):
            def fn(a, b):
                return mops.hamming_matrix(
                    a, b, dim, q_tile=tiles["q_tile"],
                    r_tile=tiles["r_tile"], word_tile=tiles["word_tile"])
            return fn, (q, r)
        return case

    if backend in FUSED_BACKENDS:
        if backend == "fused":
            from repro.kernels.hamming import ops as kops
        else:
            from repro.kernels.hamming_mxu import ops as kops

        def case(tiles):
            def fn(a, b, c, d, e, f):
                return kops.fused_search(
                    a, b, c, d, e, f, dim=dim, k=k,
                    q_tile=tiles["q_tile"], r_tile=tiles["r_tile"],
                    word_tile=tiles["word_tile"])
            return fn, (q, r, qp, rp, qc, rc)
        return case

    if backend == "rescore":
        import jax.numpy as jnp

        from repro.core import search as search_mod

        qb = 16 if q_rows % 16 == 0 else q_rows
        params = search_mod.SearchParams(backend="vpu", top_k=k, q_block=qb)

        def case(tiles):
            bucket = search_mod.row_bucket(r_rows, lo=tiles["row_bucket"])
            rows_pad, valid = search_mod.pad_candidate_rows(
                np.arange(r_rows, dtype=np.int64), bucket)
            r_hvs = jnp.zeros((bucket, dim // 32), jnp.uint32
                              ).at[:r_rows].set(r)
            rows_j = jnp.where(jnp.asarray(valid),
                               jnp.asarray(rows_pad.astype(np.int32)), -1)
            pmz = jnp.where(jnp.asarray(valid),
                            jnp.zeros((bucket,), jnp.float32).at[:r_rows]
                            .set(rp), search_mod.PAD_PMZ)
            chg = jnp.where(jnp.asarray(valid),
                            jnp.zeros((bucket,), jnp.int32).at[:r_rows]
                            .set(rc), -1)

            def fn(*a):
                return search_mod._rescore_rows_padded(
                    *a, params=params, dim=dim)
            return fn, (r_hvs, rows_j, pmz, chg, q, qp, qc)
        return case

    raise ValueError(f"backend {backend!r} is not sweepable")


# ---------------------------------------------------------------------------
# Measurement + model
# ---------------------------------------------------------------------------


def _median_time(fn, args, iters: int) -> float:
    import jax
    jax.block_until_ready(fn(*args))            # compile + warmup
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def _modeled_cost(fn, args) -> tuple[float, float]:
    """(flops, bytes) of the compiled hot fn via hlo_cost (0, 0 if the
    backend's compiler output is unparseable — model is best-effort)."""
    import jax

    from repro.utils import hlo_cost
    try:
        txt = jax.jit(fn).lower(*args).compile().as_text()
        c = hlo_cost.analyze(txt)
        return float(c["flops"]), float(c["bytes"])
    except Exception:
        return 0.0, 0.0


def useful_flops(dim: int, q_rows: int, r_rows: int) -> float:
    """Analytic "useful" work of one all-pairs scan: 2·D int ops per
    (query, reference) pair (the MXU dot formulation)."""
    return 2.0 * dim * q_rows * r_rows


def sweep_backend(backend: str, *, dim: int, k: int, q_rows: int,
                  r_rows: int, grid: str = "default", iters: int = 3,
                  seed: int = 0, timer=None, model: bool = True
                  ) -> list[SweepRow]:
    """All candidates for one backend, best (winner) first.

    ``timer(fn, args, tiles) -> seconds`` overrides wall timing (tests);
    ``model=False`` skips the compile+hlo_cost pass.
    """
    from repro.utils.roofline import Roofline, peaks_for

    peaks = peaks_for(device_kind())
    case = make_case(backend, dim=dim, k=k, q_rows=q_rows, r_rows=r_rows,
                     seed=seed)
    uflops = useful_flops(dim, q_rows, r_rows)
    rows = []
    for tiles in grid_candidates(backend, grid):
        fn, args = case(tiles)
        t = (timer(fn, args, tiles) if timer is not None
             else _median_time(fn, args, iters))
        flops, nbytes = _modeled_cost(fn, args) if model else (0.0, 0.0)
        t_bound = Roofline(flops=flops, hbm_bytes=nbytes, coll_bytes=0.0,
                           chips=1, peaks=peaks, model_flops=uflops).t_bound
        modeled = t_bound is not None
        rows.append(SweepRow(
            backend=backend, tiles=dict(tiles), median_us=t * 1e6,
            model_flops=flops, model_bytes=nbytes,
            t_bound_us=t_bound * 1e6 if modeled else None,
            roofline_frac=(t_bound / t if t > 0 else 0.0) if modeled
            else None))
    rows.sort(key=SweepRow.sort_key)
    return rows


def run_sweeps(backends, *, dim: int, k: int, q_rows: int, r_rows: int,
               grid: str = "default", iters: int = 3, seed: int = 0,
               timer=None, model: bool = True) -> dict[str, list[SweepRow]]:
    """Sweep several backends; {backend: rows best-first}. Matrix backends
    ignore ``k`` at dispatch, so their winners are keyed k=0 in the cache
    (see :func:`save_winners`)."""
    return {be: sweep_backend(be, dim=dim, k=k, q_rows=q_rows,
                              r_rows=r_rows, grid=grid, iters=iters,
                              seed=seed, timer=timer, model=model)
            for be in backends}


def cache_key_for(backend: str, *, dim: int, k: int, q_rows: int,
                  r_rows: int) -> dict:
    """The cache-key fields dispatch will look this winner up under:
    matrix tiles carry no k (keyed 0); the rescore base is global per
    device (keyed dim=k=0, unit bucket)."""
    if backend in MATRIX_BACKENDS:
        return {"dim": dim, "k": 0,
                "shape_bucket": cache_mod.shape_bucket(q_rows, r_rows)}
    if backend == "rescore":
        return {"dim": 0, "k": 0, "shape_bucket": cache_mod.shape_bucket(0, 0)}
    return {"dim": dim, "k": k,
            "shape_bucket": cache_mod.shape_bucket(q_rows, r_rows)}


def save_winners(path, results: dict[str, list[SweepRow]], *, dim: int,
                 k: int, q_rows: int, r_rows: int,
                 git_rev: str = "") -> cache_mod.TuneCache:
    """Merge each backend's winner into the cache file at ``path``."""
    cache = cache_mod.TuneCache.load(path)
    for be, rows in results.items():
        if not rows:
            continue
        w = rows[0]
        cache.put(device_kind=device_kind(), backend=be,
                  tiles=w.tiles, median_us=round(w.median_us, 1),
                  roofline_frac=(None if w.roofline_frac is None
                                 else round(w.roofline_frac, 6)),
                  git_rev=git_rev,
                  **cache_key_for(be, dim=dim, k=k, q_rows=q_rows,
                                  r_rows=r_rows))
    cache.save(path)
    return cache


def format_table(results: dict[str, list[SweepRow]], *,
                 winners_only: bool = False) -> str:
    """Winner table (or the full sweep), fixed-width, winner row starred."""
    lines = [f"{'backend':<12} {'tiles':<38} {'median_us':>10} "
             f"{'t_bound_us':>10} {'roofline':>9}"]
    for be in sorted(results):
        rows = results[be][:1] if winners_only else results[be]
        for i, r in enumerate(rows):
            star = "*" if i == 0 else " "
            if r.t_bound_us is None:
                bound, frac = f"{'n/a':>10}", f"{'n/a':>9}"
            else:
                bound = f"{r.t_bound_us:>10.2f}"
                frac = f"{r.roofline_frac * 100:>8.3f}%"
            lines.append(f"{be:<12} {r.tiles_str():<38} "
                         f"{r.median_us:>10.1f} {bound} {frac}{star}")
    return "\n".join(lines)
