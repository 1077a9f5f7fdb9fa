"""Autotune subsystem: per-device tile sweeps with a persistent winner cache.

Three layers, resolved by :func:`tiles_for` at backend dispatch:

  1. kernel defaults — the ``Q_TILE``/``R_TILE``/``WORD_TILE`` constants
     exported by the kernel wrappers (one source of truth with the
     kernels);
  2. :data:`repro.tune.promoted.PROMOTED` — reviewed per-device-kind
     constants, committed in-repo;
  3. the on-disk JSON winner cache (``repro.tune.cache``) — whatever
     ``oms.py tune`` measured on this machine, keyed by
     ``(device_kind, backend, dim, k, shape_bucket)``.

``repro.core.backends`` routes BOTH its Pallas dispatch tiles and its
``peak_intermediate`` contract bounds through :func:`tiles_for`, so a
tuned tile changes the declared bound and the launch padding together —
the analyzer stays honest without any contract loosening.

The sweep harness itself lives in :mod:`repro.tune.sweep` (imported
lazily by the CLI; it pulls in the kernels and the search orchestrator).
"""
from __future__ import annotations

import functools

from repro.tune.cache import (ENV_VAR, SCHEMA, TuneCache, cache_path,
                              cache_stats, lookup_tiles, reset_runtime,
                              set_cache_path, shape_bucket)
from repro.tune.promoted import (DEFAULT_ROW_BUCKET_LO, PROMOTED,
                                 declared_tiles)

__all__ = [
    "ENV_VAR", "SCHEMA", "TuneCache", "cache_path", "cache_stats",
    "lookup_tiles", "reset_runtime", "set_cache_path", "shape_bucket",
    "DEFAULT_ROW_BUCKET_LO", "PROMOTED", "declared_tiles",
    "device_kind", "kernel_defaults", "tiles_for", "row_bucket_lo",
    "SWEPT_BACKENDS",
]

# Backends the sweep harness knows how to benchmark. "rescore" is the
# pseudo-backend for the prefix-rescore row_bucket base.
SWEPT_BACKENDS = ("kernel_vpu", "kernel_mxu", "fused", "fused_mxu",
                  "rescore")


@functools.lru_cache(maxsize=1)
def device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind


def kernel_defaults(backend: str) -> dict[str, int]:
    """Hand-picked launch tiles for one tunable backend (lazy kernel
    import so this module stays cheap)."""
    if backend in ("kernel_mxu", "fused_mxu"):
        from repro.kernels.hamming_mxu import ops as mops
        return {"q_tile": mops.Q_TILE, "r_tile": mops.R_TILE,
                "word_tile": mops.WORD_TILE}
    if backend in ("kernel_vpu", "fused"):
        from repro.kernels.hamming import ops as hops
        return {"q_tile": hops.Q_TILE, "r_tile": hops.R_TILE,
                "word_tile": hops.WORD_TILE}
    if backend == "rescore":
        return {"row_bucket": DEFAULT_ROW_BUCKET_LO}
    raise ValueError(f"backend {backend!r} is not tunable; "
                     f"swept backends: {', '.join(SWEPT_BACKENDS)}")


def tiles_for(backend: str, *, dim: int, k: int, q_rows: int,
              r_rows: int) -> dict[str, int]:
    """Effective launch tiles for one hot call: defaults, overlaid with any
    promoted per-device constants, overlaid with any cached sweep winner.
    Pure for a fixed loaded cache — repeated same-shape dispatch resolves
    the same tiles (the recompile_guard contract depends on this)."""
    tiles = dict(kernel_defaults(backend))
    dk = device_kind()
    prom = declared_tiles(dk, backend)
    if prom:
        tiles.update(prom)
    hit = lookup_tiles(dk, backend, dim, k, q_rows, r_rows)
    if hit:
        tiles.update(hit)
    return tiles


def row_bucket_lo() -> int:
    """Tuned pow2 floor for ``core.search.row_bucket`` (the prefix-rescore
    candidate-bucket base); shape-independent, keyed dim=k=0."""
    return tiles_for("rescore", dim=0, k=0, q_rows=0,
                     r_rows=0)["row_bucket"]
