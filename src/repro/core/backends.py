"""Search-backend registry for the blocked OMS orchestrator.

Two backend kinds exist, mirroring the two ways the paper's §II-C kernel can
be realised:

  * ``matrix`` — computes the full (Qb, Rk) Hamming-distance tile; the
    orchestrator applies the precursor windows and the top-k reduction
    outside the backend. Signature: ``fn(q_hvs, r_hvs, dim) -> (Qb, Rk)
    int32 hamming``. A matrix backend may also carry an in-place scan step
    (``Backend.scan``) that returns the blocked scan's winners instead.
  * ``fused`` — the paper-faithful path: consumes the PMZ/charge windows and
    returns ranked running winners directly, never materialising the
    (Qb, Rk) similarity matrix. Signature:
    ``fn(q_hvs, r_hvs, q_pmz, r_pmz, q_charge, r_charge, *, dim, ppm_tol,
    open_tol_da, k) -> (std_sim, std_idx, open_sim, open_idx)``, each
    (Qb, k) int32 with idx relative to the reference slice (or -1).

Built-in backends:

  name        kind    engine
  ----------  ------  -----------------------------------------------------
  vpu         matrix  packed XOR + lax.population_count (paper-faithful):
                      on TPU the blocked scan step runs the in-place Pallas
                      kernel (its ``scan``: tile, windows and top-k in one
                      pass), XLA on CPU and in the prefix/rescore tiles
  mxu         matrix  ±1 int8 matmul  (D - x·yᵀ)/2  (XLA, MXU formulation)
  kernel_vpu  matrix  Pallas all-pairs Hamming tile kernel
  kernel_mxu  matrix  Pallas MXU Hamming kernel
  fused       fused   Pallas fused §II-C kernel (Hamming + dual windows +
                      running top-k, one pass over the reference stream)
  fused_mxu   fused   Pallas fused §II-C kernel on the MXU: the same
                      single-pass dual-window top-k, with the Hamming tile
                      computed as a ±1 int8 matmul — bit-identical to
                      ``fused`` (exact integer math)
  fused_xla   fused   XLA fallback of the fused reduction (still materialises
                      the tile internally; for validation/debug)

Pallas-backed backends resolve their launch tiles through
``repro.tune.tiles_for`` at dispatch (kernel defaults, overlaid with
promoted per-device constants, overlaid with any on-disk sweep-winner
cache) — and the ``peak_intermediate`` contract bounds below are phrased
through the SAME resolver, so a tuned tile moves the declared bound and
the launch padding together.

Register custom backends with :func:`register`; kernels are imported lazily
inside the backend fn so importing this module stays cheap.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

# Dependency-free registry (stdlib only) — safe at module level, checked by
# `oms.py analyze --imports`.
from repro.analysis.registry import declare as _declare
from repro.core import packing

MATRIX = "matrix"
FUSED = "fused"


@dataclasses.dataclass(frozen=True)
class Backend:
    name: str
    kind: str          # MATRIX | FUSED
    fn: Callable
    # Matrix backend whose tile fn serves this FUSED backend's prefix/
    # rescore stages (see tile_backend); None falls back to "vpu".
    tile_name: str | None = None
    # True where a Pallas kernel computes fn's result.
    pallas: bool = False
    # Optional in-place blocked-scan step of a MATRIX backend: ``scan(q_hvs,
    # q_pmz, q_charge, hvs, pmz, charge, start_row, rk, *, dim, ppm_tol,
    # open_tol_da, k)`` is the dual-window top-k of fn's tile against rows
    # ``[start_row, start_row + rk)`` of the whole library, read where they
    # lie (a Pallas kernel that keeps the winners in VMEM): (std_sim,
    # std_col, open_sim, open_col), each (Qb, k), col the row's column in
    # the run or -1. The scan takes it where ``scan_fits(max_r, n_words)``
    # holds for the library's blocking.
    scan: Callable | None = None
    scan_fits: Callable[[int, int], bool] | None = None

    def scans_in_place(self, max_r: int, n_words: int) -> bool:
        return self.scan is not None and self.scan_fits(max_r, n_words)


_REGISTRY: dict[str, Backend] = {}


def register(name: str, kind: str, fn: Callable, *,
             tile_name: str | None = None, pallas: bool = False,
             scan: Callable | None = None,
             scan_fits: Callable[[int, int], bool] | None = None) -> Backend:
    if kind not in (MATRIX, FUSED):
        raise ValueError(f"backend kind must be {MATRIX!r} or {FUSED!r}, "
                         f"got {kind!r}")
    be = Backend(name=name, kind=kind, fn=fn, tile_name=tile_name,
                 pallas=pallas, scan=scan, scan_fits=scan_fits)
    _REGISTRY[name] = be
    return be


def get(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered: {', '.join(names())}"
        ) from None


def names(kind: str | None = None) -> tuple[str, ...]:
    return tuple(n for n, b in _REGISTRY.items()
                 if kind is None or b.kind == kind)


# ---------------------------------------------------------------------------
# Built-ins
# ---------------------------------------------------------------------------


def _tuned(backend: str, dim: int, k: int, q_rows: int, r_rows: int) -> dict:
    """Effective launch tiles for one hot call (lazy tune import; pure for
    a fixed loaded winner cache, so repeat dispatch never retraces)."""
    from repro import tune
    return tune.tiles_for(backend, dim=dim, k=k, q_rows=q_rows,
                          r_rows=r_rows)


def _kernel_vpu(q, r, dim):
    from repro.kernels.hamming import ops as hops
    t = _tuned("kernel_vpu", dim, 0, q.shape[0], r.shape[0])
    return hops.hamming_matrix(q, r, q_tile=t["q_tile"], r_tile=t["r_tile"],
                               word_tile=t["word_tile"])


def _kernel_mxu(q, r, dim):
    from repro.kernels.hamming_mxu import ops as mops
    t = _tuned("kernel_mxu", dim, 0, q.shape[0], r.shape[0])
    return mops.hamming_matrix(q, r, dim, q_tile=t["q_tile"],
                               r_tile=t["r_tile"], word_tile=t["word_tile"])


def _fused_pallas(q, r, qp, rp, qc, rc, *, dim, ppm_tol, open_tol_da, k):
    from repro.kernels.hamming import ops as hops
    t = _tuned("fused", dim, k, q.shape[0], r.shape[0])
    return hops.fused_search(q, r, qp, rp, qc, rc, dim=dim, k=k,
                             ppm_tol=ppm_tol, open_tol_da=open_tol_da,
                             q_tile=t["q_tile"], r_tile=t["r_tile"],
                             word_tile=t["word_tile"])


def _fused_mxu(q, r, qp, rp, qc, rc, *, dim, ppm_tol, open_tol_da, k):
    from repro.kernels.hamming_mxu import ops as mops
    t = _tuned("fused_mxu", dim, k, q.shape[0], r.shape[0])
    return mops.fused_search(q, r, qp, rp, qc, rc, dim=dim, k=k,
                             ppm_tol=ppm_tol, open_tol_da=open_tol_da,
                             q_tile=t["q_tile"], r_tile=t["r_tile"],
                             word_tile=t["word_tile"])


def _fused_xla(q, r, qp, rp, qc, rc, *, dim, ppm_tol, open_tol_da, k):
    from repro.kernels.hamming import ref as href
    return href.fused_search(q, r, qp, rp, qc, rc, dim=dim, k=k,
                             ppm_tol=ppm_tol, open_tol_da=open_tol_da)


def _vpu_scan(q, qp, qc, hvs, pmz, charge, start_row, rk, *, dim, ppm_tol,
              open_tol_da, k):
    from repro.kernels.hamming import ops as hops
    return hops.scan_best(q, qp, qc, hvs, pmz, charge, start_row, rk=rk,
                          dim=dim, ppm_tol=ppm_tol, open_tol_da=open_tol_da,
                          k=k)


def _vpu_scan_fits(max_r: int, n_words: int) -> bool:
    """On TPU, where the kernel takes the library's blocking; the CPU (where
    Pallas would only interpret) keeps the XLA tile."""
    from repro.kernels import interpret_default
    from repro.kernels.hamming import ops as hops
    return not interpret_default() and hops.scan_tile_fits(max_r, n_words)


register("vpu", MATRIX, lambda q, r, dim: packing.hamming_matrix_packed(q, r),
         scan=_vpu_scan, scan_fits=_vpu_scan_fits)
register("mxu", MATRIX, lambda q, r, dim: packing.hamming_matrix_mxu(q, r, dim))
register("kernel_vpu", MATRIX, _kernel_vpu, pallas=True)
register("kernel_mxu", MATRIX, _kernel_mxu, pallas=True)
register("fused", FUSED, _fused_pallas, pallas=True)
register("fused_mxu", FUSED, _fused_mxu, tile_name="kernel_mxu", pallas=True)
register("fused_xla", FUSED, _fused_xla)


def tile_backend(name: str) -> Backend:
    """The matrix backend whose plain ``fn(q_hvs, r_hvs, dim) -> (Qb, Rk)
    hamming`` tile serves ``name``'s prefix/rescore stages.

    The dimension cascade's prefix scan and survivor rescore need a raw
    Hamming tile at arbitrary word widths. Matrix backends already have
    that signature; fused backends have no tile entry point (the whole
    point is not materialising one), so they route these two stages to a
    matrix sibling: ``fused_mxu`` declares ``tile_name="kernel_mxu"`` (the
    cascade stages run on the MXU tile kernel), and the rest fall back to
    the packed-VPU tile — the fused single-pass kernel still runs the main
    full-width scan when the cascade is off.
    """
    be = get(name)
    if be.kind == MATRIX:
        return be
    if be.tile_name is not None:
        return get(be.tile_name)
    return _REGISTRY["vpu"]


# ---------------------------------------------------------------------------
# Contracts — the memory/transfer/dtype story of each backend, declared next
# to its registration and machine-checked by `oms.py analyze` (the runner
# traces one blocked-scan step per backend and evaluates these; see
# repro.analysis). Registering a new backend without declaring its peak
# footprint leaves it unchecked — declare or the analyze matrix won't cover
# it.
# ---------------------------------------------------------------------------

def _declare_common(target: str) -> None:
    _declare(target, "no_host_transfer")
    _declare(target, "dtype_stability")


for _t in ("search:vpu", "search:mxu", "search:kernel_vpu",
           "search:kernel_mxu", "search:fused", "search:fused_mxu",
           "search:fused_xla"):
    _declare_common(_t)

# Peak device intermediate of ONE blocked-scan step, as a function of the
# trace context (q_block, rk = scanned rows, n_words, dim). 4 = the widest
# per-element carrier on each path (uint32 words / int32 counts). Pallas
# paths pad Q/Rk up to the kernels' launch tiles before the call, so their
# bounds are phrased over the PADDED extents — resolved through the SAME
# ``repro.tune.tiles_for`` layering the dispatch fns above use (kernel
# constants < promoted per-device constants < sweep-winner cache), so a
# tuned tile that changes padding changes the declared bound with it.


def _pad_to(n: int, tile: int) -> int:
    return -(-n // tile) * tile


def _ctx_tiles(backend: str, c, k: int = 0) -> dict:
    # The tile fns see dim = 32 * the traced word count (the prefix stage
    # dispatches at pdim = 32 * prefix_words, not the full HV width).
    return _tuned(backend, 32 * c["n_words"], k, c["q_block"], c["rk"])


def _kernel_vpu_bound(c):
    t = _ctx_tiles("kernel_vpu", c)
    rk = _pad_to(c["rk"], t["r_tile"])
    return max(_pad_to(c["q_block"], t["q_tile"]) * rk * 4,
               rk * c["n_words"] * 4)


def _kernel_mxu_bound(c):
    from repro.kernels.hamming_mxu.ops import effective_tiles
    t = _ctx_tiles("kernel_mxu", c)
    qt, rt, _ = effective_tiles(c["q_block"], c["rk"], c["n_words"],
                                q_tile=t["q_tile"], r_tile=t["r_tile"],
                                word_tile=t["word_tile"])
    rk = _pad_to(c["rk"], rt)
    return max(_pad_to(c["q_block"], qt) * rk * 4,
               rk * c["n_words"] * 4)


def _fused_bound(c):
    t = _ctx_tiles("fused", c, k=c["top_k"])
    rt = min(t["r_tile"], c["rk"])
    return max(_pad_to(c["rk"], rt) * c["n_words"] * 4,
               _pad_to(c["q_block"], t["q_tile"]) * c["n_words"] * 4)


def _fused_mxu_bound(c):
    from repro.kernels.hamming_mxu.ops import effective_tiles
    t = _ctx_tiles("fused_mxu", c, k=c["top_k"])
    qt, rt, _ = effective_tiles(c["q_block"], c["rk"], c["n_words"],
                                q_tile=t["q_tile"], r_tile=t["r_tile"],
                                word_tile=t["word_tile"])
    return max(_pad_to(c["rk"], rt) * c["n_words"] * 4,
               _pad_to(c["q_block"], qt) * c["n_words"] * 4)


_declare("search:vpu", "peak_intermediate",
         bound=lambda c: c["q_block"] * c["rk"] * c["n_words"] * 4,
         note="packed XOR/popcount tensor (Qb, Rk, W)")
_declare("search:mxu", "peak_intermediate",
         bound=lambda c: c["rk"] * c["dim"] * 4,
         note="bits_to_pm1 unpack (Rk, D) int32 before the int8 cast")
_declare("search:kernel_vpu", "peak_intermediate",
         bound=_kernel_vpu_bound,
         note="Pallas tile kernel: tile-padded (Qb', Rk') int32 output / "
              "(Rk', W) padded copy")
_declare("search:kernel_mxu", "peak_intermediate",
         bound=_kernel_mxu_bound,
         note="Pallas MXU kernel: tile-padded (Qb', Rk') int32 output / "
              "(Rk', W) padded copy")
_declare("search:fused", "peak_intermediate",
         bound=_fused_bound,
         note="§II-C streaming kernel: the (Rk', W) tile-padded reference "
              "slice is the largest HBM-resident array")
_declare("search:fused_mxu", "peak_intermediate",
         bound=_fused_mxu_bound,
         note="§II-C MXU kernel: tile-padded (Rk', W) reference slice; the "
              "±1 int8 unpack lives in VMEM inside the kernel")
_declare("search:fused_xla", "peak_intermediate",
         bound=lambda c: c["q_block"] * c["rk"] * c["n_words"] * 4,
         note="XLA fallback materialises the xor tensor like vpu")

# Dimension-cascade stages. ``prefix:<be>`` is one stage-A survivor-flag
# scan (ctx n_words = prefix_words, nqb query blocks, n_rows padded DB rows
# — the scatter target and the (nqb, rk) flag/index carriers are the extra
# non-tile intermediates); ``rescore:<be>`` is one stage-B exact rescore
# over an rk = survivor-bucket candidate set at full width. Fused backends
# route both stages through their tile sibling (see ``tile_backend``):
# fused_mxu runs them on the kernel_mxu tile, the rest fall back to the
# packed-VPU tile — each declared bound is its tile fn's bound.


def _prefix_extra(c):
    return max(c["nqb"] * c["rk"] * 4, c["n_rows"] * 4)


def _prefix_vpu_bound(c):
    return max(c["q_block"] * c["rk"] * c["n_words"] * 4, _prefix_extra(c))


def _prefix_mxu_bound(c):
    return max(c["rk"] * 32 * c["n_words"] * 4, _prefix_extra(c))


def _prefix_kernel_vpu_bound(c):
    return max(_kernel_vpu_bound(c), _prefix_extra(c))


def _prefix_kernel_mxu_bound(c):
    return max(_kernel_mxu_bound(c), _prefix_extra(c))


for _t, _b, _n in (
    ("prefix:vpu", _prefix_vpu_bound, "packed XOR tensor (Qb, Rk, P)"),
    ("prefix:mxu", _prefix_mxu_bound, "bits_to_pm1 unpack (Rk, 32P) int32"),
    ("prefix:kernel_vpu", _prefix_kernel_vpu_bound,
     "tile-padded Pallas output / padded (Rk', P) copy"),
    ("prefix:kernel_mxu", _prefix_kernel_mxu_bound,
     "tile-padded Pallas MXU output / padded (Rk', P) copy"),
    ("prefix:fused", _prefix_vpu_bound, "packed-VPU tile fallback"),
    ("prefix:fused_mxu", _prefix_kernel_mxu_bound,
     "kernel_mxu tile sibling: tile-padded Pallas MXU output"),
    ("prefix:fused_xla", _prefix_vpu_bound, "packed-VPU tile fallback"),
    ("rescore:vpu", _prefix_vpu_bound, "packed XOR tensor (Qb, S, W)"),
    ("rescore:mxu", _prefix_mxu_bound, "bits_to_pm1 unpack (S, D) int32"),
    ("rescore:kernel_vpu", _prefix_kernel_vpu_bound,
     "tile-padded Pallas output / padded (S', W) copy"),
    ("rescore:kernel_mxu", _prefix_kernel_mxu_bound,
     "tile-padded Pallas MXU output / padded (S', W) copy"),
    ("rescore:fused", _prefix_vpu_bound, "packed-VPU tile fallback"),
    ("rescore:fused_mxu", _prefix_kernel_mxu_bound,
     "kernel_mxu tile sibling: tile-padded Pallas MXU output"),
    ("rescore:fused_xla", _prefix_vpu_bound, "packed-VPU tile fallback"),
):
    _declare_common(_t)
    _declare(_t, "peak_intermediate", bound=_b, note=_n)

# The paper's single-pass kernel never materialises the (Qb, Rk) score
# matrix; matrix-kind backends compute exactly that tile BY DESIGN, so the
# contract is only declared on the fused backends. fused_xla is the
# documented exemption: it is FUSED-kind (consumes windows, returns ranked
# winners) but internally materialises the tile — it exists for
# validation/debug, and the analyzer reports (rather than fails) it.
_declare("search:fused", "no_materialize",
         note="single-pass running top-k; tile lives in VMEM")
_declare("search:fused_mxu", "no_materialize",
         note="single-pass running top-k; the ±1 unpack and the MXU dot "
              "tile both live in VMEM")
_declare("search:fused_xla", "no_materialize", expect=False,
         note="XLA reference reduction materialises the tile internally "
              "by design (validation/debug backend)")
