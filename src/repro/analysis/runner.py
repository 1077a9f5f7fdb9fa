"""Contract-matrix driver: trace every hot path, check every declaration.

``python -m repro.launch.oms analyze`` lands here. The runner builds one
smoke-scale fixture (synthetic library -> in-memory pipeline + on-disk
store), then for every registered (encode backend x search backend x
resident/streamed x cascade on/off) combination:

  * traces the path's jitted hot function(s) with ``jax.make_jaxpr`` (no
    compile, no accelerator needed — CPU/interpret is exact for the
    *structural* contracts);
  * evaluates every :mod:`repro.analysis.registry` declaration whose
    target the combination exercises;
  * runs the ``recompile_guard`` (the one runtime contract) by calling
    the real resident/streamed search twice with same-shaped batches and
    asserting zero jit-cache growth on the repeat call.

Traces are memoized per unique (target, path) key — an encode backend
does not change the search jaxpr — so the full N-combination report costs
one trace per distinct hot function, not one per combination row.

The JSON report names, for every combination, each contract's pass/fail
and (on failure) the offending jaxpr equation; :func:`run` returns it and
the CLI exits nonzero if any non-exempt contract fails.
"""
from __future__ import annotations

import dataclasses
import shutil
import tempfile
from typing import Any

import jax
import numpy as np

from repro.analysis import contracts as C
from repro.analysis import registry


@dataclasses.dataclass(frozen=True)
class SmokeShapes:
    """Small enough to trace everything in seconds, large enough that the
    contract dimensions (q-block, scanned rows, word count, word tile)
    are all DISTINCT sizes — shape-membership tests must not collide."""

    dim: int = 512           # n_words = 16
    n_levels: int = 8
    max_r: int = 64
    q_block: int = 8
    top_k: int = 2
    n_refs: int = 768
    n_queries: int = 32
    encode_batch: int = 16
    slab_rows: int = 128     # 2 blocks per slab
    narrow_tol_da: float = 1.0
    seed: int = 3

    @property
    def n_words(self) -> int:
        return self.dim // 32


def _encode_ctx(sm: SmokeShapes, peaks: int, n_bins: int) -> dict[str, Any]:
    return {"dim": sm.dim, "n_words": sm.n_words, "batch": sm.encode_batch,
            "peaks": peaks, "n_levels": sm.n_levels, "n_bins": n_bins,
            "word_tile": min(8, sm.n_words)}


def _search_ctx(sm: SmokeShapes, rk: int, **extra) -> dict[str, Any]:
    return {"dim": sm.dim, "n_words": sm.n_words, "q_block": sm.q_block,
            "rk": rk, "top_k": sm.top_k, **extra}


def _eval_decls(target: str, jaxpr, ctx) -> list[C.ContractResult]:
    return [C.evaluate(d, jaxpr, ctx) for d in registry.declarations(target)
            if d.contract != "recompile_guard"]


class _Fixture:
    """One smoke dataset + resident pipeline + streamed pipeline (tmp store)."""

    def __init__(self, sm: SmokeShapes):
        from repro.core import OMSConfig, OMSPipeline
        from repro.data.spectra import LibraryConfig, make_dataset

        self.sm = sm
        self.cfg = OMSConfig(dim=sm.dim, n_levels=sm.n_levels, max_r=sm.max_r,
                             q_block=sm.q_block, top_k=sm.top_k,
                             encode_batch=sm.encode_batch, seed=sm.seed)
        self.ds = make_dataset(LibraryConfig(n_refs=sm.n_refs,
                                             n_queries=sm.n_queries,
                                             seed=sm.seed))
        self.tmp = tempfile.mkdtemp(prefix="oms-analyze-")
        store = OMSPipeline.ingest(self.cfg, self.ds.refs,
                                   f"{self.tmp}/store")
        self.resident = OMSPipeline.from_store(store, self.cfg)
        self.streamed = OMSPipeline.from_store(store, self.cfg,
                                               resident=False,
                                               slab_rows=sm.slab_rows)
        hvs, qp, qc = self.resident.encode_queries(self.ds.queries)
        self.q = (hvs, qp, qc)
        self.qp_np = np.asarray(qp)
        self.qc_np = np.asarray(qc)

    def close(self):
        shutil.rmtree(self.tmp, ignore_errors=True)

    # -- padded query layout (what the blocked scan actually consumes) -----
    def padded_queries(self):
        from repro.core.search import sort_pad_plan
        hvs, qp, qc = self.q
        gather, _ = sort_pad_plan(qp, qc, self.sm.q_block,
                                  q_charge_np=self.qc_np)
        return hvs[gather], qp[gather], qc[gather]


# ---------------------------------------------------------------------------
# Per-axis trace+check passes (memoized by construction: called once each)
# ---------------------------------------------------------------------------


def _encode_results(fx: _Fixture) -> dict[str, list[C.ContractResult]]:
    """Trace ``preprocess_encode`` per registered encode backend."""
    from repro.core import encode_backends

    qs = fx.ds.queries
    peaks = int(qs.mz.shape[1])
    out: dict[str, list[C.ContractResult]] = {}
    for name in encode_backends.names():
        def trace(mz, inten, pmz, charge, backend=name):
            return encode_backends.preprocess_encode(
                mz, inten, pmz, charge, fx.resident.codebooks,
                fx.cfg.preprocess_params, backend=backend,
                batch=fx.sm.encode_batch)

        jaxpr = jax.make_jaxpr(trace)(qs.mz, qs.intensity, qs.pmz, qs.charge)
        out[name] = _eval_decls(f"encode:{name}", jaxpr,
                                _encode_ctx(fx.sm, peaks, fx.cfg.n_bins))
    return out


def _trace_search(fx: _Fixture, db, params):
    from repro.core import search as search_mod
    qh, qp, qc = fx.padded_queries()
    return jax.make_jaxpr(
        lambda d, a, b, c: search_mod._search_sorted_padded(
            d, a, b, c, params=params, dim=fx.sm.dim))(db, qh, qp, qc)


def _search_results(fx: _Fixture) -> dict[tuple, list[C.ContractResult]]:
    """Trace the blocked scan per (search backend, path, stage) and check
    the backend's declarations at that path's scanned-rows extent.

    Keys: (backend, "resident"|"streamed", "open"|"narrow").
    """
    from repro.core import backends
    from repro.core.search import narrow_search_params
    from repro.serve.slabs import slab_arrays

    sm = fx.sm
    base = fx.resident.search_params(fx.qp_np, fx.qc_np)
    narrow = narrow_search_params(fx.resident.db, fx.qp_np, fx.qc_np, base,
                                  narrow_tol_da=sm.narrow_tol_da)
    eng = fx.streamed.engine
    slab = slab_arrays(eng.layout, 0, eng.plan)
    slab_cap = eng.plan.slab_blocks

    out: dict[tuple, list[C.ContractResult]] = {}
    for be in backends.names():
        for stage, p in (("open", base), ("narrow", narrow)):
            pr = p._replace(backend=be)
            rk = pr.k_blocks * sm.max_r
            jaxpr = _trace_search(fx, fx.resident.db, pr)
            out[(be, "resident", stage)] = _eval_decls(
                f"search:{be}", jaxpr, _search_ctx(sm, rk))

            ps = pr._replace(k_blocks=min(pr.k_blocks, slab_cap))
            rk_s = ps.k_blocks * sm.max_r
            jaxpr_s = _trace_search(fx, slab, ps)
            ctx_s = _search_ctx(sm, rk_s, slab_rows=eng.plan.slab_rows)
            res = _eval_decls(f"search:{be}", jaxpr_s, ctx_s)
            res += _eval_decls("serve:slab_step", jaxpr_s, ctx_s)
            out[(be, "streamed", stage)] = res
    return out


_PREFIX_WORDS = 4    # distinct from n_words (16), word_tile (8), q_block (8)
_RESCORE_ROWS = 64   # the smallest survivor bucket (core.search.row_bucket)


def _prefix_results(fx: _Fixture) -> dict[str, dict[str, list]]:
    """Trace the dimension cascade's two stages per search backend.

    Stage A (``_prefix_flags``) is traced against both the resident DB's
    prefix-column view and a prefix slab (k_blocks capped, slab shapes);
    stage B (``_rescore_rows_padded``) once per backend at the smallest
    survivor bucket. Keys: backend -> path -> results.
    """
    from repro.core import backends
    from repro.core import search as search_mod
    from repro.serve.slabs import slab_arrays

    sm = fx.sm
    P = _PREFIX_WORDS
    base = fx.resident.search_params(fx.qp_np, fx.qc_np)
    qh, qp, qc = fx.padded_queries()
    Qp = int(qp.shape[0])
    nqb = Qp // sm.q_block
    thr = np.zeros((Qp,), np.int32)
    eng = fx.streamed.engine
    slab = slab_arrays(eng.layout, 0, eng.plan, n_words=P)
    slab_cap = eng.plan.slab_blocks
    db_p = dataclasses.replace(fx.resident.db,
                               hvs=fx.resident.db.hvs[:, :P])

    S = _RESCORE_ROWS
    r_hvs = np.zeros((S, sm.n_words), np.uint32)
    r_rows = np.arange(S, dtype=np.int32)
    r_pmz = np.zeros((S,), np.float32)
    r_charge = np.zeros((S,), np.int32)

    out: dict[str, dict[str, list]] = {}
    for be in backends.names():
        pr = base._replace(backend=be, prefix_words=P)
        per_path: dict[str, list] = {}
        for path, db, p in (
                ("resident", db_p, pr),
                ("streamed", slab,
                 pr._replace(k_blocks=min(pr.k_blocks, slab_cap)))):
            rk = p.k_blocks * sm.max_r
            jaxpr = jax.make_jaxpr(
                lambda d, a, b, c, t1, t2, _p=p: search_mod._prefix_flags(
                    d, a, b, c, t1, t2, params=_p, dim=sm.dim))(
                db, qh[:, :P], qp, qc, thr, thr)
            ctx = {"dim": sm.dim, "n_words": P, "q_block": sm.q_block,
                   "rk": rk, "top_k": sm.top_k, "nqb": nqb,
                   "n_rows": int(db.pmz.shape[0])}
            per_path[path] = _eval_decls(f"prefix:{be}", jaxpr, ctx)

        jaxpr_r = jax.make_jaxpr(
            lambda *a, _p=pr: search_mod._rescore_rows_padded(
                *a, params=_p, dim=sm.dim))(
            r_hvs, r_rows, r_pmz, r_charge, qh, qp, qc)
        ctx_r = {"dim": sm.dim, "n_words": sm.n_words,
                 "q_block": sm.q_block, "rk": S, "top_k": sm.top_k,
                 "nqb": nqb, "n_rows": int(fx.resident.db.pmz.shape[0])}
        resc = _eval_decls(f"rescore:{be}", jaxpr_r, ctx_r)
        out[be] = {path: res + resc for path, res in per_path.items()}
    return out


def _slab_step_results(fx: _Fixture) -> list[C.ContractResult]:
    """The streamed path's whole slab step (q-block slice, scan, offset and
    fold into the running best): no host transfer, no 64-bit promotion."""
    from repro.serve.engine import _empty_run, _search_sorted_padded_slab
    from repro.serve.slabs import slab_arrays

    sm = fx.sm
    eng = fx.streamed.engine
    qh, qp, qc = fx.padded_queries()
    p = fx.resident.search_params(fx.qp_np, fx.qc_np)
    p = p._replace(k_blocks=min(p.k_blocks, eng.plan.slab_blocks))
    run = _empty_run(qh.shape[0], sm.top_k, None)
    j = jax.make_jaxpr(
        lambda r, d, a, b, c: _search_sorted_padded_slab(
            r, d, a, b, c, np.int32(0), np.int32(64), params=p, dim=sm.dim,
            n_qb=1))(run, slab_arrays(eng.layout, 0, eng.plan), qh, qp, qc)
    return [C.check_no_host_transfer(j, target="serve:slab_step"),
            C.check_dtype_stability(j, target="serve:slab_step",
                                    hv_words=sm.n_words)]


def _obs_results(fx: _Fixture) -> list[C.ContractResult]:
    """The ``trace_transparency`` contract: installing a ``repro.obs``
    tracer must (a) leave the traced hot jaxpr byte-identical — host-side
    spans cannot inject host-transfer prims into a program they never
    enter — and (b) change zero result bytes of a real resident AND
    streamed search. The traced side runs under a profiler session, so the
    spans' profiler annotations are exercised too. The tracer must also
    actually record every span of a search during the instrumented calls,
    or the check would be vacuous."""
    from repro.obs import trace as trace_mod

    target = "serve:obs"
    hvs, qp, qc = fx.q
    base = fx.resident.search_params(fx.qp_np, fx.qc_np)

    def snapshot():
        outs = []
        for pipe in (fx.resident, fx.streamed):
            out = pipe.search_encoded(hvs, qp, qc)
            outs.append(tuple(np.asarray(a).tobytes()
                              for a in out.result))
        return outs

    jaxpr_off = str(_trace_search(fx, fx.resident.db, base))
    res_off = snapshot()
    tracer = trace_mod.install(trace_mod.Tracer())
    try:
        with tempfile.TemporaryDirectory() as d, jax.profiler.trace(d):
            jaxpr_on = str(_trace_search(fx, fx.resident.db, base))
            res_on = snapshot()
    finally:
        trace_mod.uninstall()

    results = []
    if jaxpr_on != jaxpr_off:
        results.append(C.ContractResult(
            "trace_transparency", target, False,
            "hot search jaxpr changed with a tracer installed — a span "
            "leaked inside the traced function"))
    else:
        results.append(C.ContractResult(
            "trace_transparency", target, True,
            "hot search jaxpr byte-identical with tracer installed"))
    if res_on != res_off:
        results.append(C.ContractResult(
            "trace_transparency", target, False,
            "search results differ with a tracer installed"))
    else:
        results.append(C.ContractResult(
            "trace_transparency", target, True,
            "resident+streamed results byte-identical with tracer "
            "installed"))
    names = {ev.name for ev in tracer.events()}
    expected = {"pipeline.search", "pipeline.precursors_to_host",
                "pipeline.plan", "pipeline.scan", "search.sort_pad",
                "search.gather", "search.kernel", "search.restore",
                "pipeline.fdr", "serve.scan"}
    missing = expected - names
    if missing:
        results.append(C.ContractResult(
            "trace_transparency", target, False,
            f"tracer recorded no {sorted(missing)} spans — the "
            f"transparency check ran against uninstrumented code"))
    else:
        results.append(C.ContractResult(
            "trace_transparency", target, True,
            f"{tracer.n_recorded} spans recorded across "
            f"{len(names)} stages"))
    return results


def _recompile_results(fx: _Fixture) -> dict[str, list[C.ContractResult]]:
    """The runtime contract: repeated same-shaped serve calls must be free
    of jit-cache growth. One warmup + one armed call per (backend, path)."""
    from repro.core import backends, encode_backends
    from repro.core import search as search_mod
    from repro.serve import engine as engine_mod

    hvs, qp, qc = fx.q
    tracked = [
        ("search._search_sorted_padded", search_mod._search_sorted_padded),
        ("search._prefix_flags", search_mod._prefix_flags),
        ("search._rescore_rows_padded", search_mod._rescore_rows_padded),
        ("engine._search_sorted_padded_slab",
         engine_mod._search_sorted_padded_slab),
        ("engine._merge_partials", engine_mod._merge_partials),
        ("encode._preprocess_jit", encode_backends._preprocess_jit),
        ("encode._encode_batched_jit", encode_backends._encode_batched_jit),
    ]
    out: dict[str, list[C.ContractResult]] = {}
    for be in backends.names():
        results = []
        for path, pipe in (("resident", fx.resident),
                           ("streamed", fx.streamed)):
            guard = C.RecompileGuard(tracked)
            # warmup/compile: the plain scan AND the dimension cascade (its
            # survivor buckets are deterministic for same-shaped batches, so
            # steady-state repeats must hit the same jit cache entries)
            pipe.search_encoded(hvs, qp, qc, backend=be)
            pipe.search_encoded(hvs, qp, qc, backend=be,
                                prefix_words=_PREFIX_WORDS)
            guard.arm()
            pipe.search_encoded(hvs, qp, qc, backend=be)     # steady state
            pipe.search_encoded(hvs, qp, qc, backend=be,
                                prefix_words=_PREFIX_WORDS)
            results.append(guard.check(target=f"serve:loop[{path}:{be}]"))
        out[be] = results
    return out


# ---------------------------------------------------------------------------
# Assembly
# ---------------------------------------------------------------------------


def run(sm: SmokeShapes | None = None, *,
        with_recompile: bool = True) -> dict:
    """Full contract matrix -> JSON-able report dict (see module docstring)."""
    sm = sm or SmokeShapes()
    fx = _Fixture(sm)
    try:
        enc = _encode_results(fx)
        srch = _search_results(fx)
        pref = _prefix_results(fx)
        slab_res = _slab_step_results(fx)
        obs_res = _obs_results(fx)
        reco = _recompile_results(fx) if with_recompile else {}
    finally:
        fx.close()

    combos = []
    for e in sorted(enc):
        for (be, path, stage) in sorted(srch):
            cascade = stage == "narrow"
            results = list(enc[e]) + list(srch[(be, path, stage)])
            if path == "streamed":
                results += slab_res
            if not cascade and be in reco:
                results += [r for r in reco[be]
                            if f"[{path}:" in r.target]
            combos.append({
                "encode": e, "search": be, "path": path,
                "cascade": cascade, "prefix": False,
                "contracts": [r.as_dict() for r in results],
                "passed": all(r.passed for r in results),
            })
        for be in sorted(pref):
            for path in ("resident", "streamed"):
                results = list(enc[e]) + list(pref[be][path])
                combos.append({
                    "encode": e, "search": be, "path": path,
                    "cascade": False, "prefix": True,
                    "contracts": [r.as_dict() for r in results],
                    "passed": all(r.passed for r in results),
                })

    combos.append({
        "encode": "-", "search": "-", "path": "obs",
        "cascade": False, "prefix": False,
        "contracts": [r.as_dict() for r in obs_res],
        "passed": all(r.passed for r in obs_res),
    })

    n_checks = sum(len(c["contracts"]) for c in combos)
    failed = [c for c in combos if not c["passed"]]
    return {
        "smoke": dataclasses.asdict(sm),
        "n_combinations": len(combos),
        "n_checks": n_checks,
        "n_failed_combinations": len(failed),
        "combos": combos,
        "ok": not failed,
    }


def summarize(report: dict) -> str:
    """Human-readable digest of a :func:`run` report."""
    lines = [f"[analyze] {report['n_combinations']} combinations, "
             f"{report['n_checks']} contract checks"]
    seen: set[tuple] = set()
    for combo in report["combos"]:
        for r in combo["contracts"]:
            if r["passed"]:
                continue
            key = (r["target"], r["contract"], r.get("eqn"))
            if key in seen:
                continue
            seen.add(key)
            lines.append(f"  FAIL {r['target']} :: {r['contract']} — "
                         f"{r['detail']}")
            if r.get("eqn"):
                lines.append(f"       offending eqn: {r['eqn']}")
    lines.append("[analyze] " + ("ALL CONTRACTS HOLD" if report["ok"] else
                                 f"{report['n_failed_combinations']} "
                                 f"combination(s) FAILED"))
    return "\n".join(lines)
