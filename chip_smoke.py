"""Chip smoke: ingest -> batch search -> serve on one TPU at iPRG2012 size.

Run from the root of a checkout, on a machine with one TPU:

    python chip_smoke.py

Every phase runs in this one process, because a chip belongs to one process
at a time. The phases drive the normal entry points:

  * ingest: the iPRG2012-shaped library at full width and full size
    (1.16M targets + 1.16M decoys, dim 4096, bin 0.05, 20 ppm / 75 Da,
    32 levels, ``word_tiled`` encoder) through ``OMSPipeline.ingest`` into a
    ``LibraryStore`` in a temporary directory outside the checkout;
  * batch search: ``OMSPipeline.from_store`` cold start, then a resident
    search of 2048 queries with the ``vpu`` backend and again with the fused
    Pallas kernel; the two results must be identical, and the compiled fused
    program must contain the kernel (``tpu_custom_call``);
  * reference: for a seeded sample of 64 queries, a host NumPy popcount scan
    of every same-charge row in each precursor window must give the same
    top-1 (sim, row), ranked (sim desc, row asc), in both windows;
  * serve: 128 requests through ``oms.py serve`` (micro-batcher over the
    streaming engine, default ``--slab-rows``); every response must equal
    the resident answer for its query, and none may be an error.

Earlier lines print sizes, per-phase seconds and agreement counts; the
seconds are one smoke run's, not benchmark figures. The last line is
``{"ok": true, "device": {...}}``. With no TPU (or outside a checkout) the
script exits non-zero and prints no result line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import io
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

SEED = 0
N_QUERIES = 2048          # resident batch search
N_REFERENCE = 64          # queries checked against the NumPy scan
N_SERVE = 128             # requests through the serve loop


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def reference_top1(db_hvs, db_pmz, db_charge, q_hv, q_pmz, q_charge, *,
                   dim: int, ppm_tol: float, open_tol_da: float):
    """Plain NumPy dual-window top-1: ((std_sim, std_row), (open_sim,
    open_row)), -1 where a window holds no row; ties go to the lowest row.
    Window arithmetic is float32, as on the device."""
    dp = np.abs(db_pmz - q_pmz)
    rows = np.flatnonzero((db_charge == q_charge)
                          & (dp <= np.float32(open_tol_da)))
    sims = dim - np.bitwise_count(db_hvs[rows] ^ q_hv).sum(
        axis=1, dtype=np.int64)
    in_std = dp[rows] <= q_pmz * np.float32(ppm_tol * 1e-6)

    def top1(sel):
        if not sel.any():
            return -1, -1
        s = np.where(sel, sims, -1)
        best = int(s.max())
        return best, int(rows[np.flatnonzero(s == best)[0]])

    return top1(in_std), top1(np.ones(rows.shape, bool))


def run_smoke(*, n_refs: int | None = None, n_queries: int = N_QUERIES,
              n_reference: int = N_REFERENCE, n_serve: int = N_SERVE,
              dim: int | None = None, store_root: str | None = None) -> dict:
    """All phases; raises on any failure. ``n_refs``/``dim`` default to the
    iPRG2012 deployment (smaller values are for rehearsal only)."""
    import jax

    from repro.core import OMSConfig, OMSPipeline
    from repro.core.search import _search_sorted_padded, sort_pad_plan
    from repro.data.spectra import iprg2012_config, make_dataset
    from repro.launch import oms

    lib = iprg2012_config(scale=1.0, seed=SEED)
    lib = dataclasses.replace(lib, n_queries=n_queries,
                              n_refs=n_refs or lib.n_refs)
    cfg = OMSConfig() if dim is None else OMSConfig(dim=dim)
    t = {}

    # -- ingest ---------------------------------------------------------------
    t0 = time.perf_counter()
    ds = make_dataset(lib)
    queries = ds.queries
    jax.block_until_ready(queries)
    t["dataset"] = time.perf_counter() - t0
    store_dir = tempfile.mkdtemp(prefix="oms_smoke_", dir=store_root)
    try:
        t0 = time.perf_counter()
        store = OMSPipeline.ingest(cfg, ds.refs, store_dir)
        t["ingest"] = time.perf_counter() - t0
        del ds
        log(f"library {lib.n_refs} targets -> {store.n_rows} rows "
            f"({store.n_targets} targets + decoys), dim {cfg.dim}, bin "
            f"{cfg.bin_size}, {cfg.ppm_tol} ppm / {cfg.open_tol_da} Da, "
            f"{cfg.n_levels} levels, encoder {cfg.encode_backend}; store "
            f"{store.nbytes() / 2**30:.3f} GiB in {len(store.shards)} shards")

        # -- cold start + resident batch search -----------------------------
        t0 = time.perf_counter()
        pipe = OMSPipeline.from_store(store_dir)
        jax.block_until_ready(pipe.db.hvs)
        t["cold_start"] = time.perf_counter() - t0
        log(f"resident DB {pipe.db.n_rows} rows x {pipe.db.n_words} words "
            f"({pipe.db.n_blocks} blocks of {pipe.cfg.max_r})")

        hvs, q_pmz, q_charge = pipe.encode_queries(queries)
        jax.block_until_ready(hvs)
        results = {}
        for be in ("vpu", "fused"):
            t0 = time.perf_counter()
            out = pipe.search_encoded(hvs, q_pmz, q_charge, backend=be)
            jax.block_until_ready(out)
            t[f"first_call_{be}"] = time.perf_counter() - t0
            t0 = time.perf_counter()
            out = pipe.search_encoded(hvs, q_pmz, q_charge, backend=be)
            jax.block_until_ready(out)
            t[f"steady_{be}"] = time.perf_counter() - t0
            results[be] = jax.tree_util.tree_map(np.asarray, out.result)
        res = results["vpu"]
        for f in res._fields:
            check(np.array_equal(getattr(res, f),
                                 getattr(results["fused"], f)),
                  f"vpu and fused disagree on {f}")
        log(f"batch search {n_queries} queries: vpu == fused on all "
            f"{len(res._fields)} result arrays; open-window matches "
            f"{int((res.open_idx[:, 0] >= 0).sum())}/{n_queries}")

        qp_np, qc_np = np.asarray(q_pmz), np.asarray(q_charge)
        params = pipe.search_params(qp_np, qc_np, backend="fused")
        gather, _ = sort_pad_plan(q_pmz, q_charge, params.q_block,
                                  q_charge_np=qc_np)
        hlo = _search_sorted_padded.lower(
            pipe.db, hvs[gather], q_pmz[gather], q_charge[gather],
            params=params, dim=pipe.cfg.dim).compile().as_text()
        check("tpu_custom_call" in hlo,
              "compiled fused search holds no tpu_custom_call")
        log(f"compiled fused search program holds the Pallas kernel "
            f"(k_blocks={params.k_blocks})")

        # -- plain NumPy reference ---------------------------------------------
        t0 = time.perf_counter()
        db_hvs = np.asarray(pipe.db.hvs)
        db_pmz = np.asarray(pipe.db.pmz)
        db_charge = np.asarray(pipe.db.charge)
        hv_np = np.asarray(hvs)
        sample = np.sort(np.random.default_rng(SEED).choice(
            n_queries, size=min(n_reference, n_queries), replace=False))
        agree = 0
        for i in sample:
            (ss, sr), (os_, or_) = reference_top1(
                db_hvs, db_pmz, db_charge, hv_np[i], qp_np[i], qc_np[i],
                dim=pipe.cfg.dim, ppm_tol=pipe.cfg.ppm_tol,
                open_tol_da=pipe.cfg.open_tol_da)
            got = (int(res.std_sim[i, 0]), int(res.std_row[i, 0]),
                   int(res.open_sim[i, 0]), int(res.open_row[i, 0]))
            check(got == (ss, sr, os_, or_),
                  f"query {i}: search (std sim,row, open sim,row) {got} != "
                  f"reference {(ss, sr, os_, or_)}")
            agree += 1
        t["reference"] = time.perf_counter() - t0
        log(f"reference: {agree}/{len(sample)} sampled queries match the "
            f"NumPy popcount scan in both windows")
        del db_hvs, pipe

        # -- serve ---------------------------------------------------------------
        served = type(queries)(*(np.asarray(a)[:n_serve] for a in queries))
        stdin = io.StringIO("".join(oms.request_lines(served)))
        stdout = io.StringIO()
        t0 = time.perf_counter()
        saved_stdin, sys.stdin = sys.stdin, stdin
        try:
            with contextlib.redirect_stdout(stdout):
                oms.cmd_serve(["--store", store_dir])
        except SystemExit as e:
            raise SmokeFailure(f"serve exited with {e.code}") from e
        finally:
            sys.stdin = saved_stdin
        t["serve"] = time.perf_counter() - t0
        responses = [json.loads(line) for line in
                     stdout.getvalue().splitlines()]
        check(len(responses) == len(served.pmz),
              f"{len(responses)} responses to {len(served.pmz)} requests")
        for r in responses:
            i = r["id"]
            check("error" not in r, f"request {i} answered {r.get('error')}")
            want = {"std": {"idx": res.std_idx[i].tolist(),
                            "sim": res.std_sim[i].tolist()},
                    "open": {"idx": res.open_idx[i].tolist(),
                             "sim": res.open_sim[i].tolist()}}
            check({"std": r["std"], "open": r["open"]} == want,
                  f"request {i}: served {r} != resident {want}")
        log(f"serve: {len(responses)}/{len(served.pmz)} responses equal the "
            f"resident answers, no errors")
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)

    log("smoke timings (one run, not benchmark figures), seconds: "
        + ", ".join(f"{k} {v:.2f}" for k, v in t.items()))
    return t


def main() -> int:
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"[smoke] no TPU: JAX found platform {dev.platform!r}; the "
              f"smoke runs only on a TPU", file=sys.stderr)
        return 1
    here = os.path.dirname(os.path.abspath(__file__))
    if not os.path.isdir(os.path.join(here, "src", "repro")):
        print("[smoke] run from a checkout: src/repro is missing next to "
              "chip_smoke.py", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(here, "src"))
    from repro.launch import oms
    log(f"device {dev.platform} / {dev.device_kind} x {len(devs)}; compile "
        f"cache {oms.enable_compile_cache()}")
    try:
        run_smoke()
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
