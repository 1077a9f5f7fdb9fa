"""Roofline reporter: reads results/dryrun/*.json into the §Roofline table.

Also derives the OMS-engine roofline (the paper's workload) analytically from
the same v5e constants, for the §Perf comparison of the paper-faithful VPU
path vs the beyond-paper MXU path.

``tune_sweeps`` runs the tile-sweep harness (``repro.tune.sweep``) over the
tunable backends and reports measured-vs-modeled: per backend one row whose
``us_per_call`` is the MODELED roofline bound at the hand-picked default
tiles (deterministic — the history timing gate cannot flake on CI wall
clock) and whose ``model_flops``/``model_bytes`` tokens are structural
(0% drift tolerance in ``benchmarks/history.py``). The measured medians,
the sweep winner, and the tuned-vs-default speedup ride along as
non-structural derived tokens. Env knobs: ``BENCH_TUNE_DIM`` / ``_K`` /
``_Q`` / ``_ROWS`` / ``_GRID`` / ``_ITERS``.
"""
from __future__ import annotations

import glob
import json
import os

from benchmarks.common import emit
from repro.utils.roofline import PEAKS

V5E = PEAKS["TPU v5 lite"]   # the analytic OMS model below is for this chip


def lm_table(out_dir="results/dryrun"):
    rows = []
    for f in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        r = json.load(open(f))
        if r.get("multi_pod"):
            continue
        if r["status"] != "ok":
            emit(f"roofline/{r['arch']}_x_{r['shape']}", 0.0,
                 f"SKIPPED: {r.get('reason', r.get('error', ''))[:80]}")
            continue
        roof = r["roofline"]
        t_bound = max(roof["t_compute_s"], roof["t_memory_s"],
                      roof["t_collective_s"])
        emit(f"roofline/{r['arch']}_x_{r['shape']}", t_bound * 1e6,
             f"tC={roof['t_compute_s']:.2e}s tM={roof['t_memory_s']:.2e}s "
             f"tX={roof['t_collective_s']:.2e}s bneck={roof['bottleneck']} "
             f"useful={roof['useful_flops_frac']*100:.1f}% "
             f"roofline_frac={roof['roofline_fraction']*100:.2f}%")
        rows.append(r)
    return rows


def oms_roofline(n_refs=1_160_000, n_queries=2048, dhv=4096, q_block=64,
                 reduction=5.5):
    """Three-term roofline for the paper's own workload on one v5e chip."""
    W = dhv // 32
    cmp_total = n_refs * n_queries / reduction  # blocked pruning
    # VPU (paper-faithful): ~10 int ops/word; packed bytes amortised
    vpu_ops = cmp_total * W * 10
    bytes_ = cmp_total * (W * 4) / q_block + n_queries * W * 4
    t_vpu = vpu_ops / 9.6e12
    t_mem = bytes_ / V5E.hbm_bw
    emit("roofline/oms_vpu_paper_faithful", max(t_vpu, t_mem) * 1e6,
         f"tC={t_vpu:.2e}s tM={t_mem:.2e}s "
         f"bneck={'compute' if t_vpu > t_mem else 'memory'}")
    # MXU (beyond-paper): 2*Dhv int8 ops per comparison at the int8 peak
    mxu_ops = cmp_total * 2 * dhv
    t_mxu = mxu_ops / V5E.int8_ops
    emit("roofline/oms_mxu_beyond_paper", max(t_mxu, t_mem) * 1e6,
         f"tC={t_mxu:.2e}s tM={t_mem:.2e}s "
         f"speedup_vs_vpu={max(t_vpu, t_mem)/max(t_mxu, t_mem):.2f}x")
    # collective: winner merge over model axis
    t_coll = (n_queries * 16) / V5E.ici_bw
    emit("roofline/oms_collective_merge", t_coll * 1e6,
         "16B/query winner merge — negligible by construction")


def tune_sweeps(dim=512, k=2, q_rows=16, r_rows=1024, grid="tiny", iters=3):
    """Tile-sweep rows for the tunable backends (see module docstring for
    which tokens are structural vs timing-derived)."""
    from repro import tune
    from repro.tune import sweep as sweep_mod

    results = sweep_mod.run_sweeps(tune.SWEPT_BACKENDS, dim=dim, k=k,
                                   q_rows=q_rows, r_rows=r_rows, grid=grid,
                                   iters=iters)
    for be in sorted(results):
        rows = results[be]
        if not rows:
            continue
        win = rows[0]
        want = tune.kernel_defaults(be)
        default = next((r for r in rows if r.tiles == want), win)
        speed = (default.median_us / win.median_us
                 if win.median_us > 0 else 0.0)
        frac = ("n/a" if win.roofline_frac is None
                else f"{win.roofline_frac:.5f}")
        # us_per_call is the modeled bound: 0.0 where the device has no
        # published peaks (the derived tokens say "n/a")
        emit(f"tune/{be}/d{dim}_q{q_rows}xr{r_rows}",
             default.t_bound_us or 0.0,
             f"model_flops={default.model_flops:.0f} "
             f"model_bytes={default.model_bytes:.0f} "
             f"default[{default.tiles_str()}] measured={default.median_us:.1f}us "
             f"winner[{win.tiles_str()}] measured={win.median_us:.1f}us "
             f"speedup_vs_default={speed:.2f}x "
             f"roofline_frac={frac}")


def main():
    if glob.glob("results/dryrun/*.json"):
        lm_table()
    oms_roofline()
    tune_sweeps(dim=int(os.environ.get("BENCH_TUNE_DIM", 512)),
                k=int(os.environ.get("BENCH_TUNE_K", 2)),
                q_rows=int(os.environ.get("BENCH_TUNE_Q", 16)),
                r_rows=int(os.environ.get("BENCH_TUNE_ROWS", 1024)),
                grid=os.environ.get("BENCH_TUNE_GRID", "tiny"),
                iters=int(os.environ.get("BENCH_TUNE_ITERS", 3)))


if __name__ == "__main__":
    main()
