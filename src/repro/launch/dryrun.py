import os
os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder CPU devices stand in for 2 TPU v5e pods; every
cell's step function must lower AND compile for the single-pod (16,16) and
multi-pod (2,16,16) production meshes. The compiled artifact yields
memory_analysis (fits?) and cost_analysis (FLOPs/bytes) plus parsed
collective traffic — the inputs to EXPERIMENTS.md §Roofline.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b \
        --shape train_4k [--multi-pod] [--out results/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import time
import traceback

import jax
import numpy as np

from repro.configs.registry import get_config, list_archs
from repro.configs.shapes import SHAPES, applicable
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import make_cell, make_step_fn
from repro.utils import hlo_cost
from repro.utils import roofline as rl


def _sharded_arg_bytes(args, in_specs, mesh) -> float:
    """Per-device bytes of the step inputs under their shardings."""
    total = 0.0
    for a_tree, s_tree in zip(args, in_specs):
        flat_a = jax.tree_util.tree_leaves(a_tree)
        flat_s, _ = jax.tree_util.tree_flatten(
            s_tree, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        for a, s in zip(flat_a, flat_s):
            n = float(np.prod(a.shape)) * jax.numpy.dtype(a.dtype).itemsize
            denom = 1
            for axis in (s or ()):
                if axis is None:
                    continue
                for ax in (axis if isinstance(axis, tuple) else (axis,)):
                    denom *= mesh.shape[ax]
            total += n / denom
    return total


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             n_microbatches: int = 4, verbose: bool = True,
             unroll: bool = False, chunk: int = 1024) -> dict:
    """unroll=True lowers with every scan unrolled so cost_analysis carries
    true whole-step FLOPs/bytes/collectives (XLA counts while bodies once);
    used for the single-pod roofline pass. Rolled scans (default) are the
    production/compile-check configuration."""
    from repro.utils import unrollctl
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = applicable(cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "multi_pod": multi_pod, "chips": chips,
    }
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec

    t0 = time.time()
    cell = make_cell(arch, shape_name, mesh=mesh,
                     n_microbatches=n_microbatches, chunk=chunk)
    step = make_step_fn(cell, n_microbatches=n_microbatches)

    from jax.sharding import NamedSharding

    def shardify(spec_tree):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))

    in_shardings = tuple(shardify(s) for s in cell.in_specs)
    jitted = jax.jit(step, in_shardings=in_shardings,
                     donate_argnums=cell.donate)
    with unrollctl.analysis_unroll(unroll):
        with mesh:
            lowered = jitted.lower(*cell.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

    # ---- analyses ------------------------------------------------------
    try:
        mem = compiled.memory_analysis()
        mem_rec = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        } if mem is not None else None
    except Exception:
        mem_rec = None

    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
    except Exception:
        cost = {}
    raw_flops = float(cost.get("flops", 0.0) or 0.0)
    raw_bytes = float(cost.get("bytes accessed", 0.0) or 0.0)

    # Trip-count-weighted per-chip cost from the partitioned module text.
    # compiled.as_text() is the per-DEVICE SPMD program, so flops/bytes/
    # collective bytes below are all PER-CHIP quantities.
    weighted = hlo_cost.analyze(compiled.as_text())
    flops = weighted["flops"]
    hbm_bytes = weighted["bytes"]
    coll = weighted["coll"]
    coll_total = float(weighted["coll_total"])

    if cell.kind == "train":
        model_flops = rl.model_flops_train(cell.n_params_active,
                                           cell.tokens_per_step)
    elif cell.kind == "prefill":
        model_flops = 2.0 * cell.n_params_active * cell.tokens_per_step
    else:
        model_flops = rl.model_flops_decode(cell.n_params_active,
                                            cell.tokens_per_step)

    roof = rl.Roofline(flops=flops, hbm_bytes=hbm_bytes,
                       coll_bytes=coll_total, chips=1,
                       peaks=rl.PEAKS["TPU v5 lite"],   # the pods modeled
                       model_flops=model_flops / chips)

    arg_bytes_per_dev = _sharded_arg_bytes(cell.args, cell.in_specs, mesh)

    rec.update(
        status="ok", kind=cell.kind, unrolled=unroll,
        seq_len=shape.seq_len, global_batch=shape.global_batch,
        n_params=cell.n_params, n_params_active=cell.n_params_active,
        tokens_per_step=cell.tokens_per_step,
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        memory_analysis=mem_rec,
        arg_bytes_per_device=arg_bytes_per_dev,
        arg_bytes_global=cell.arg_bytes,
        cost_analysis_raw={"flops": raw_flops, "bytes_accessed": raw_bytes},
        per_chip={"flops": flops, "bytes": hbm_bytes,
                  "coll_bytes": coll_total},
        collectives=coll,
        roofline=roof.as_dict(),
    )
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} mesh={rec['mesh']}: "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s "
              f"flops={flops:.3e} bytes={hbm_bytes:.3e} "
              f"coll={coll_total:.3e} args/dev={arg_bytes_per_dev/2**30:.2f}GiB "
              f"bottleneck={roof.bottleneck}")
        if mem_rec:
            print(f"[dryrun]   memory_analysis: {mem_rec}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--unroll", action="store_true",
                    help="unroll scans for exact cost accounting (roofline)")
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = []
    if args.all:
        cells = [(a, s) for a in list_archs() for s in SHAPES]
    else:
        archs = [args.arch] if args.arch else list_archs()
        shapes = [args.shape] if args.shape else list(SHAPES)
        cells = [(a, s) for a in archs for s in shapes]

    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}__{shape}__{'pod2' if mp else 'pod1'}"
            out_path = os.path.join(args.out, tag + ".json")
            if os.path.exists(out_path):
                print(f"[dryrun] {tag}: cached")
                continue
            try:
                rec = run_cell(arch, shape, multi_pod=mp,
                               n_microbatches=args.microbatches,
                               unroll=args.unroll, chunk=args.chunk)
            except Exception as e:
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                       "status": "error", "error": f"{type(e).__name__}: {e}"}
                failures += 1
            with open(out_path, "w") as f:
                json.dump(rec, f, indent=1)
            jax.clear_caches()
    print(f"[dryrun] done; {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
