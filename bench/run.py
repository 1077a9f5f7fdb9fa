"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell is an entry of ``workloads`` in
``BENCHMARK.json``; everything it needs is found by name under ``bench/``
(see ``bench/harness.py``). With ``--trace 0`` the result line carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics from a
profiler trace of the window. Sizes and phase seconds go to stderr, and the
numbers compared with the reference, each beside its limit, are the last
lines there. The last line on stdout is the result: a JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` ``breakdown``), then ``checks``.

Runs only on a TPU with as many chips as the cell asks for; elsewhere it
exits 1 and prints no result. JAX's persistent compilation cache is kept in
``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the program with the configuration's control "
                         "path switched on (check.control); for limit "
                         "readings, never for measurement")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0:
        print("[bench] --seed must be >= 0", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("[bench] no program next to the benchmark: src/repro is "
              "missing from the checkout", file=sys.stderr)
        return 1
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.COMPILE_CACHE
    import jax

    jax.config.update("jax_compilation_cache_dir", harness.COMPILE_CACHE)
    bm = harness.benchmark()
    cell, w = harness.cell_from_benchmark(
        bm, args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace))
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < w["chips"]:
        print(f"[bench] the cell needs {w['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 1
    cell.t_start = T0
    if args.control:
        cell.program_overrides.update(cell.cfg["check"]["control"])
    harness.log(f"cell {cell.name} seed {args.seed} seconds {args.seconds} "
                f"trace {args.trace} on {len(devs)} x {devs[0].device_kind}"
                + (f", control {cell.program_overrides}" if args.control
                   else ""))
    line = harness.run_cell(cell)
    harness.log("sizes and phases: " + json.dumps(
        {**cell.info, **{f"{k}_s": v for k, v in cell.phases.items()}},
        sort_keys=True))
    print(json.dumps(line), flush=True)
    for name, v, lim in cell.checks:
        print(f"[bench] check {name} {v} limit {lim}", file=sys.stderr,
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
