"""Run one batch cell traced, as ``bench/run.py --trace 1`` does, and split
the window's device-idle time by the program span open on the host.

    python3 bench/tools/span_split.py --workload <cell> --seed <n> --seconds <s>

On a TPU, from the root of a checkout. The harness reduces the trace with
``bench.trace_reduce`` alone; here its ``load`` is wrapped so that the same
file is also split by program span (``bench.span_reduce``) before the
harness removes it. The last line printed is the run's result line with the
three idle metrics of ``bench/metrics/`` (``plan_idle_ms``,
``dispatch_idle_ms``, ``unspanned_idle_ms``) among its metrics and, under
``idle_ms_per_run``, the idle milliseconds per query run under each
program span.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
METRICS = ("plan_idle_ms.batch", "dispatch_idle_ms.batch",
           "unspanned_idle_ms.batch")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench/tools/span_split.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, span_reduce, trace_reduce

    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.COMPILE_CACHE
    import jax

    jax.config.update("jax_compilation_cache_dir", harness.COMPILE_CACHE)
    cell, w = harness.cell_from_benchmark(
        harness.benchmark(), args.workload, seed=args.seed,
        seconds=args.seconds, trace=True)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < w["chips"]:
        print(f"[bench] the cell needs {w['chips']} TPU chip(s)",
              file=sys.stderr)
        return 1
    cell.t_start = T0
    cell.metric_defs += [{"name": n, "unit": "ms"} for n in METRICS]
    load = trace_reduce.load

    def load_and_split(path):
        trace = load(path)
        cell.layer["idle_spans"] = span_reduce.idle_by_span(
            trace, span_reduce.load_spans(path))
        return trace

    trace_reduce.load = load_and_split
    line = harness.run_cell(cell)
    runs = cell.layer["runs"]
    line["idle_ms_per_run"] = {
        k: v * 1e3 / runs for k, v in sorted(
            cell.layer.get("idle_spans", {}).items(), key=lambda kv: -kv[1])}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
