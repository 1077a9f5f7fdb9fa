"""Find the highest rate an open-loop serve cell sustains: a one-off sweep.

    python3 bench/sweep.py --config iprg2012 --traffic open_poisson \\
        --seed <n> --rates 10,20,40,80 --phase-seconds 15 [--out sweep.jsonl]

One process, one set-up and one ``oms.py serve`` session, as in a run of
the cell; after the warm-up, each rate in turn is offered for
``--phase-seconds`` (the same quantile gaps as the cell, seeded order), with
a 3 s pause between phases. A phase is sustained when every request got its
response and the median latency of its last third is under twice that of
its first third plus 50 ms (no growing backlog); the sweep stops after the
first phase that is not. Prints one JSON line per phase, and last the
highest sustained rate with the offered rate the cell should use, 4/5 of it.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench/sweep.py")
    ap.add_argument("--config", required=True,
                    help="a file name under bench/configs/, without .json")
    ap.add_argument("--traffic", required=True,
                    help="an open-loop traffic file under bench/traffic/")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--phase-seconds", type=float, default=15.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.COMPILE_CACHE
    import jax
    import numpy as np

    jax.config.update("jax_compilation_cache_dir", harness.COMPILE_CACHE)
    if jax.devices()[0].platform != "tpu":
        print("[sweep] needs a TPU", file=sys.stderr)
        return 1
    from bench.drivers import open_loop

    cell = harness.make_cell(
        f"{args.config}.{args.traffic}", f"bench/configs/{args.config}.json",
        args.traffic, seed=args.seed, seconds=args.phase_seconds,
        trace=False, metric_defs=[])
    harness.listen_for_compiles()
    rates = [float(r) for r in args.rates.split(",")]
    tr = cell.traffic
    n_warm = max(1, round(rates[0] * tr["warmup_s"]))
    sizes = [max(1, round(r * args.phase_seconds)) for r in rates]
    queries, server, path = open_loop.setup(cell, n_warm + sum(sizes))
    lines = open_loop.request_lines(queries, range(n_warm + sum(sizes)))
    client = open_loop.Client(server)
    out = open(args.out, "w") if args.out else None
    best = None
    try:
        t = time.perf_counter() + 0.05
        client.play(lines[:n_warm], range(n_warm),
                    open_loop.arrival_offsets(n_warm, tr["warmup_s"],
                                              cell.warm_seed), t)
        start = n_warm
        for rate, n in zip(rates, sizes):
            ids = list(range(start, start + n))
            t = time.perf_counter() + 0.05
            b0 = server.batch_counts()
            client.late.clear()
            client.play(lines[start:start + n], ids,
                        open_loop.arrival_offsets(n, args.phase_seconds,
                                                  cell.order_seed), t)
            time.sleep(3.0)
            got = open_loop.parse_responses(list(server.responses))
            b1 = server.batch_counts()
            lat = [(client.due[i], got[i][0] - client.due[i])
                   for i in ids if i in got]
            lat.sort()
            third = max(1, len(lat) // 3)
            first = np.median([x for _, x in lat[:third]]) if lat else None
            last = np.median([x for _, x in lat[-third:]]) if lat else None
            stats = open_loop.latency_stats(ids, client.due, got)
            ok = (stats["missing"] == 0 and lat
                  and last < 2 * first + 0.05)
            row = {"rate_per_s": rate, "requests": n, **stats,
                   "p50_first_third_ms": None if first is None
                   else first * 1e3,
                   "p50_last_third_ms": None if last is None else last * 1e3,
                   "compiles": harness.compiles_between(t, time.perf_counter()),
                   "batches": b1[0] - b0[0],
                   "batch_mean": ((b1[1] - b0[1]) / (b1[0] - b0[0])
                                  if b1[0] > b0[0] else None),
                   "late_max_ms": float(max(client.late) * 1e3),
                   "sustained": bool(ok)}
            print(json.dumps(row), flush=True)
            if out:
                out.write(json.dumps(row) + "\n")
            start += n
            if not ok:
                break
            best = rate
    finally:
        server.stop(timeout=120.0)
        shutil.rmtree(path, ignore_errors=True)
    verdict = {"cell": cell.name, "highest_sustained_per_s": best,
               "offered_per_s": None if best is None else 0.8 * best}
    print(json.dumps(verdict), flush=True)
    if out:
        out.write(json.dumps(verdict) + "\n")
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
