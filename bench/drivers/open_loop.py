"""Open-loop serving through ``oms.py serve``.

Set-up generates the library and the requests' query spectra from the seed,
ingests the library into a store, and starts ``cmd_serve`` of
``repro.launch.oms`` unchanged, in a thread of this process, with its stdin
and stdout on pipes: the benchmark is the client at the other end. The
server cold-starts from the store; then warm-up traffic (its own queries,
arrival order from another sub-seed) runs for ``warmup_s`` at the cell's
rate, and the window's traffic follows without a pause.

The traffic file's parameters:

* ``rate_per_s``: mean arrival rate. Inter-arrival gaps are the quantiles
  of an exponential distribution, scaled so the window's gaps add up to
  ``--seconds``, in an order drawn from the seed, so every seed offers the
  same gaps. Every request carries a distinct query spectrum;
* ``warmup_s``: seconds of warm-up traffic before the window;
* ``serve_args``: further ``oms.py serve`` flags (``--resident`` among them
  for the resident path); ``program``: further search settings as flags.

A request's latency runs from when it was due to when its response line
reached the client. The generator's lateness (send time minus due time) is
reported beside it.

Checked after the window: every window response against the plain
reference (``response_mismatch``: differing (index, similarity) entries of
both windows' top-k, limit 0), and ``missing_responses`` (limit 0).
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import threading
import time

import numpy as np

from bench import faults, harness


def request_lines(queries, ids) -> list[str]:
    """One serve request line per id, carrying query row ``id``,
    zero-intensity peaks left out."""
    mz = np.asarray(queries.mz)
    inten = np.asarray(queries.intensity)
    pmz = np.asarray(queries.pmz)
    charge = np.asarray(queries.charge)
    out = []
    for r in ids:
        r = int(r)
        keep = inten[r] > 0
        out.append(json.dumps(
            {"id": r, "pmz": float(pmz[r]), "charge": int(charge[r]),
             "mz": mz[r][keep].tolist(),
             "intensity": inten[r][keep].tolist()},
            separators=(",", ":")) + "\n")
    return out


def arrival_offsets(n: int, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the first) of ``n`` Poisson arrivals: the same set
    of gaps for every seed, in a seeded order, adding up to ``seconds``."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps *= seconds / gaps.sum()
    gaps = np.random.default_rng(seed).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def serve_argv(cfg: dict, traffic: dict, store: str, overrides: dict
               ) -> list[str]:
    enc, s = cfg["encoding"], cfg["search"]
    if s["ppm_tol"] != 20.0 or s["fdr_threshold"] != 0.01:
        raise ValueError("oms.py serve fixes ppm_tol 20 and fdr 0.01; the "
                         "configuration states otherwise")
    argv = ["--store", store, "--max-r", str(s["max_r"]),
            "--q-block", str(s["q_block"]), "--open-tol", str(s["open_tol_da"]),
            "--backend", s["backend"], "--top-k", str(s["top_k"]),
            "--encode-backend", enc["encode_backend"],
            "--encode-batch", str(enc["encode_batch"])]
    argv += [str(a) for a in traffic.get("serve_args", [])]
    for k, v in overrides.items():
        argv += ["--" + k.replace("_", "-"), str(v)]
    return argv


class Server:
    """``cmd_serve`` in a thread, with this process's stdin and stdout
    swapped for pipes while it runs; instrumented from outside to read
    its micro-batcher's counters and time its cold start."""

    def __init__(self, argv: list[str], cell_faults=()):
        self.argv = argv
        self.faults = tuple(cell_faults)
        self.batchers: list = []
        self.cold_start_s = None
        self.ready = threading.Event()
        self.error: BaseException | None = None
        self.responses: list[tuple[float, str]] = []

    def _instrument(self):
        import jax

        import repro.serve as serve_pkg
        from repro.launch import oms

        orig_batcher, orig_pipe = serve_pkg.MicroBatcher, oms.OMSPipeline
        server = self

        class Batcher(orig_batcher):
            def __init__(self, *a, **k):
                super().__init__(*a, **k)
                server.batchers.append(self)

        class Pipe(orig_pipe):
            @classmethod
            def from_store(cls, *a, **k):
                t0 = time.perf_counter()
                p = orig_pipe.from_store(*a, **k)
                if p.db is not None:
                    jax.block_until_ready(p.db.hvs)
                server.cold_start_s = time.perf_counter() - t0
                if server.faults:
                    p.search_encoded = faults.wrap_search(p.search_encoded,
                                                          server.faults)
                server.ready.set()
                return p

        serve_pkg.MicroBatcher, oms.OMSPipeline = Batcher, Pipe
        return lambda: (setattr(serve_pkg, "MicroBatcher", orig_batcher),
                        setattr(oms, "OMSPipeline", orig_pipe))

    def start(self):
        from repro.launch import oms

        req_r, req_w = os.pipe()
        resp_r, resp_w = os.pipe()
        self._srv_in = os.fdopen(req_r, "r")
        self._srv_out = os.fdopen(resp_w, "w")
        self.client_out = os.fdopen(req_w, "w")
        self._client_in = os.fdopen(resp_r, "r")
        self._restore = self._instrument()
        self._saved = sys.stdin, sys.stdout
        sys.stdin, sys.stdout = self._srv_in, self._srv_out

        def serve():
            try:
                oms.cmd_serve(self.argv)
            except BaseException as e:      # SystemExit included
                self.error = e
            finally:
                self.ready.set()

        def read():
            for line in self._client_in:
                self.responses.append((time.perf_counter(), line))

        self._thread = threading.Thread(target=serve, name="bench-serve",
                                        daemon=True)
        self._reader = threading.Thread(target=read, name="bench-client",
                                        daemon=True)
        self._reader.start()
        self._thread.start()

    def send(self, line: str) -> None:
        self.client_out.write(line)
        self.client_out.flush()

    def batch_counts(self) -> tuple[int, int]:
        b = self.batchers[-1] if self.batchers else None
        return (b.n_batches, b.n_queries) if b is not None else (0, 0)

    def stop(self, timeout: float) -> bool:
        """Close the request stream and wait for the server to answer
        everything; True if it ended in time."""
        self.client_out.close()
        self._thread.join(timeout)
        ended = not self._thread.is_alive()
        sys.stdin, sys.stdout = self._saved
        self._restore()
        if ended:
            self._srv_out.close()
            self._reader.join(timeout)
            self._srv_in.close()
        return ended


class Client:
    """Sends request lines at their due times and keeps the schedule."""

    def __init__(self, server: Server):
        self.server = server
        self.due: dict[int, float] = {}
        self.late: list[float] = []

    def play(self, lines, ids, offsets, t_base: float) -> None:
        from jax.profiler import TraceAnnotation

        for line, rid, off in zip(lines, ids, offsets):
            due = t_base + off
            wait = due - time.perf_counter()
            if wait > 0:
                with TraceAnnotation("bench.arrival_wait"):
                    time.sleep(wait)
            self.server.send(line)
            self.due[int(rid)] = due
            self.late.append(time.perf_counter() - due)


def parse_responses(responses) -> dict[int, tuple[float, dict]]:
    out = {}
    for t, line in responses:
        try:
            obj = json.loads(line)
            out[int(obj["id"])] = (t, obj)
        except (ValueError, KeyError, TypeError):
            continue
    return out


def latency_stats(ids, due: dict, got: dict) -> dict:
    lat = np.asarray([got[i][0] - due[i] for i in ids if i in got])
    return {"n": int(lat.size),
            "p50_ms": float(np.percentile(lat, 50) * 1e3) if lat.size else None,
            "p95_ms": float(np.percentile(lat, 95) * 1e3) if lat.size else None,
            "errors": sum(1 for i in ids if i in got and "error" in got[i][1]),
            "missing": sum(1 for i in ids if i not in got)}


def setup(cell: harness.Cell, n_queries: int):
    """Data, store and a started server; returns (queries, server, path)."""
    ds = harness.make_data(cell, n_queries)
    path = harness.ingest(cell, ds.refs)
    queries = ds.queries
    del ds
    server = Server(serve_argv(cell.cfg, cell.traffic, path,
                               cell.program_overrides), cell.faults)
    with cell.phase("cold_start"):
        server.start()
        server.ready.wait()
    if server.error is not None:
        raise RuntimeError(f"oms serve failed at start: {server.error!r}")
    cell.phases["cold_start"] = server.cold_start_s
    return queries, server, path


def run(cell: harness.Cell) -> dict:
    tr = cell.traffic
    rate = float(tr["rate_per_s"])
    n_warm = max(1, round(rate * tr["warmup_s"]))
    n_win = max(1, round(rate * cell.seconds))
    queries, server, path = setup(cell, n_warm + n_win)
    try:
        warm_ids = list(range(n_warm))
        win_ids = list(range(n_warm, n_warm + n_win))
        warm_lines = request_lines(queries, warm_ids)
        win_lines = request_lines(queries, win_ids)
        warm_off = arrival_offsets(n_warm, tr["warmup_s"], cell.warm_seed)
        win_off = arrival_offsets(n_win, cell.seconds, cell.order_seed)
        client = Client(server)
        t_base = time.perf_counter() + 0.05
        with cell.phase("warmup"):
            client.play(warm_lines, warm_ids, warm_off, t_base)
        t_win = t_base + tr["warmup_s"]
        with harness.window(cell) as w:
            b0 = server.batch_counts()
            client.late.clear()
            client.play(win_lines, win_ids, win_off, t_win)
            ended = server.stop(timeout=cell.seconds + 60.0)
            got = parse_responses(server.responses)
            last = max((got[i][0] for i in win_ids if i in got),
                       default=time.perf_counter())
            w.t1 = last
            b1 = server.batch_counts()
        cell.phases["setup_total"] = t_win - cell.t_start
    finally:
        shutil.rmtree(path, ignore_errors=True)
    if not ended:
        harness.log("the server did not end within a minute of the close")
    stats = latency_stats(win_ids, client.due, got)
    late = np.asarray(client.late)
    dq, db = b1[1] - b0[1], b1[0] - b0[0]
    cell.layer.update(batch_size_mean=dq / db if db else None,
                      compiles_in_window=w.compiles)
    cell.info.update(requests=n_win, warmup_requests=n_warm, rate_per_s=rate,
                     late_p50_ms=float(np.percentile(late, 50) * 1e3),
                     late_max_ms=float(late.max() * 1e3),
                     compiles_in_window=w.compiles, window_s=w.elapsed)
    harness.log(f"window: {stats['n']}/{n_win} responses, p50 "
                f"{stats['p50_ms']} ms, p95 {stats['p95_ms']} ms, generator "
                f"late p50 {cell.info['late_p50_ms']:.3f} ms max "
                f"{cell.info['late_max_ms']:.3f} ms, {w.compiles} compiles, "
                f"{db} batches of mean {cell.layer['batch_size_mean']}")
    peak = harness.memory_peak()
    del server
    with cell.phase("reference"):
        _check(cell, queries, win_ids, got)
    cell.check("missing_responses", stats["missing"], 0)
    return {"attempted": n_win, "failed": stats["errors"] + stats["missing"],
            "peak": peak,
            "e2e": {"serve_p50_ms": stats["p50_ms"],
                    "serve_p95_ms": stats["p95_ms"],
                    "setup_s": cell.phases["setup_total"]}}


def _check(cell: harness.Cell, queries, ids, got: dict) -> None:
    want, _ = harness.reference_answers(cell, queries, np.asarray(ids))
    bad = 0
    for j, rid in enumerate(ids):
        if rid not in got or "error" in got[rid][1]:
            continue
        r = got[rid][1]
        for w in ("std", "open"):
            for f in ("idx", "sim"):
                bad += int((np.asarray(r[w][f])
                            != getattr(want, f"{w}_{f}")[j]).sum())
    cell.check("response_mismatch", bad, 0)
