"""What every cell shares: finding its pieces by name, seeds, set-up of the
library, the measured window, the compile counter, and the result line.

A cell (``workloads`` in BENCHMARK.json) names a configuration file
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the mix names its driver
(``bench/drivers/<driver>.py``), and each per-layer metric ``<stem>`` or
``<stem>.<split>`` is read by ``bench/metrics/<stem>.py``. Adding any of
them is adding a file.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPILE_CACHE = os.path.join(ROOT, ".jax_cache")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# The precursor layout every seed deals out in its own order (bench/data.py),
# so that the work of a search does not change with the seed.
LAYOUT_SEED = 20240913


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def sub_seeds(seed: int, n: int) -> list[int]:
    """``n`` 31-bit seeds derived from any non-negative whole number."""
    ss = np.random.SeedSequence(int(seed))
    return [int(x) & 0x7FFFFFFF for x in ss.generate_state(n, np.uint32)]


@dataclasses.dataclass
class Cell:
    """One run of one cell: its definition, and what the run gathers."""

    name: str
    cfg: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    metric_defs: list                   # BENCHMARK.json metrics of the cell
    # search settings on top of the configuration's: the traffic file's
    # ``program``, and the control's
    program_overrides: dict = dataclasses.field(default_factory=dict)
    faults: tuple = ()                  # test-only breakage, by name
    t_start: float = dataclasses.field(default_factory=time.perf_counter)
    phases: dict = dataclasses.field(default_factory=dict)
    checks: list = dataclasses.field(default_factory=list)
    info: dict = dataclasses.field(default_factory=dict)
    layer: dict = dataclasses.field(default_factory=dict)
    reduction: Any = None

    def __post_init__(self):
        (self.data_seed, self.codebook_seed, self.sample_seed,
         self.warm_seed, self.order_seed) = sub_seeds(self.seed, 5)

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        yield
        self.phases[name] = time.perf_counter() - t0
        log(f"phase {name}: {self.phases[name]:.3f} s")

    def check(self, name: str, value: float, limit: float) -> None:
        """One compared number: the run is correct only if value <= limit."""
        self.checks.append((name, value, limit))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(v <= lim for _, v, lim in self.checks)


def make_cell(name: str, config_file: str, traffic: str, *, seed: int,
              seconds: float, trace: bool, metric_defs: list,
              root: str = ROOT) -> Cell:
    """A cell of the configuration file ``config_file`` (relative to the
    root) under ``bench/traffic/<traffic>.json``."""
    cfg = load_json(os.path.join(root, config_file))
    tr = load_json(os.path.join(root, "bench", "traffic", traffic + ".json"))
    return Cell(name=name, cfg=cfg, traffic=tr, seed=seed, seconds=seconds,
                trace=trace, metric_defs=metric_defs,
                program_overrides=dict(tr.get("program", {})))


def cell_from_benchmark(bm: dict, workload: str, *, seed: int,
                        seconds: float, trace: bool, root: str = ROOT
                        ) -> tuple[Cell, dict]:
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bm["configs"]}[w["config"]]
    kind = "per_layer" if trace else "end_to_end"
    defs = [m for m in bm[kind]
            if "workloads" not in m or workload in m["workloads"]]
    return make_cell(workload, conf["file"], w["traffic"], seed=seed,
                     seconds=seconds, trace=trace, metric_defs=defs,
                     root=root), w


# ---------------------------------------------------------------------------
# Compile counter
# ---------------------------------------------------------------------------

_compiles: list[float] = []
_listening = False


def listen_for_compiles() -> None:
    """Record the end time of every backend compile (or persistent-cache
    load) from here on."""
    global _listening
    if _listening:
        return
    import jax

    def on_event(event, duration, **kw):
        if event == COMPILE_EVENT:
            _compiles.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_event)
    _listening = True


def compiles_between(t0: float, t1: float) -> int:
    return sum(1 for t in list(_compiles) if t0 <= t <= t1)


# ---------------------------------------------------------------------------
# Program configuration and set-up
# ---------------------------------------------------------------------------


def program_config(cfg: dict, codebook_seed: int):
    """The program's OMSConfig for a configuration file."""
    from repro.core import OMSConfig

    enc, s = cfg["encoding"], cfg["search"]
    return OMSConfig(
        dim=enc["dim"], n_levels=enc["n_levels"], bin_size=enc["bin_size"],
        mz_min=enc["mz_min"], mz_max=enc["mz_max"],
        add_decoys=cfg["library"]["add_decoys"],
        encode_backend=enc["encode_backend"],
        encode_batch=enc["encode_batch"], seed=codebook_seed,
        max_r=s["max_r"], q_block=s["q_block"], ppm_tol=s["ppm_tol"],
        open_tol_da=s["open_tol_da"], fdr_threshold=s["fdr_threshold"],
        backend=s["backend"], top_k=s["top_k"])


def serving_overrides(cfg: dict) -> dict:
    """Search-side OMSConfig fields for ``OMSPipeline.from_store``."""
    enc, s = cfg["encoding"], cfg["search"]
    return dict(max_r=s["max_r"], q_block=s["q_block"], ppm_tol=s["ppm_tol"],
                open_tol_da=s["open_tol_da"],
                fdr_threshold=s["fdr_threshold"], backend=s["backend"],
                top_k=s["top_k"], encode_backend=enc["encode_backend"],
                encode_batch=enc["encode_batch"])


def make_data(cell: Cell, n_queries: int):
    import jax

    from bench import data

    p = data.library_params(cell.cfg, n_queries)
    with cell.phase("dataset"):
        ds = data.make_dataset(p, cell.data_seed, LAYOUT_SEED)
        jax.block_until_ready(ds)
    return ds


def ingest(cell: Cell, refs) -> str:
    """Ingest ``refs`` into a new store under the temp directory; returns
    its path (the caller removes it)."""
    from repro.core import OMSPipeline

    path = tempfile.mkdtemp(prefix="oms_bench_store_")
    cfg = program_config(cell.cfg, cell.codebook_seed)
    with cell.phase("ingest"):
        store = OMSPipeline.ingest(
            cfg, refs, path, chunk_rows=cell.cfg["ingest"]["chunk_rows"])
    cell.info["store_rows"] = store.n_rows
    cell.info["store_gib"] = store.nbytes() / 2**30
    return path


def cold_start(cell: Cell, path: str):
    import jax

    from repro.core import OMSPipeline

    over = {**serving_overrides(cell.cfg), **cell.program_overrides}
    with cell.phase("cold_start"):
        pipe = OMSPipeline.from_store(path, **over)
        jax.block_until_ready(pipe.db.hvs)
    cell.info["db_rows"] = int(pipe.db.n_rows)
    cell.info["db_blocks"] = int(pipe.db.n_blocks)
    return pipe


def memory_peak() -> int:
    import jax

    peaks = []
    for d in jax.local_devices():
        try:
            peaks.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except Exception:
            pass
    return max(peaks) if peaks else 0


def reference_answers(cell: Cell, queries, idx):
    """The plain reference's answers to the queries at ``idx``, from data
    regenerated from the seed (the program's state freed first)."""
    import jax.numpy as jnp

    from bench import data, reference

    gc.collect()
    cfg = cell.cfg
    enc, s = cfg["encoding"], cfg["search"]
    refs = data.make_dataset(data.library_params(cfg, 1), cell.data_seed,
                             LAYOUT_SEED).refs
    cb = reference.codebooks(cell.codebook_seed, enc)
    lib = reference.build_library(refs, cb, enc,
                                  add_decoys=cfg["library"]["add_decoys"],
                                  chunk_rows=cfg["ingest"]["chunk_rows"])
    del refs
    sel = jnp.asarray(idx)
    q_hvs = reference.encode(queries.mz[sel], queries.intensity[sel], cb, enc)
    want = reference.search(
        lib, q_hvs, np.asarray(queries.pmz)[idx],
        np.asarray(queries.charge)[idx], dim=enc["dim"],
        ppm_tol=s["ppm_tol"], open_tol_da=s["open_tol_da"], top_k=s["top_k"])
    return want, lib


# ---------------------------------------------------------------------------
# The measured window
# ---------------------------------------------------------------------------


class Window:
    t0: float = 0.0
    t1: float = 0.0
    compiles: int = 0

    @property
    def elapsed(self) -> float:
        return self.t1 - self.t0


@contextlib.contextmanager
def window(cell: Cell):
    """Measure the block: host clock, compiles, and with ``--trace 1`` the
    profiler and the program's own spans."""
    import jax

    w = Window()
    trace_dir = tracer = None
    if cell.trace:
        from repro.obs import trace as obs_trace

        trace_dir = tempfile.mkdtemp(prefix="oms_bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        tracer = obs_trace.install(obs_trace.Tracer(capacity=1 << 20))
    w.t0 = time.perf_counter()
    cell.phases.setdefault("setup_total", w.t0 - cell.t_start)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield w
    finally:
        w.t1 = w.t1 or time.perf_counter()
        w.compiles = compiles_between(w.t0, w.t1)
        if tracer is not None:
            from repro.obs import trace as obs_trace

            obs_trace.uninstall()
            cell.layer["spans"] = [e for e in tracer.events()
                                   if w.t0 * 1e9 <= e.t_start_ns
                                   and e.t_end_ns <= w.t1 * 1e9]
        if trace_dir is not None:
            jax.profiler.stop_trace()
            _reduce_trace(cell, trace_dir)


def _reduce_trace(cell: Cell, trace_dir: str) -> None:
    from bench import trace_reduce

    try:
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        t0 = time.perf_counter()
        cell.reduction = trace_reduce.reduce(trace_reduce.load(files[0]))
        log(f"trace reduced in {time.perf_counter() - t0:.1f} s: window "
            f"{cell.reduction.window_s:.3f} s, busy "
            f"{cell.reduction.busy_s:.3f} s")
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# Per-layer metrics and the result line
# ---------------------------------------------------------------------------


def read_layer_metrics(cell: Cell) -> dict:
    out = {}
    for m in cell.metric_defs:
        stem = m["name"].split(".")[0]
        reader = importlib.import_module(f"bench.metrics.{stem}")
        value = reader.read(cell)
        if value is None:
            log(f"metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def device_info(cell: Cell, peak: int) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}
    if cell.trace and cell.reduction is not None:
        info["busy_s"] = cell.reduction.busy_s
        info["window_s"] = cell.reduction.window_s
    return info


def result_line(cell: Cell, *, attempted: int, failed: int, e2e: dict,
                peak: int) -> dict:
    from bench import trace_reduce

    if cell.trace:
        metrics = read_layer_metrics(cell)
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.metric_defs if m["name"] in e2e}
    line = {"correct": cell.correct, "attempted": attempted,
            "failed": failed, "metrics": metrics,
            "device": device_info(cell, peak)}
    if cell.trace and cell.reduction is not None:
        line["breakdown"] = trace_reduce.breakdown(cell.reduction)
    line["checks"] = {name: {"value": v, "limit": lim}
                      for name, v, lim in cell.checks}
    return line


def run_cell(cell: Cell) -> dict:
    """Set up, measure and check one cell; returns its result line."""
    driver = importlib.import_module(
        f"bench.drivers.{cell.traffic['driver']}")
    listen_for_compiles()
    return result_line(cell, **driver.run(cell))

