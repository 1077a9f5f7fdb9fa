"""Units of the benchmark's yardstick, on the CPU: the trace reduction on a
trace recorded on a TPU v5e, the roofline's pairs and bytes, the generator
copy, and the entry point's refusal to run without a chip."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench_tiny import ROOT
from bench import roofline, trace_reduce

SAMPLE = os.path.join(os.path.dirname(__file__), "data", "sample.xplane.pb")


# -- trace reduction ---------------------------------------------------------

def _raw_events():
    from jax.profiler import ProfileData

    dev, host = {}, []
    for plane in ProfileData.from_file(SAMPLE).planes:
        lines = {ln.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in ln.events] for ln in plane.lines}
        if plane.name.startswith("/device:TPU:"):
            dev[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for evs in lines.values():
                host += [e for e in evs if e[0].startswith("bench.")]
    return dev, host


def test_trace_busy_and_idle_match_a_brute_force_grid():
    dev, host = _raw_events()
    lo = min(s for n, s, e in host if n == "bench.window")
    hi = max(e for n, s, e in host if n == "bench.window")
    r = trace_reduce.reduce(trace_reduce.load(SAMPLE))
    assert r.window_s == pytest.approx((hi - lo) / 1e9)
    step = 100  # ns
    grid_busy = []
    for lines in dev.values():
        ops = lines.get("XLA Ops", [])
        grid = np.zeros(int((hi - lo) // step) + 1, bool)
        for _, s, e in ops:
            a, b = max(s, lo), min(e, hi)
            if b > a:
                grid[int((a - lo) // step):int(np.ceil((b - lo) / step))] = True
        if grid.any():
            grid_busy.append(grid.sum() * step / 1e9)
    assert r.n_devices == len(grid_busy) >= 1
    assert r.busy_s == pytest.approx(np.mean(grid_busy), abs=2e-6 * 50)
    assert 0 < r.busy_s < r.window_s
    idle = sum(r.idle_gaps.values())
    assert idle == pytest.approx(r.window_s - r.busy_s, rel=1e-9, abs=1e-9)
    # the host slept 2 x 10 ms under bench.sleep with the device idle
    assert r.idle_gaps.get("bench.sleep", 0) > 0.015


def test_trace_program_time_is_the_sum_of_module_events():
    dev, host = _raw_events()
    lo = min(s for n, s, e in host if n == "bench.window")
    hi = max(e for n, s, e in host if n == "bench.window")
    want: dict[str, float] = {}
    n_dev = 0
    for lines in dev.values():
        if not any(min(e, hi) > max(s, lo) for _, s, e in
                   lines.get("XLA Ops", [])):
            continue
        n_dev += 1
        for name, s, e in lines.get("XLA Modules", []):
            if min(e, hi) > max(s, lo):
                key = trace_reduce.program_name(name)
                want[key] = want.get(key, 0) + (min(e, hi) - max(s, lo)) / 1e9
    r = trace_reduce.reduce(trace_reduce.load(SAMPLE))
    assert set(r.programs) == set(want)
    for k in want:
        assert r.programs[k] == pytest.approx(want[k] / n_dev)
    b = trace_reduce.breakdown(r)
    assert 1 <= len(b["device_ops"]) <= 10 and 1 <= len(b["idle_gaps"]) <= 10
    assert trace_reduce.program_seconds(r, [r"no_such_program"]) is None


def test_union_and_gaps():
    u = trace_reduce.union([(5, 9), (0, 3), (2, 4), (8, 12), (20, 30)], 1, 25)
    assert u == [(1, 4), (5, 12), (20, 25)]
    assert trace_reduce.gaps(u, 0, 26) == [(0, 1), (4, 5), (12, 20), (25, 26)]


# -- roofline ----------------------------------------------------------------

def test_window_work_matches_brute_force():
    rng = np.random.default_rng(3)
    r_pmz = rng.uniform(400, 800, 700).astype(np.float32)
    r_pmz[:50] = r_pmz[50:100]              # exact ties, as decoys have
    r_c = rng.integers(2, 4, 700)
    q_pmz = rng.uniform(380, 820, 90).astype(np.float32)
    q_c = rng.integers(2, 5, 90)            # charge 4 has no rows
    tol = 25.0
    inwin = ((np.abs(r_pmz[None, :].astype(np.float64)
                     - q_pmz[:, None].astype(np.float64)) <= tol)
             & (r_c[None, :] == q_c[:, None]))
    w = roofline.window_work(r_pmz, r_c, q_pmz, q_c, tol)
    assert w.pairs == int(inwin.sum())
    assert w.rows == int(inwin.any(axis=0).sum())
    assert w.n_queries == 90
    assert roofline.scan_ops(w, 4096) == w.pairs * 8192
    assert roofline.scan_bytes(w, 4096, 1) == (w.rows + 90) * 512 + 90 * 24
    peaks = roofline.peaks_for("TPU v5 lite")
    t, bound = roofline.least_time(w, 4096, 1, peaks)
    t_c = w.pairs * 8192 / 393e12
    t_m = ((w.rows + 90) * 512 + 90 * 24) / 819e9
    assert (t, bound) == ((t_c, "compute") if t_c >= t_m else (t_m, "memory"))
    big = roofline.Work(pairs=10**9, rows=10**6, n_queries=10**4)
    assert roofline.least_time(big, 4096, 1, peaks)[1] == "compute"


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu")


# -- generator copy ----------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 2**31 - 5])
def test_generator_copy_equals_the_program_generator(seed):
    import jax

    from repro.data import spectra

    from bench import data

    cfg = spectra.LibraryConfig(n_refs=2500, n_queries=300, seed=seed)
    want = jax.jit(lambda: spectra.make_dataset(cfg))()
    got = data.make_dataset(data.LibraryParams(n_refs=2500, n_queries=300),
                            seed)
    for a, b in zip(jax.tree.leaves((want.refs, want.queries,
                                     want.query_source,
                                     want.query_modified)),
                    jax.tree.leaves((got.refs, got.queries, got.query_source,
                                     got.query_modified))):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_layout_seed_gives_every_seed_the_same_precursors():
    """With a layout seed, two run seeds search the same multiset of
    precursors, dealt out in another order, with other spectra."""
    from bench import data

    p = data.LibraryParams(n_refs=2500, n_queries=300)
    a = data.make_dataset(p, 7, layout_seed=11)
    b = data.make_dataset(p, 2**32 - 3, layout_seed=11)

    def precursors(s):
        return sorted(zip(np.asarray(s.charge).tolist(),
                          np.asarray(s.pmz).tolist()))

    assert precursors(a.refs) == precursors(b.refs)
    assert precursors(a.queries) == precursors(b.queries)
    assert not np.array_equal(np.asarray(a.refs.pmz), np.asarray(b.refs.pmz))
    assert not np.array_equal(np.asarray(a.queries.pmz),
                              np.asarray(b.queries.pmz))
    assert not np.array_equal(np.asarray(a.refs.mz), np.asarray(b.refs.mz))
    for d in (a, b):
        src = np.asarray(d.query_source)
        unmod = ~np.asarray(d.query_modified)
        assert np.array_equal(np.asarray(d.queries.charge),
                              np.asarray(d.refs.charge)[src])
        assert np.array_equal(np.asarray(d.queries.pmz)[unmod],
                              np.asarray(d.refs.pmz)[src][unmod])


# -- the entry point without a chip ------------------------------------------

def _run(cwd, extra_env=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(extra_env or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "iprg2012.batch",
         "--seed", str(2**32 + 9), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_run_without_tpu_exits_nonzero_with_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "TPU" in p.stderr


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    bm = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in bm["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert _no_result(p.stdout)


def test_benchmark_names_a_file_for_every_piece():
    bm = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for c in bm["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in bm["workloads"]:
        t = json.load(open(os.path.join(ROOT, "bench", "traffic",
                                        w["traffic"] + ".json")))
        assert os.path.isfile(os.path.join(ROOT, "bench", "drivers",
                                           t["driver"] + ".py"))
    for m in bm["per_layer"]:
        stem = m["name"].split(".")[0]
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           stem + ".py"))
