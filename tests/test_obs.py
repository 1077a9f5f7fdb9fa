"""Observability layer: span fast path, tracer ring buffer, both export
formats round-tripping through the trace-report loader/validator, the
deterministic histogram quantiles, and — the contract the analyzer also
machine-checks — that installing a tracer changes zero result bytes of a
real search while recording the pipeline stage spans.
"""
import json
import threading

import numpy as np
import pytest

from repro.obs import (Counter, Gauge, Histogram, Metrics, Tracer, enabled,
                       install, span, uninstall)
from repro.obs import report as report_mod
from repro.obs import trace as trace_mod


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """Every test starts and ends with tracing disabled."""
    uninstall()
    yield
    uninstall()


# ---------------------------------------------------------------------------
# span() fast path + tracer
# ---------------------------------------------------------------------------


def test_span_disabled_is_shared_noop_singleton():
    assert not enabled()
    s1, s2 = span("a", x=1), span("b")
    assert s1 is s2 is trace_mod.NOOP_SPAN     # no allocation when disabled
    with s1 as s:
        s.add(ignored=True)                    # all no-ops
    assert trace_mod.current() is None


def test_span_records_name_attrs_and_midspan_add():
    t = install(Tracer())
    assert enabled() and trace_mod.current() is t
    with span("stage", rows=7) as s:
        s.add(bytes=28)
    (ev,) = t.events()
    assert ev.name == "stage"
    assert ev.attrs == {"rows": 7, "bytes": 28}
    assert ev.t_end_ns >= ev.t_start_ns
    assert ev.dur_ns == ev.t_end_ns - ev.t_start_ns
    assert ev.tid == threading.get_ident()
    uninstall()
    with span("after"):
        pass
    assert t.n_recorded == 1                   # uninstall really detaches


def test_span_ids_link_children_to_their_parent_on_the_same_thread():
    t = install(Tracer())
    with span("root") as root:
        with span("a") as a:
            with span("leaf") as leaf:
                pass
        with span("b") as b:
            other = []
            th = threading.Thread(target=lambda: other.append(
                span("elsewhere").__enter__()))
            th.start()
            th.join(timeout=10)
            assert not th.is_alive()
            other[0].__exit__(None, None, None)
    with span("next") as nxt:
        pass
    assert root.parent_id == 0 and root.trace_id == root.span_id
    assert (a.parent_id, b.parent_id) == (root.span_id, root.span_id)
    assert leaf.parent_id == a.span_id
    assert {a.trace_id, b.trace_id, leaf.trace_id} == {root.span_id}
    # another thread's span has no parent here; a later root starts a trace
    assert other[0].parent_id == 0 and other[0].trace_id == other[0].span_id
    assert nxt.parent_id == 0 and nxt.trace_id == nxt.span_id
    ids = [root.span_id, a.span_id, leaf.span_id, b.span_id,
           other[0].span_id, nxt.span_id]
    assert len(set(ids)) == len(ids) and 0 not in ids
    got = {ev.name: (ev.span_id, ev.parent_id, ev.trace_id)
           for ev in t.events()}
    assert got["leaf"] == (leaf.span_id, a.span_id, root.span_id)
    assert got["root"] == (root.span_id, 0, root.span_id)


def test_tracer_ring_buffer_bounds_memory():
    t = Tracer(capacity=4)
    for i in range(10):
        t.record(f"e{i}", 0, 1)
    assert t.n_recorded == 10
    assert t.n_dropped == 6
    assert [ev.name for ev in t.events()] == ["e6", "e7", "e8", "e9"]
    t.clear()
    assert t.events() == [] and t.n_recorded == 0 and t.n_dropped == 0


def test_tracer_capacity_validation():
    with pytest.raises(ValueError, match="capacity"):
        Tracer(capacity=0)


# ---------------------------------------------------------------------------
# Export formats round-trip through the report loader (the CI validator)
# ---------------------------------------------------------------------------


def _sample_tracer() -> Tracer:
    t = Tracer()
    t.record("encode", 1_000_000, 3_000_000, {"rows": 5})
    t.record("scan", 3_000_000, 9_000_000, {"rows": 11, "bytes": 44})
    t.record("scan", 9_000_000, 10_000_000, {"rows": 1, "bytes": 4})
    return t


def _nested_tracer() -> Tracer:
    """search(1) > plan(2), scan(3) > kernel(4); times in ns."""
    t = Tracer()
    t.record("search", 0, 10_000_000, {}, 1, 0, 1)
    t.record("plan", 1_000_000, 3_000_000, {}, 2, 1, 1)
    t.record("kernel", 4_000_000, 8_000_000, {"rows": 4}, 4, 3, 1)
    t.record("scan", 3_000_000, 9_000_000, {}, 3, 1, 1)
    return t


def _export(t: Tracer, tmp_path, fmt: str) -> str:
    path = str(tmp_path / ("t.jsonl" if fmt == "jsonl" else "t.json"))
    t.to_jsonl(path) if fmt == "jsonl" else t.to_chrome(path)
    return path


@pytest.mark.parametrize("fmt", ["jsonl", "chrome"])
def test_export_round_trips_through_loader(tmp_path, fmt):
    t = _sample_tracer()
    path = str(tmp_path / ("t.jsonl" if fmt == "jsonl" else "t.json"))
    n = t.to_jsonl(path) if fmt == "jsonl" else t.to_chrome(path)
    assert n == 3
    events = report_mod.load_trace(path)
    assert [ev.name for ev in events] == ["encode", "scan", "scan"]
    assert events[0].dur_ns == 2_000_000
    assert events[1].attrs["rows"] == 11
    nested = report_mod.load_trace(_export(_nested_tracer(), tmp_path, fmt))
    assert nested == _nested_tracer().events()   # ids out of attrs, intact


@pytest.mark.parametrize("fmt", ["memory", "jsonl", "chrome"])
def test_rollup_self_time_subtracts_direct_children(tmp_path, fmt):
    t = _nested_tracer()
    events = (t.events() if fmt == "memory"
              else report_mod.load_trace(_export(t, tmp_path, fmt)))
    roll = report_mod.rollup(events)
    assert {n: a["self_us"] for n, a in roll.items()} == pytest.approx(
        {"search": 2000.0, "plan": 2000.0, "scan": 2000.0, "kernel": 4000.0})
    assert roll["search"]["total_us"] == pytest.approx(10000.0)
    assert roll["kernel"]["rows"] == 4
    header = report_mod.format_table(roll).splitlines()[0].split()
    assert header[:5] == ["span", "count", "total", "self", "share"]


@pytest.mark.parametrize("fmt", ["jsonl", "chrome"])
def test_loader_reads_files_written_without_ids(tmp_path, fmt):
    ev = {"name": "scan", "ts_us": 1.0, "dur_us": 5.0, "tid": 3, "rows": 2}
    path = str(tmp_path / ("old.jsonl" if fmt == "jsonl" else "old.json"))
    with open(path, "w") as f:
        if fmt == "jsonl":
            f.write(json.dumps(ev) + "\n")
        else:
            json.dump({"traceEvents": [
                {"name": "scan", "ph": "X", "ts": 1.0, "dur": 5.0, "pid": 1,
                 "tid": 3, "args": {"rows": 2}}]}, f)
    (got,) = report_mod.load_trace(path)
    assert (got.span_id, got.parent_id, got.trace_id) == (0, 0, 0)
    assert got.attrs == {"rows": 2}
    roll = report_mod.rollup([got, got])
    assert roll["scan"]["self_us"] == roll["scan"]["total_us"] == 10.0


def test_chrome_export_is_valid_trace_event_json(tmp_path):
    path = str(tmp_path / "t.json")
    _sample_tracer().to_chrome(path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["displayTimeUnit"] == "ms"
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X"                 # complete events only
        assert set(ev) >= {"name", "pid", "tid", "ts", "dur", "args"}


@pytest.mark.parametrize("line,msg", [
    ('{"ts_us": 1, "dur_us": 2, "tid": 3}', "missing 'name'"),
    ('{"name": "x", "ts_us": 1, "tid": 3}', "missing 'dur_us'"),
    ('{"name": "x", "ts_us": 1, "dur_us": -2, "tid": 3}', "non-negative"),
    ('{"name": "", "ts_us": 1, "dur_us": 2, "tid": 3}', "non-empty"),
    ('{"name": "x", "ts_us": 1, "dur_us": 2, "tid": 3, "span_id": -1}',
     "span_id must be a non-negative integer"),
    ('{"name": "x", "ts_us": 1, "dur_us": 2, "tid": 3, "parent_id": 1.5}',
     "parent_id must be a non-negative integer"),
    ("not json", "invalid JSON"),
])
def test_loader_rejects_malformed_jsonl(tmp_path, line, msg):
    path = str(tmp_path / "bad.jsonl")
    with open(path, "w") as f:
        f.write(line + "\n")
    with pytest.raises(report_mod.TraceFormatError, match=msg):
        report_mod.load_trace(path)


def test_loader_rejects_malformed_chrome(tmp_path):
    path = str(tmp_path / "bad.json")
    with open(path, "w") as f:
        json.dump({"traceEvents": [{"name": "x", "ph": "B", "ts": 0,
                                    "dur": 1, "pid": 1, "tid": 1}]}, f)
    with pytest.raises(report_mod.TraceFormatError, match="ph='X'"):
        report_mod.load_trace(path)


def test_loader_rejects_empty_file(tmp_path):
    path = str(tmp_path / "empty.jsonl")
    open(path, "w").close()
    with pytest.raises(report_mod.TraceFormatError, match="empty"):
        report_mod.load_trace(path)


def test_rollup_counts_totals_and_summed_attrs(tmp_path):
    events = _sample_tracer().events()
    roll = report_mod.rollup(events)
    assert set(roll) == {"encode", "scan"}
    assert roll["scan"]["count"] == 2
    assert roll["scan"]["total_us"] == pytest.approx(7000.0)
    assert roll["scan"]["rows"] == 12 and roll["scan"]["bytes"] == 48
    assert roll["encode"]["rows"] == 5 and roll["encode"]["bytes"] == 0
    # exact nearest-rank percentiles of the durations (6000, 1000 us)
    assert roll["scan"]["p50_us"] == 1000.0
    assert roll["scan"]["p95_us"] == roll["scan"]["p99_us"] == 6000.0
    assert roll["encode"]["p50_us"] == roll["encode"]["p99_us"] == 2000.0
    table = report_mod.format_table(roll)
    assert table.splitlines()[2].startswith("scan")    # widest stage first
    assert "encode" in table


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------


def test_counter_and_gauge():
    c = Counter()
    c.inc()
    c.inc(4)
    assert c.value == 5 and c.snapshot() == 5
    g = Gauge()
    g.inc(3)
    g.dec()
    assert g.value == 2.0 and g.max == 3.0     # high-water mark survives dec
    g.set(0.5)
    assert g.snapshot() == {"value": 0.5, "max": 3.0}


def test_histogram_deterministic_quantiles():
    h = Histogram(bounds=(1.0, 2.0, 4.0))
    assert h.p50 == 0.0                        # empty -> 0.0 by definition
    for v in (0.5, 1.5, 3.0, 9.0):
        h.observe(v)
    assert h.count == 4 and h.sum == pytest.approx(14.0)
    # rank math over bucket counts: p50 covers rank 2 -> bound 2.0;
    # p99 lands in the overflow bucket, reported as the last finite bound
    assert h.p50 == 2.0
    assert h.p99 == 4.0
    assert h.quantile(0.0) == 1.0              # rank clamps to 1
    snap = h.snapshot()
    assert snap["buckets"] == {"1.0": 1, "2.0": 1, "4.0": 1, "inf": 1}
    assert snap["p50"] == 2.0


def test_histogram_identical_workloads_identical_percentiles():
    a, b = Histogram(), Histogram()
    vals = [10 ** (i % 5 - 4) for i in range(100)]
    for v in vals:
        a.observe(v)
    for v in reversed(vals):                   # arrival order must not matter
        b.observe(v)
    sa, sb = a.snapshot(), b.snapshot()
    assert sa["sum"] == pytest.approx(sb["sum"])  # float-add order wiggles
    for k in ("count", "buckets", "p50", "p95", "p99"):
        assert sa[k] == sb[k]


def test_histogram_bounds_validation():
    for bad in ((), (2.0, 1.0), (1.0, 1.0), (1.0, float("inf"))):
        with pytest.raises(ValueError):
            Histogram(bounds=bad)
    with pytest.raises(ValueError, match="q must be"):
        Histogram().quantile(1.5)


def test_metrics_registry_get_or_create_and_kind_mismatch():
    m = Metrics()
    assert m.counter("a") is m.counter("a")
    assert m.histogram("h") is m.histogram("h")
    with pytest.raises(TypeError, match="already registered"):
        m.gauge("a")
    m.counter("a").inc()
    m.gauge("g").set(2.0)
    snap = m.snapshot()
    assert snap["a"] == 1 and snap["g"]["value"] == 2.0
    assert snap["h"]["count"] == 0


# ---------------------------------------------------------------------------
# Trace transparency on the real pipeline (the analyzer's contract, in vivo)
# ---------------------------------------------------------------------------


def test_traced_search_byte_identical_and_spans_recorded():
    from repro.core import OMSConfig, OMSPipeline
    from repro.data.spectra import LibraryConfig, make_dataset

    cfg = OMSConfig(dim=256, n_levels=8, max_r=32, q_block=8)
    ds = make_dataset(LibraryConfig(n_refs=200, n_queries=16, seed=7))
    pipe = OMSPipeline(cfg, ds.refs)
    hvs, qp, qc = pipe.encode_queries(ds.queries)

    plain = pipe.search_encoded(hvs, qp, qc)
    t = install(Tracer())
    try:
        traced = pipe.search_encoded(hvs, qp, qc)
    finally:
        uninstall()

    for f in plain.result._fields:
        a = np.asarray(getattr(plain.result, f))
        b = np.asarray(getattr(traced.result, f))
        assert a.tobytes() == b.tobytes(), f
    names = {ev.name for ev in t.events()}
    assert {"pipeline.plan", "pipeline.scan", "pipeline.fdr"} <= names


SEARCH_SPANS = {   # name -> parent's name, as search_encoded nests them
    "pipeline.search": None,
    "pipeline.precursors_to_host": "pipeline.search",
    "pipeline.plan": "pipeline.search", "pipeline.scan": "pipeline.search",
    "pipeline.fdr": "pipeline.search", "search.sort_pad": "pipeline.scan",
    "search.gather": "pipeline.scan", "search.kernel": "pipeline.scan",
    "search.restore": "pipeline.scan"}


def test_profiled_search_writes_every_span_with_its_ids(tmp_path):
    """Under a profiler session each span also lands in the profiler's host
    trace, with the ids the ring buffer holds, nested as the code runs."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.core import OMSConfig, OMSPipeline
    from repro.data.spectra import LibraryConfig, make_dataset

    cfg = OMSConfig(dim=256, n_levels=8, max_r=32, q_block=8)
    ds = make_dataset(LibraryConfig(n_refs=200, n_queries=16, seed=7))
    pipe = OMSPipeline(cfg, ds.refs)
    hvs, qp, qc = pipe.encode_queries(ds.queries)
    pipe.search_encoded(hvs, qp, qc)            # compile outside the trace
    t = install(Tracer())
    try:
        with jax.profiler.trace(str(tmp_path)):
            jax.block_until_ready(pipe.search_encoded(hvs, qp, qc))
    finally:
        uninstall()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    profiled = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                st = dict(e.stats)
                if "span_id" in st:
                    profiled[st["span_id"]] = (
                        e.name, st["parent_id"], st["trace_id"],
                        e.start_ns, e.start_ns + e.duration_ns)
    ring = {ev.span_id: (ev.name, ev.parent_id, ev.trace_id)
            for ev in t.events()}
    assert {v[:3] for v in profiled.values()} == set(ring.values())
    assert sorted(v[0] for v in profiled.values()) == sorted(SEARCH_SPANS)
    for sid, (name, parent, trace, t0, t1) in profiled.items():
        if SEARCH_SPANS[name] is None:
            assert parent == 0 and trace == sid
            continue
        p_name, _, p_trace, p0, p1 = profiled[parent]
        assert p_name == SEARCH_SPANS[name] and trace == p_trace
        assert p0 <= t0 and t1 <= p1


@pytest.mark.parametrize("forced", [False, True], ids=["cpu", "forced"])
def test_search_kernel_span_and_counter_name_the_lowering(request, forced):
    """Each search records how its Hamming tiles were computed: ``xla`` on
    the CPU, ``pallas`` where the vpu scan step takes its in-place kernel
    (forced here through the test's hook), with the same answers."""
    from repro.core import OMSConfig, OMSPipeline, search
    from repro.data.spectra import LibraryConfig, make_dataset

    cfg = OMSConfig(dim=256, n_levels=8, max_r=1024, q_block=16)
    ds = make_dataset(LibraryConfig(n_refs=300, n_queries=16, seed=7))
    pipe = OMSPipeline(cfg, ds.refs)
    hvs, qp, qc = pipe.encode_queries(ds.queries)
    plain = pipe.search_encoded(hvs, qp, qc).result
    if forced:
        request.getfixturevalue("force_scan_kernel")
    want = "pallas" if forced else "xla"
    before = search.METRICS.snapshot()
    t = install(Tracer())
    try:
        got = pipe.search_encoded(hvs, qp, qc).result
    finally:
        uninstall()
    after = search.METRICS.snapshot()
    (ev,) = [e for e in t.events() if e.name == "search.kernel"]
    assert ev.attrs["lowering"] == want
    for name in ("lowering_pallas", "lowering_xla"):
        grew = after.get(name, 0) - before.get(name, 0)
        assert grew == (1 if name == f"lowering_{want}" else 0), name
    for f in plain._fields:
        assert (np.asarray(getattr(plain, f))
                == np.asarray(getattr(got, f))).all(), f


def test_streamed_slab_gather_runs_on_the_prefetch_thread_under_scan():
    """The streamed search's slab reads run on the engine's prefetch thread
    as ``serve.slab.gather`` spans, children of the call's ``serve.scan``
    span; the loop's waits for them are ``serve.slab.wait`` spans, and each
    slab step's ``serve.slab.search`` names its lowering. Tracing changes
    no result byte."""
    import tempfile

    from repro.core import OMSConfig, OMSPipeline
    from repro.data.spectra import LibraryConfig, make_dataset

    cfg = OMSConfig(dim=256, n_levels=8, max_r=32, q_block=8)
    ds = make_dataset(LibraryConfig(n_refs=200, n_queries=16, seed=7))
    with tempfile.TemporaryDirectory() as d:
        OMSPipeline.ingest(cfg, ds.refs, d + "/store", chunk_rows=64)
        pipe = OMSPipeline.from_store(d + "/store", cfg, resident=False,
                                      slab_rows=64)
        hvs, qp, qc = pipe.encode_queries(ds.queries)
        plain = pipe.search_encoded(hvs, qp, qc)
        t = install(Tracer())
        try:
            traced = pipe.search_encoded(hvs, qp, qc)
        finally:
            uninstall()
    for f in plain.result._fields:
        a = np.asarray(getattr(plain.result, f))
        b = np.asarray(getattr(traced.result, f))
        assert a.tobytes() == b.tobytes(), f

    evs = t.events()
    (scan,) = [e for e in evs if e.name == "serve.scan"]
    n = scan.attrs["slabs"]
    assert n > 1
    assert scan.attrs["pairs"] == pipe.engine.last_stats.scanned_pairs
    gathers = [e for e in evs if e.name == "serve.slab.gather"]
    assert len(gathers) == n
    for g in gathers:
        assert g.tid != scan.tid                   # the prefetch thread
        assert g.parent_id == scan.span_id and g.trace_id == scan.trace_id
        assert scan.t_start_ns <= g.t_start_ns and g.t_end_ns <= scan.t_end_ns
    for name in ("serve.slab.wait", "serve.slab.search"):
        evs_n = [e for e in evs if e.name == name]
        assert len(evs_n) == n
        assert all(e.tid == scan.tid and e.parent_id == scan.span_id
                   for e in evs_n), name
    assert {e.attrs["lowering"] for e in evs
            if e.name == "serve.slab.search"} == {"xla"}


@pytest.mark.parametrize("forced", [False, True], ids=["cpu", "forced"])
def test_scan_epilogue_is_named_on_every_search_and_slab_step(request,
                                                              forced):
    """Each resident search (``search.kernel``) and each slab step of a
    streamed one (``serve.slab.search``) records where its dual-window
    winners were chosen: ``kernel`` where the vpu scan step keeps them in
    VMEM (forced here through the test's hook), ``xla`` on the CPU, where
    ``_find_topk_dual`` reduces the tile; ``epilogue_kernel`` /
    ``epilogue_xla`` count one per search and per slab step. The answers
    do not change."""
    import tempfile

    from repro.core import OMSConfig, OMSPipeline, search
    from repro.data.spectra import LibraryConfig, make_dataset

    cfg = OMSConfig(dim=256, n_levels=8, max_r=1024, q_block=16)
    ds = make_dataset(LibraryConfig(n_refs=300, n_queries=16, seed=7))
    with tempfile.TemporaryDirectory() as d:
        OMSPipeline.ingest(cfg, ds.refs, d + "/store")
        pipes = (OMSPipeline.from_store(d + "/store", cfg),
                 OMSPipeline.from_store(d + "/store", cfg, resident=False,
                                        slab_rows=1024))
        hvs, qp, qc = pipes[0].encode_queries(ds.queries)
        plain = [p.search_encoded(hvs, qp, qc).result for p in pipes]
        if forced:
            request.getfixturevalue("force_scan_kernel")
        want = "kernel" if forced else "xla"
        for pipe, base, name in zip(pipes, plain, ("search.kernel",
                                                   "serve.slab.search")):
            before = search.METRICS.snapshot()
            t = install(Tracer())
            try:
                got = pipe.search_encoded(hvs, qp, qc).result
            finally:
                uninstall()
            after = search.METRICS.snapshot()
            evs = [e for e in t.events() if e.name == name]
            assert evs and {e.attrs["epilogue"] for e in evs} == {want}
            if name == "serve.slab.search":
                assert len(evs) == pipe.engine.last_stats.n_scanned > 1
            for c in ("epilogue_kernel", "epilogue_xla"):
                grew = after.get(c, 0) - before.get(c, 0)
                assert grew == (len(evs) if c == f"epilogue_{want}" else 0), c
            for f in base._fields:
                assert (np.asarray(getattr(base, f))
                        == np.asarray(getattr(got, f))).all(), (name, f)
