"""The split of device-idle time by program span (``bench/span_reduce.py``)
and the idle readers built on it, on the CPU: a synthetic trace whose split
is worked out by hand, the TPU sample without program spans, and a TPU
sample recorded with the program's tracer installed
(``bench/tools/record_span_trace.py``)."""
from __future__ import annotations

import os
import types

import pytest

from bench_tiny import ROOT  # noqa: F401  (puts the benchmark on the path)
from bench import span_reduce, trace_reduce
from bench.metrics import dispatch_idle_ms, plan_idle_ms, unspanned_idle_ms

DATA = os.path.join(os.path.dirname(__file__), "data")
SAMPLE = os.path.join(DATA, "sample.xplane.pb")
SAMPLE_SPANS = os.path.join(DATA, "sample_spans.xplane.pb")
READERS = {"plan_idle_ms": plan_idle_ms, "dispatch_idle_ms": dispatch_idle_ms,
           "unspanned_idle_ms": unspanned_idle_ms}
MS = 1_000_000   # ns


def _cell(split, runs):
    return types.SimpleNamespace(layer={"idle_spans": split, "runs": runs})


def _synthetic():
    """Window [0, 100] ms; the device runs [10, 20], [50, 60], [90, 95], so
    it idles 75 ms. Spans: a root, its children (one starting on the same
    ns as the root), a span wholly inside busy time, a span on another
    thread running past the window, and one outside it."""
    dev = trace_reduce.Device(
        ops=[trace_reduce.Event("op", a * MS, b * MS)
             for a, b in ((10, 20), (50, 60), (90, 95))], modules=[])
    idle_dev = trace_reduce.Device(ops=[], modules=[])
    trace = trace_reduce.Trace(
        devices={"/device:TPU:0": dev, "/device:TPU:1": idle_dev},
        host=[trace_reduce.Event("bench.window", 0, 100 * MS)])
    S = span_reduce.Span
    spans = [S("pipeline.search", 5 * MS, 80 * MS, 1, 0, 1),
             S("pipeline.precursors_to_host", 5 * MS, 8 * MS, 2, 1, 1),
             S("search.gather", 12 * MS, 14 * MS, 3, 1, 1),
             S("pipeline.plan", 15 * MS, 30 * MS, 4, 1, 1),
             S("search.kernel", 30 * MS, 55 * MS, 5, 1, 1),
             S("search.restore", 55 * MS, 70 * MS, 6, 1, 1),
             S("serve.scan", 85 * MS, 120 * MS, 7, 0, 7),
             S("pipeline.fdr", 200 * MS, 210 * MS, 8, 0, 8)]
    return trace, spans


def test_split_is_exact_on_a_synthetic_trace():
    trace, spans = _synthetic()
    split = span_reduce.idle_by_span(trace, spans)
    want_ms = {"none": 10, "pipeline.search": 12,
               "pipeline.precursors_to_host": 3, "search.gather": 0,
               "pipeline.plan": 10, "search.kernel": 20,
               "search.restore": 10, "serve.scan": 10}
    assert split == pytest.approx({k: v / 1e3 for k, v in want_ms.items()})
    r = trace_reduce.reduce(trace)
    assert sum(split.values()) == pytest.approx(r.window_s - r.busy_s)
    got = {n: m.read(_cell(split, 2)) for n, m in READERS.items()}
    assert got == pytest.approx({"plan_idle_ms": 13 / 2,
                                 "dispatch_idle_ms": 52 / 2,
                                 "unspanned_idle_ms": 10 / 2})


def test_split_of_a_trace_without_program_spans_is_all_none():
    trace = trace_reduce.load(SAMPLE)
    split = span_reduce.idle_by_span(trace, span_reduce.load_spans(SAMPLE))
    r = trace_reduce.reduce(trace)
    assert list(split) == ["none"]
    assert split["none"] == pytest.approx(r.window_s - r.busy_s, abs=1e-12)
    assert split["none"] == pytest.approx(sum(r.idle_gaps.values()),
                                          abs=1e-12)


@pytest.mark.parametrize("name", sorted(READERS))
def test_idle_readers_return_none_without_program_spans(name):
    trace = trace_reduce.load(SAMPLE)
    split = span_reduce.idle_by_span(trace, span_reduce.load_spans(SAMPLE))
    reader = READERS[name]
    assert reader.read(_cell(split, 2)) is None
    assert reader.read(_cell(None, 2)) is None
    assert reader.read(types.SimpleNamespace(layer={})) is None


# -- the TPU sample recorded with the program's tracer installed -------------

def _spans_sample():
    trace = trace_reduce.load(SAMPLE_SPANS)
    return trace, span_reduce.load_spans(SAMPLE_SPANS)


def test_idle_readers_add_up_to_the_device_idle_of_the_chip_sample():
    trace, spans = _spans_sample()
    r = trace_reduce.reduce(trace)
    runs = sum(1 for e in trace.host if e.name == "bench.search")
    assert runs == 2
    cell = _cell(span_reduce.idle_by_span(trace, spans), runs)
    got = {n: m.read(cell) for n, m in READERS.items()}
    assert all(v is not None and v >= 0 for v in got.values())
    idle_ms = (r.window_s - r.busy_s) * 1e3 / runs
    assert sum(got.values()) == pytest.approx(idle_ms, rel=1e-9)
    assert got["plan_idle_ms"] > 0 and got["dispatch_idle_ms"] > 0
    # the program's spans cover the gap the midpoint rule puts under the
    # benchmark's own bench.search annotation
    under_search_ms = r.idle_gaps["bench.search"] * 1e3 / runs
    assert (got["plan_idle_ms"] + got["dispatch_idle_ms"]
            >= 0.9 * under_search_ms)


def test_chip_sample_holds_every_search_span_nested_by_id():
    _, spans = _spans_sample()
    by_id = {s.span_id: s for s in spans}
    assert len(by_id) == len(spans)
    want_parent = {
        "pipeline.search": None, "pipeline.encode": None,
        "pipeline.precursors_to_host": "pipeline.search",
        "pipeline.plan": "pipeline.search", "pipeline.scan": "pipeline.search",
        "pipeline.fdr": "pipeline.search", "search.sort_pad": "pipeline.scan",
        "search.gather": "pipeline.scan", "search.kernel": "pipeline.scan",
        "search.restore": "pipeline.scan"}
    assert {s.name for s in spans} == set(want_parent)
    for s in spans:
        if want_parent[s.name] is None:
            assert s.parent_id == 0 and s.trace_id == s.span_id
            continue
        p = by_id[s.parent_id]
        assert p.name == want_parent[s.name]
        assert s.trace_id == p.trace_id
        assert p.start <= s.start and s.end <= p.end


def test_chip_sample_program_spans_lie_inside_the_bench_annotation():
    """The clocks agree: each span lies inside the ``bench.*`` annotation
    around the call that opened it."""
    trace, spans = _spans_sample()
    caller = {"pipeline.encode": "bench.encode"}
    for s in spans:
        want = caller.get(s.name, "bench.search")
        assert any(e.name == want and e.start <= s.start and s.end <= e.end
                   for e in trace.host), s
