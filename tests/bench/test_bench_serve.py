"""The open-loop serve driver end to end on the CPU at a tiny size, through
``oms.py serve``'s stdin and stdout."""
from __future__ import annotations

import numpy as np
import pytest

from bench_tiny import tiny_cell
from bench import harness
from bench.drivers import open_loop


def test_serve_sound_run_is_correct():
    line = harness.run_cell(tiny_cell("iprg2012.serve"))
    assert line["correct"] is True
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"serve_p50_ms", "serve_p95_ms", "setup_s"}
    p50 = line["metrics"]["serve_p50_ms"]["value"]
    assert 0 < p50 <= line["metrics"]["serve_p95_ms"]["value"]


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
def test_serve_fault_is_not_correct(fault):
    line = harness.run_cell(tiny_cell("iprg2012.serve", faults=(fault,)))
    assert line["correct"] is False
    assert line["checks"]["response_mismatch"]["value"] > 0


def test_serve_control_is_not_correct():
    line = harness.run_cell(tiny_cell("iprg2012.serve", control=True))
    assert line["correct"] is False


def test_arrivals_same_gaps_every_seed():
    a = open_loop.arrival_offsets(500, 10.0, 1)
    b = open_loop.arrival_offsets(500, 10.0, 2)
    ga, gb = np.diff(np.r_[a, 10.0]), np.diff(np.r_[b, 10.0])
    assert not np.array_equal(ga, gb)
    np.testing.assert_allclose(np.sort(ga), np.sort(gb))
    assert a[0] == 0.0 and abs(ga.sum() - 10.0) < 1e-9


def test_request_lines_carry_each_query_by_its_id():
    import json

    cell = tiny_cell("iprg2012.serve")
    queries = harness.make_data(cell, 6).queries
    lines = open_loop.request_lines(queries, [4, 1])
    assert all(line.endswith("\n") for line in lines)
    for rid, line in zip([4, 1], lines):
        obj = json.loads(line)
        assert obj["id"] == rid
        assert obj["pmz"] == float(np.asarray(queries.pmz)[rid])
        assert obj["charge"] == int(np.asarray(queries.charge)[rid])
        kept = np.asarray(queries.intensity)[rid] > 0
        assert obj["mz"] == np.asarray(queries.mz)[rid][kept].tolist()
        assert len(obj["mz"]) == len(obj["intensity"]) > 0


def test_latency_is_from_due_time_and_counts_missing_and_errors():
    due = {0: 1.0, 1: 2.0, 2: 3.0, 3: 4.0}
    got = {0: (1.010, {"id": 0}), 1: (2.030, {"id": 1}),
           3: (4.020, {"id": 3, "error": "x"})}
    st = open_loop.latency_stats([0, 1, 2, 3], due, got)
    assert st["n"] == 3 and st["missing"] == 1 and st["errors"] == 1
    assert abs(st["p50_ms"] - 20.0) < 1e-6
    assert abs(st["p95_ms"] - 29.0) < 1e-6
