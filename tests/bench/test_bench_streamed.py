"""The streamed batch cell end to end on the CPU at a tiny size: a sound run
is correct, a planted fault and the control are not, and the two stream
metrics read the program's recorded spans."""
from __future__ import annotations

import shutil

import numpy as np

from bench_tiny import TINY_DIM, TINY_QUERIES, tiny_control
from bench import harness
from bench.metrics import slab_gather_ms, slab_wait_ms

CELL = "hek293_streamed.batch_streamed"
E2E = ["batch_qps", "setup_s"]


def tiny_streamed_cell(*, seed: int = 2**33 + 29, faults=(),
                       control: bool = False) -> harness.Cell:
    """The cell cut as ``bench_tiny.tiny_cell`` cuts the batch cells, with
    slabs of four 256-row blocks, so a search streams several slabs."""
    cell = harness.make_cell(
        CELL, "bench/configs/hek293_streamed.json", "batch_streamed",
        seed=seed, seconds=0.5, trace=False,
        metric_defs=[{"name": n, "unit": "-"} for n in E2E])
    c = cell.cfg
    c["check"]["control"] = tiny_control(c["check"]["control"],
                                         c["encoding"]["dim"],
                                         c["library"]["queries_per_run"])
    c["library"]["n_targets"] = 2000
    c["library"]["queries_per_run"] = TINY_QUERIES
    c["encoding"]["dim"] = TINY_DIM
    c["search"]["max_r"] = 256
    c["ingest"]["chunk_rows"] = 1024
    c["check"]["sample_queries"] = 48
    c["store"]["slab_rows"] = 1024
    cell.faults = tuple(faults)
    if control:
        cell.program_overrides.update(c["check"]["control"])
    return cell


def test_streamed_sound_run_is_correct():
    cell = tiny_streamed_cell()
    line = harness.run_cell(cell)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == set(E2E)
    assert {n: v["value"] for n, v in line["checks"].items()} == {
        "sample_mismatch": 0, "fdr_mismatch": 0, "repeat_mismatch": 0}
    assert cell.info["n_slabs"] > 1 and cell.info["slabs_scanned"] > 1
    assert cell.layer["scanned_pairs"] == cell.info["scanned_pairs"] > 0
    assert cell.info["warmup_compiles"] >= 0


def test_streamed_fault_is_not_correct():
    line = harness.run_cell(tiny_streamed_cell(faults=("answer_altered",)))
    assert line["correct"] is False
    assert line["checks"]["sample_mismatch"]["value"] > 0


def test_streamed_control_is_not_correct():
    line = harness.run_cell(tiny_streamed_cell(control=True))
    assert line["correct"] is False
    assert line["checks"]["sample_mismatch"]["value"] > 0


def test_stream_metrics_read_recorded_spans():
    from repro.core import OMSPipeline
    from repro.obs import Tracer, install, uninstall

    cell = tiny_streamed_cell()
    ds = harness.make_data(cell, cell.cfg["library"]["queries_per_run"])
    path = harness.ingest(cell, ds.refs)
    try:
        pipe = OMSPipeline.from_store(
            path, resident=False, slab_rows=cell.cfg["store"]["slab_rows"],
            **harness.serving_overrides(cell.cfg))
        hvs, qp, qc = pipe.encode_queries(ds.queries)
        pipe.search_encoded(hvs, qp, qc)
        assert slab_gather_ms.read(cell) is None   # nothing recorded yet
        t = install(Tracer())
        try:
            for _ in range(2):
                pipe.search_encoded(hvs, qp, qc)
        finally:
            uninstall()
    finally:
        shutil.rmtree(path, ignore_errors=True)
    cell.layer.update(spans=t.events(), runs=2)
    for reader, name in ((slab_gather_ms, "serve.slab.gather"),
                         (slab_wait_ms, "serve.slab.wait")):
        durs = [e.dur_ns for e in t.events() if e.name == name]
        assert len(durs) == 2 * pipe.engine.last_stats.n_scanned
        assert np.isclose(reader.read(cell), sum(durs) / 1e6 / 2)
