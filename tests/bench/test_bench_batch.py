"""The batch driver end to end on the CPU at a tiny size: a sound run is
correct, and the control and each planted fault come out not correct."""
from __future__ import annotations

import shutil

import numpy as np
import pytest

from bench_tiny import tiny_cell
from bench import harness, roofline


def test_batch_sound_run_is_correct():
    cell = tiny_cell("iprg2012.batch")
    line = harness.run_cell(cell)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"batch_qps", "setup_s"}
    assert line["metrics"]["batch_qps"]["value"] > 0
    assert {n: v["value"] for n, v in line["checks"].items()} == {
        "sample_mismatch": 0, "fdr_mismatch": 0, "repeat_mismatch": 0}


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
def test_batch_fault_is_not_correct(fault):
    line = harness.run_cell(tiny_cell("iprg2012.batch", faults=(fault,)))
    assert line["correct"] is False
    assert line["checks"]["sample_mismatch"]["value"] > 0


@pytest.mark.parametrize("config", ["iprg2012", "hek293"])
def test_batch_control_is_not_correct(config):
    """The program's own inexact path (prefix-word pruning with a margin
    under the exact bound, scaled from the configuration's) is the control:
    it must fail the comparison."""
    line = harness.run_cell(tiny_cell(f"{config}.batch", control=True))
    assert line["correct"] is False
    assert line["checks"]["sample_mismatch"]["value"] > 0


def test_scan_useful_pct_at_most_100():
    from repro.core.search import scanned_rows

    from bench.metrics import scan_useful_pct

    cell = tiny_cell("hek293.batch")
    ds = harness.make_data(cell, cell.cfg["library"]["queries_per_run"])
    path = harness.ingest(cell, ds.refs)
    try:
        pipe = harness.cold_start(cell, path)
    finally:
        shutil.rmtree(path, ignore_errors=True)
    qp = np.asarray(ds.queries.pmz)
    qc = np.asarray(ds.queries.charge)
    params = pipe.search_params(qp, qc)
    cell.layer["scanned_pairs"] = scanned_rows(pipe.db, len(qp), params)
    refs = ds.refs
    pmz = np.tile(np.asarray(refs.pmz), 2)
    charge = np.tile(np.asarray(refs.charge), 2)
    cell.layer["work"] = roofline.window_work(
        pmz, charge, qp, qc, cell.cfg["search"]["open_tol_da"])
    pct = scan_useful_pct.read(cell)
    assert 0 < pct <= 100
