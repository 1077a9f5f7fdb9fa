"""Tiny cells of the benchmark for CPU tests: the configurations of
BENCHMARK.json with the library, queries and widths cut down, driven by the
harness with the chip check left out."""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402


# The end-to-end metrics each traffic's driver reports.
E2E = {"batch": ["batch_qps", "setup_s"],
       "open_poisson": ["serve_p50_ms", "serve_p95_ms", "setup_s"]}


TINY_DIM = 512
TINY_QUERIES = 200


def tiny_control(control: dict, full_dim: int, full_queries: int) -> dict:
    """The configuration's control at ``TINY_DIM`` and ``TINY_QUERIES``: the
    same share of the words as the prefix, the same share of the exact
    margin (the bits the prefix leaves out) as the margin, and a seed window
    over which the queries cover the same share of the precursor range."""
    words = control["prefix_words"] * TINY_DIM // full_dim
    exact_full = full_dim - 32 * control["prefix_words"]
    exact_tiny = TINY_DIM - 32 * words
    return {"prefix_words": words,
            "prefix_margin": control["prefix_margin"] * exact_tiny
            // exact_full,
            "prefix_seed_da": control["prefix_seed_da"] * full_queries
            / TINY_QUERIES}


def tiny_cell(workload: str, *, seed: int = 2**33 + 17, faults=(),
              control: bool = False, seconds: float = 0.5) -> harness.Cell:
    """``workload`` is ``<config>.<traffic>``, as BENCHMARK.json names its
    cells; it need not be one of them."""
    config, traffic = workload.split(".")
    traffic = {"serve": "open_poisson"}.get(traffic, traffic)
    cell = harness.make_cell(
        workload, f"bench/configs/{config}.json", traffic, seed=seed,
        seconds=seconds, trace=False,
        metric_defs=[{"name": n, "unit": "-"} for n in E2E[traffic]])
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
    if cell.traffic["driver"] == "open_loop":
        cell.traffic["rate_per_s"] = 40.0
        cell.traffic["warmup_s"] = 0.5
    cell.faults = tuple(faults)
    if control:
        cell.program_overrides.update(c["check"]["control"])
    return cell
