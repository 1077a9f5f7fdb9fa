"""``oms.py`` round trips, run in-process through ``main(argv)``.

``build`` then ``search`` answers as ``OMSPipeline.from_store`` does, resident
and streamed; ``queries | serve`` answers every line as the pipeline does,
with and without ``--resident``; a ``serve --trace`` recording reads back
through ``trace-report``; ``analyze --imports-only`` passes. The store is a
CPU-size build of the launcher's own synthetic library.
"""
import contextlib
import io
import json

import jax
import numpy as np
import pytest

from repro.core import OMSPipeline
from repro.data.spectra import LibraryConfig, make_dataset
from repro.launch import oms

REFS, QUERIES, TOP_K = 240, 40, 2
# Two full micro-batches of 20: one batch shape, compiled once.
SERVE = ["--max-r", "32", "--slab-rows", "96", "--top-k", str(TOP_K),
         "--max-batch", "20", "--max-wait-ms", "200", "--no-result-cache"]


@pytest.fixture(scope="module")
def launcher():
    """``oms.main`` with JAX's persistent compile cache left off, as in the
    rest of the suite; returns (stdout, stderr) of one call."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(oms, "enable_compile_cache", lambda: "")
        platforms = jax.config.jax_platforms

        def run(argv, stdin=""):
            out, err = io.StringIO(), io.StringIO()
            mp.setattr("sys.stdin", io.StringIO(stdin))
            try:
                with contextlib.redirect_stdout(out), \
                        contextlib.redirect_stderr(err):
                    oms.main(argv)
            finally:    # `queries` pins the platform; put it back
                jax.config.update("jax_platforms", platforms)
            return out.getvalue(), err.getvalue()

        yield run


@pytest.fixture(scope="module")
def store(launcher, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("launcher") / "store")
    out, _ = launcher(["build", "--store", path, "--refs", str(REFS),
                       "--dim", "512", "--n-levels", "16",
                       "--chunk-rows", "128"])
    assert f"wrote {path}: {2 * REFS} rows" in out
    return path


@pytest.fixture(scope="module")
def dataset():
    return make_dataset(LibraryConfig(n_refs=REFS, n_queries=QUERIES))


def _pipeline(store, mode):
    return OMSPipeline.from_store(store, max_r=32, top_k=TOP_K,
                                  resident=mode == "resident", slab_rows=96)


@pytest.fixture(scope="module")
def searched(launcher, store):
    """`search`'s printed summary and the answers its pipeline returned."""
    answers = []
    real = OMSPipeline.search_encoded

    def spy(self, *a, **k):
        answers.append(real(self, *a, **k))
        return answers[-1]

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(OMSPipeline, "search_encoded", spy)
        out, _ = launcher(["search", "--store", store, "--queries",
                           str(QUERIES), "--max-r", "32", "--top-k",
                           str(TOP_K)])
    assert len(answers) == 1
    return out, answers[0]


@pytest.mark.parametrize("mode", ["resident", "streamed"])
def test_build_then_search_equals_from_store(searched, store, dataset, mode):
    out, got = searched
    want = _pipeline(store, mode).search(dataset.queries)
    for f in want.result._fields:
        assert (np.asarray(getattr(want.result, f))
                == np.asarray(getattr(got.result, f))).all(), f
    assert (np.asarray(want.open_fdr.accept)
            == np.asarray(got.open_fdr.accept)).all()
    n_acc = int(want.open_fdr.n_accepted)
    assert f"identifications @ 1% FDR: {n_acc} / {QUERIES * TOP_K}" in out


def _served(launcher, store, extra):
    lines, _ = launcher(["queries", "--refs", str(REFS), "--queries",
                         str(QUERIES)])
    out, err = launcher(["serve", "--store", store, *SERVE, *extra],
                        stdin=lines)
    return [json.loads(line) for line in out.splitlines()], err


@pytest.mark.parametrize("mode", ["resident", "streamed"])
def test_queries_pipe_serve_answers_as_the_pipeline(launcher, store, dataset,
                                                    mode):
    answers, err = _served(launcher, store,
                           ["--resident"] if mode == "resident" else [])
    assert f"answered {QUERIES} queries" in err
    res = _pipeline(store, mode).search(dataset.queries).result
    want = [{"id": i,
             "std": {"idx": np.asarray(res.std_idx)[i].tolist(),
                     "sim": np.asarray(res.std_sim)[i].tolist()},
             "open": {"idx": np.asarray(res.open_idx)[i].tolist(),
                      "sim": np.asarray(res.open_sim)[i].tolist()}}
            for i in range(QUERIES)]
    assert answers == want


@pytest.mark.parametrize("fmt", ["json", "jsonl"])
def test_trace_report_reads_a_recorded_serve_trace(launcher, store, tmp_path,
                                                   fmt):
    trace = str(tmp_path / f"serve.trace.{fmt}")
    traced, err = _served(launcher, store, ["--trace", trace])
    assert f"-> {trace}" in err
    assert traced == _served(launcher, store, [])[0]   # tracing is transparent
    table, err = launcher(["trace-report", trace])
    for stage in ("pipeline.encode", "serve.scan", "serve.slab.search",
                  "serve.slab.gather", "serve.slab.wait"):
        assert stage in table, stage
    roll = json.loads(launcher(["trace-report", trace, "--json"])[0])
    assert roll["serve.slab.search"]["count"] >= 1
    assert f"in {trace}" in err


def test_trace_report_rejects_a_malformed_trace(launcher, tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"name": "x"}\nnot json\n')
    with pytest.raises(SystemExit) as exit_info:
        launcher(["trace-report", str(bad)])
    assert exit_info.value.code == 1


def test_analyze_imports_only_passes(launcher):
    out, _ = launcher(["analyze", "--imports-only"])
    assert "imports:" in out and "OK" in out
