"""Record the small profiler trace with program spans that tests/bench
checks the span split (``bench/span_reduce.py``) against, and print what
the trace holds.

    python3 bench/tools/record_span_trace.py <out_dir>

On a TPU: a small resident ``OMSPipeline`` searches one query batch twice
inside a ``bench.window`` annotation, each run as a batch cell runs it
(``bench.encode``, ``bench.search``, ``bench.fetch``), with a
``repro.obs.trace`` tracer installed and the harness's profiler options.
Writes ``<out_dir>/sample_spans.xplane.pb``. Also prints the stats that the
device's ``XLA Ops`` events carry, which says whether a reduction could read
an op's ``jax.named_scope`` from them under these options.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def record(out_dir: str) -> str:
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from repro.core import OMSConfig, OMSPipeline
    from repro.data.spectra import LibraryConfig, make_dataset
    from repro.obs import trace as obs_trace

    ds = make_dataset(LibraryConfig(n_refs=3000, n_queries=256, seed=7))
    pipe = OMSPipeline(OMSConfig(dim=1024, max_r=256), ds.refs)

    def one_run():
        with TraceAnnotation("bench.encode"):
            hvs, qp, qc = pipe.encode_queries(ds.queries)
        with TraceAnnotation("bench.search"):
            out = pipe.search_encoded(hvs, qp, qc)
        with TraceAnnotation("bench.fetch"):
            return [np.asarray(a) for a in jax.tree.leaves(out)]

    one_run()                                   # compiles every program
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=opts)
    obs_trace.install(obs_trace.Tracer())
    try:
        with TraceAnnotation("bench.window"):
            for _ in range(2):
                one_run()
    finally:
        obs_trace.uninstall()
        jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "sample_spans.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    return dst


def describe(path: str) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("   line", repr(line.name), len(evs))
            if line.name == "XLA Ops":
                stats = sorted({k for e in evs for k, _ in e.stats})
                print("      XLA Ops stats:", stats)
                for e in evs[:3]:
                    print("      ", repr(e.name[:160]), list(e.stats))
            for e in evs:
                st = dict(e.stats)
                if "span_id" in st:
                    print("      span", e.name, e.start_ns, e.duration_ns, st)
    print("bytes", os.path.getsize(path))


def main(out_dir: str) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    describe(record(out_dir))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
