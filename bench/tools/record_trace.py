"""Record the small profiler trace that tests/bench checks the trace
reduction against, and print the trace's planes and lines.

    python3 bench/tools/record_trace.py <out_dir>

On a TPU: two jitted programs run twice inside a ``bench.window``
annotation, with host sleeps under ``bench.*`` annotations between them, so
the trace holds device busy time, idle gaps and their host activities.
Writes ``<out_dir>/sample.xplane.pb``.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    mm = jax.jit(lambda x: (x @ x).sum())
    ew = jax.jit(lambda x: jnp.sin(x) * 2.0 + 1.0)
    x = jnp.ones((2048, 2048), jnp.float32)
    mm(x).block_until_ready()
    ew(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.matmul"):
                mm(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.01)
            with jax.profiler.TraceAnnotation("bench.elementwise"):
                ew(x).block_until_ready()
            time.sleep(0.005)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "sample.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(dst).planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("   line", repr(line.name), len(evs),
                  [(e.name, e.start_ns, e.duration_ns) for e in evs[:4]])
    print("bytes", os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
