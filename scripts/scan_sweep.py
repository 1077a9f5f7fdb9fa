"""Time the vpu scan step's lowerings on one chip: the XLA tile (row slice,
then XOR, popcount and a sum over words) and the in-place Pallas kernel
(``kernels/hamming/hamming.py`` ``scan_tile_kernel``) at each register
blocking of queries x 128-row chunks.

    python3 scripts/scan_sweep.py [--rows 2322432] [--k-blocks 129]
                                  [--q-blocks 48]

On a TPU, from the root of a checkout (on the CPU the kernel is
interpreted: use small sizes there). A random library of iPRG2012's padded
size (2.32M rows of 128 words) lies on the device; each variant
scans ``--q-blocks`` query blocks of 16 against ``--k-blocks`` row blocks
of 1024 from seeded start rows, in one jitted ``lax.map``. Printed per
variant: the best of five runs in ms per q-block, and in cycles at 1.5 GHz
per vreg of 1024 XORed words; and whether its tiles and per-query minima
equal the XLA tile's bit for bit. The committed blocking is
``SCAN_Q_GROUP`` x ``SCAN_CHUNK_GROUP``.

Then, at the committed blocking, the scan step with its dual-window top-k:
the tile kernel followed by ``core.search._find_topk_dual`` in XLA (a
(16, rk) tile to HBM and back) against ``scan_best_pallas``, which keeps the
winners in VMEM; rows carry sorted precursor m/z with a charge boundary
mid-library, each query block sits at its run's middle. Printed: ms per
q-block of each, and whether their winners are equal bit for bit.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCKINGS = ((2, 8), (4, 8), (8, 4), (16, 2), (4, 4), (8, 2), (2, 4))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="scripts/scan_sweep.py")
    ap.add_argument("--rows", type=int, default=2_322_432)
    ap.add_argument("--k-blocks", type=int, default=129)
    ap.add_argument("--q-blocks", type=int, default=48)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.packing import hamming_matrix_packed
    from repro.kernels import interpret_default
    from repro.kernels.hamming import hamming as hk

    sr, w, nqb, kb = hk.SCAN_ROWS, 128, args.q_blocks, args.k_blocks
    rows = args.rows
    hvs = jax.random.bits(jax.random.PRNGKey(3), (rows, w), jnp.uint32)
    qs = jax.random.bits(jax.random.PRNGKey(4), (nqb, 16, w), jnp.uint32)
    qs = qs.at[:, 0, :4].set(0).at[:, 1, :4].set(0xFFFFFFFF)
    starts = jnp.asarray(np.random.default_rng(5).integers(
        0, rows // sr - kb + 1, nqb) * sr, jnp.int32)

    # The library is an argument of every jitted program, never a constant.
    def xla(q, s, hvs):
        return hamming_matrix_packed(
            q, jax.lax.dynamic_slice(hvs, (s, 0), (kb * sr, w)))

    def timed(tile):
        run = jax.jit(lambda qs, starts, hvs: jax.lax.map(
            lambda a: (lambda h: (h.min(1), h.argmin(1)))(tile(*a, hvs)),
            (qs, starts)))
        out = jax.block_until_ready(run(qs, starts, hvs))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(run(qs, starts, hvs))
            ts.append(time.perf_counter() - t0)
        return min(ts), [np.asarray(o) for o in out]

    vregs = nqb * 16 * kb * sr * w / 1024
    want_tile = np.asarray(jax.jit(xla)(qs[0], starts[0], hvs))
    best, want = timed(xla)
    print(f"xla: {best / nqb * 1e3:.4f} ms/qblock, "
          f"{best * 1.5e9 / vregs:.3f} cycles/vreg", flush=True)
    committed = (hk.SCAN_Q_GROUP, hk.SCAN_CHUNK_GROUP)
    for qg, cg in BLOCKINGS:
        hk.SCAN_Q_GROUP, hk.SCAN_CHUNK_GROUP = qg, cg

        def kernel(q, s, hvs):    # a new function, so that it is traced anew
            return hk.scan_tile_pallas(q, hvs, s // sr, n_blocks=kb,
                                       interpret=interpret_default())

        tile = np.asarray(jax.jit(kernel)(qs[0], starts[0], hvs))
        best, got = timed(kernel)
        same = bool((tile == want_tile).all()) and all(
            (a == b).all() for a, b in zip(got, want))
        mark = " (committed)" if (qg, cg) == committed else ""
        print(f"kernel q{qg}xc{cg}{mark}: {best / nqb * 1e3:.4f} ms/qblock, "
              f"{best * 1.5e9 / vregs:.3f} cycles/vreg, equal={same}",
              flush=True)
    hk.SCAN_Q_GROUP, hk.SCAN_CHUNK_GROUP = committed
    epilogue(hvs, qs, starts, kb)
    return 0


def epilogue(hvs, qs, starts, kb: int) -> None:
    """Time the scan step's dual-window top-k: tile + XLA against the
    kernel that keeps the winners in VMEM."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.search import SearchParams, _find_topk_dual
    from repro.kernels import interpret_default
    from repro.kernels.hamming import hamming as hk
    from repro.kernels.hamming.ops import PAD_PMZ

    sr, rows, nqb = hk.SCAN_ROWS, hvs.shape[0], qs.shape[0]
    dim, p = 32 * hvs.shape[1], SearchParams()
    pmz = jnp.linspace(400.0, 1400.0, rows, dtype=jnp.float32)
    charge = jnp.where(jnp.arange(rows) < rows // 2, 2, 3).astype(jnp.int32)
    mid = starts + kb * sr // 2
    qp = pmz[mid][:, None] + jnp.linspace(-1.0, 1.0, 16)[None, :]
    qc = jnp.broadcast_to(charge[mid][:, None], (nqb, 16))
    interpret = interpret_default()

    def tile_topk(q, qp, qc, s, hvs, pmz, charge):
        ham = hk.scan_tile_pallas(q, hvs, s // sr, n_blocks=kb,
                                  interpret=interpret)
        rp = jax.lax.dynamic_slice(pmz, (s,), (kb * sr,))
        rc = jax.lax.dynamic_slice(charge, (s,), (kb * sr,))
        return _find_topk_dual(dim - ham, jnp.abs(qp[:, None] - rp[None, :]),
                               qp, qc, rc, rp, p)

    def step(q, qp, qc, s, hvs, pmz, charge):
        return hk.scan_best_pallas(
            q, qp, qc, qp * (p.ppm_tol * 1e-6), hvs, pmz, charge, s // sr,
            n_blocks=kb, dim=dim, k=1, open_tol_da=p.open_tol_da,
            pad_pmz=PAD_PMZ, interpret=interpret)

    outs = {}
    for name, fn in (("tile + XLA top-k", tile_topk),
                     ("kernel top-k", step)):
        run = jax.jit(lambda qs, qp, qc, starts, hvs, pmz, charge, fn=fn:
                      jax.lax.map(lambda a: fn(*a, hvs, pmz, charge),
                                  (qs, qp, qc, starts)))
        args = (qs, qp, qc, starts, hvs, pmz, charge)
        outs[name] = [np.asarray(o) for o in
                      jax.block_until_ready(run(*args))]
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(run(*args))
            ts.append(time.perf_counter() - t0)
        print(f"{name}: {min(ts) / nqb * 1e3:.4f} ms/qblock", flush=True)
    a, b = outs.values()
    print(f"epilogue equal={all((x == y).all() for x, y in zip(a, b))}, "
          f"queries with a winner: std {int((a[0] >= 0).sum())}, open "
          f"{int((a[2] >= 0).sum())} of {a[0].size}", flush=True)


if __name__ == "__main__":
    sys.exit(main())
