"""Pallas TPU kernel: Hamming distance via unpack-in-VMEM ±1 int8 MXU matmul.

The beyond-paper TPU adaptation (DESIGN.md §4). The FPGA spends LUT fabric on
XOR+popcount; a TPU has a 128x128 systolic MXU that does int8 matmuls at 2x
the bf16 rate. With bits mapped to ±1,

    dot(x, y) = (#agree - #disagree) = D - 2 * hamming
    hamming   = (D - dot) / 2

so Hamming search IS a matmul — *if* the operands are unpacked. Unpacking in
HBM would cost 32x the bandwidth (and the paper's whole point is bandwidth).
This kernel therefore streams the *packed* uint32 words HBM->VMEM and unpacks
to ±1 int8 inside VMEM right before feeding the MXU:

    HBM traffic:   packed (Dhv/8 bytes per HV)   — paper-faithful compression
    compute:       int8 MXU matmul               — TPU-native throughput

Layout note: the unpack is by bit plane — plane b holds bit b of every word
of a wt-word chunk as a (tile, wt) ±1 int8 matrix, and the dot is the sum of
the 32 plane matmuls, each contracting over the chunk's words. The word axis
stays on the vector lanes, so no in-kernel reshape is needed, and integer
addition makes the plane order irrelevant to the result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.hamming import hamming as _vpu


def _pm1_plane(words: jax.Array, b: int) -> jax.Array:
    """(N, wt) uint32 -> (N, wt) int8: bit b of each word as ±1 (0 -> +1)."""
    bit = (words >> jnp.uint32(b)) & jnp.uint32(1)
    return (1 - 2 * bit.astype(jnp.int32)).astype(jnp.int8)


def _dot_tile(q_ref, r_ref, wt: int):
    """(QT, W) x (RT, W) packed-word refs -> (QT, RT) int32 ±1 dot product,
    in static wt-word chunks (the caller guarantees W % wt == 0)."""
    acc = 0
    for s in range(0, q_ref.shape[1], wt):
        q = q_ref[:, s:s + wt]
        r = r_ref[:, s:s + wt]
        for b in range(32):
            acc = acc + jax.lax.dot_general(
                _pm1_plane(q, b), _pm1_plane(r, b),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)
    return acc


def hamming_mxu_kernel(q_ref, r_ref, out_ref, *, dim: int, wt: int):
    out_ref[...] = (dim - _dot_tile(q_ref, r_ref, wt)) // 2


def hamming_matrix_mxu_pallas(q, r, *, dim: int, q_tile: int = 128,
                              r_tile: int = 256, word_tile: int = 128,
                              interpret: bool = True):
    """All-pairs Hamming (Q, R) int32 via the MXU formulation.

    Requires dim == 32 * W (no partial last word; ops.py enforces).
    """
    Q, W = q.shape
    R = r.shape[0]
    grid = (Q // q_tile, R // r_tile)
    return pl.pallas_call(
        functools.partial(hamming_mxu_kernel, dim=dim, wt=word_tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((q_tile, W), lambda i, j: (i, 0)),
            pl.BlockSpec((r_tile, W), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((q_tile, r_tile), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Q, R), jnp.int32),
        interpret=interpret,
    )(q, r)


# ---------------------------------------------------------------------------
# Fused dual-window search on the MXU (§II-C kernel, MXU formulation)
# ---------------------------------------------------------------------------
#
# The VPU kernel's fused body (repro.kernels.hamming.fused_search_kernel —
# grid over (q-tile, r-tile), sequential last axis, running top-k winners)
# with the Hamming tile from the ±1 int8 MXU matmul instead of xor+popcount.
# The dot is exact integer arithmetic, so the winners are bit-identical to
# the VPU kernel's.


def mxu_sims(q_ref, r_ref, *, dim: int, wt: int):
    """(QT, RT) int32 similarity ``dim - hamming`` from the ±1 dot."""
    return dim - (dim - _dot_tile(q_ref, r_ref, wt)) // 2


def fused_search_mxu_pallas(q_hvs, r_hvs, q_pmz, r_pmz, q_charge, r_charge,
                            *, dim: int, k: int = 1, ppm_tol: float = 20.0,
                            open_tol_da: float = 75.0,
                            q_tile: int = 32, r_tile: int = 256,
                            word_tile: int = 128, pad_pmz: float | None = None,
                            interpret: bool = True):
    """Returns (std_sim, std_idx, open_sim, open_idx), each (Q, k) int32.

    Same contract as ``hamming.fused_search_pallas`` (idx is the row in
    ``r_hvs`` or -1; rank order (sim desc, row asc); ``k`` static), with
    the Hamming tile computed on the MXU. Requires dim == 32 * W.
    """
    return _vpu.fused_search_pallas(
        q_hvs, r_hvs, q_pmz, r_pmz, q_charge, r_charge, dim=dim, k=k,
        ppm_tol=ppm_tol, open_tol_da=open_tol_da, q_tile=q_tile,
        r_tile=r_tile, pad_pmz=pad_pmz,
        sims_fn=functools.partial(mxu_sims, dim=dim, wt=word_tile),
        interpret=interpret)
