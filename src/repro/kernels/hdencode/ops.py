"""Jitted wrapper for the hdencode kernel.

The kernel runs in Pallas interpret mode only. Compiled for a TPU it is
refused: its (n_bins, word_tile) codebook block breaks the rule that a
block's last two dims be multiples of (8, 128), and a block widened to
128 words would put an 18 MiB codebook slice plus an in-kernel
``jnp.take`` gather into VMEM. Selecting it where the kernels compile
therefore raises instead of failing deep inside the compiler.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.hdencode import hdencode as _k


@partial(jax.jit, static_argnames=("spectra_tile", "word_tile", "interpret"))
def hdencode(bins, levels, mask, id_hvs, level_hvs, tiebreak, *,
             spectra_tile: int = 16, word_tile: int = 8,
             interpret: bool | None = None):
    if interpret is None:
        interpret = interpret_default()
    if not interpret:
        raise NotImplementedError(
            "the 'pallas' encode backend has no compiled TPU lowering (its "
            "codebook block and in-kernel gather do not fit the TPU "
            "compiler); use encode_backend 'word_tiled' or 'fused'")
    B = bins.shape[0]
    W = id_hvs.shape[1]
    st = min(spectra_tile, B) if B else spectra_tile
    wt = min(word_tile, W)
    while W % wt:
        wt -= 1
    padb = (-B) % st

    def padrows(x, value=0):
        return jnp.pad(x, [(0, padb), (0, 0)], constant_values=value) if padb else x

    out = _k.hdencode_pallas(
        padrows(bins.astype(jnp.int32)),
        padrows(levels.astype(jnp.int32)),
        padrows(mask.astype(jnp.int32)),
        id_hvs, level_hvs, tiebreak,
        spectra_tile=st, word_tile=wt, interpret=interpret)
    return out[:B]
