"""Pallas kernels for the paper's hot spots: the §II-C Hamming search (VPU
and MXU formulations) and the §II-A HD encoder."""
from __future__ import annotations

import jax


def interpret_default() -> bool:
    """Pallas interpret mode is the default on the CPU backend only.

    Any other platform compiles the kernels for real, so a platform the
    kernels cannot lower to fails loudly instead of silently falling back
    to the interpreter.
    """
    return jax.default_backend() == "cpu"
