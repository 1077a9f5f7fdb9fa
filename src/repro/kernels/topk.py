"""Running-argmax top-k selection — the single source of truth for the
winner-ranking contract shared by the fused Pallas kernel, the matrix-backend
orchestrator reduction (core/search.py), and the sharded winner merge
(distributed/collectives.py).

Contract: candidates ranked by (similarity desc, column asc) — ties resolve
to the first global maximum, bit-exact with ``jnp.argmax`` at k=1; ranks past
the valid candidates report -1. Invalid inputs are marked -1; consumed
entries are sunk to -2 so they are never re-selected, and outputs are clamped
back to -1.

Only max/min reductions, iota compares and selects (an unrolled static-k
loop; no ``argmax``, gather or ``lax.top_k``), so the same code lowers inside
a Pallas TPU kernel, in interpret mode, and under XLA. The ``lax.top_k``
path in kernels/hamming/ref.py is intentionally NOT routed through this
helper — it is the independent oracle the tests cross-check against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_INT32_MIN = int(jnp.iinfo(jnp.int32).min)


def select_topk(s, k: int, payload=None):
    """s: (Q, C) int32 masked sims, -1 = invalid.

    Returns ((Q, k) sims, (Q, k) picks) under the contract above. A pick is
    the selected column, or ``payload`` (Q, C) int32 at that column when
    given; -1 where the rank is empty.
    """
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    sims_out, pick_out = [], []
    for _ in range(k):
        best = jnp.max(s, axis=1, keepdims=True)
        # first column holding the maximum == jnp.argmax's tie-break
        first = jnp.min(jnp.where(s == best, col, s.shape[1]), axis=1,
                        keepdims=True)
        hot = col == first
        pick = first if payload is None else jnp.max(
            jnp.where(hot, payload, _INT32_MIN), axis=1, keepdims=True)
        best = jnp.maximum(best, jnp.int32(-1))
        sims_out.append(best)
        pick_out.append(jnp.where(best >= 0, pick, jnp.int32(-1)))
        s = jnp.where(hot, jnp.int32(-2), s)
    return (jnp.concatenate(sims_out, axis=1),
            jnp.concatenate(pick_out, axis=1))


def merge_topk(sim_a, idx_a, sim_b, idx_b, k: int):
    """Merge two (Q, k) ranked winner lists (sim, payload-idx) into one.

    ``a`` must hold the earlier (lower-index) candidates: on sim ties the
    first occurrence wins, so earlier candidates keep winning.
    """
    return select_topk(jnp.concatenate([sim_a, sim_b], axis=1), k,
                       payload=jnp.concatenate([idx_a, idx_b], axis=1))
