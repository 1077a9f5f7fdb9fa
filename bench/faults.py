"""Faults planted under the timed path, for the tests that show a broken
program comes out not correct. A normal run plants none."""
from __future__ import annotations


def wrap_search(search, faults):
    """``search(hvs, q_pmz, q_charge) -> OMSOutput`` with ``faults`` in it:

    * ``half_batch``: only the first half of the queries is searched; the
      rest come back with no match;
    * ``answer_altered``: every open-window similarity is off by one.
    """
    import jax.numpy as jnp

    if "half_batch" in faults:
        inner_half = search

        def search(hvs, qp, qc):
            n, m = hvs.shape[0], hvs.shape[0] // 2
            out = inner_half(hvs[:m], qp[:m], qc[:m])

            def pad(x, fill):
                return jnp.concatenate(
                    [x, jnp.full((n - m,) + x.shape[1:], fill, x.dtype)])

            return out._replace(
                result=type(out.result)(*(pad(x, -1) for x in out.result)),
                open_fdr=out.open_fdr._replace(
                    accept=pad(out.open_fdr.accept, False)),
                std_fdr=out.std_fdr._replace(
                    accept=pad(out.std_fdr.accept, False)))
    if "answer_altered" in faults:
        inner_alt = search

        def search(hvs, qp, qc):
            out = inner_alt(hvs, qp, qc)
            r = out.result
            return out._replace(result=r._replace(
                open_sim=jnp.where(r.open_sim >= 0, r.open_sim + 1, -1)))
    return search
