"""Query planning: ``sort_pad_plan`` and ``plan_search``.

``sort_pad_plan`` gathers a batch into (charge, pmz) order with each charge
run padded to a ``q_block`` multiple; its ``unpad`` must invert the gather.
``plan_search`` sizes the scan (``k_blocks``) from q-blocks it forms on the
host, and those must be the q-blocks the device scans: the padded order cut
every ``q_block`` rows. Varied: Q {1, 15, 16, 17} x q_block {8, 16} x charge
runs {one, two} (with Q = 1 the one query of the second kind has charge 3).
"""
import types

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.search import plan_search, sort_pad_plan

OPEN_TOL = 20.0


def _queries(Q: int, runs: str, seed: int):
    """Charge-2 precursors within 100 Da, charge-3 ones over the whole
    range, so the charge-3 q-blocks set ``k_blocks``. With two runs a third
    of the queries, interleaved in the input, have charge 2, so the charge-3
    run starts off a q-block boundary of the batch."""
    rng = np.random.default_rng(seed)
    charge = np.full(Q, 2 if runs == "one" else 3, np.int32)
    if runs == "two":
        charge[rng.permutation(Q)[:Q // 3]] = 2
    pmz = np.where(charge == 2, rng.uniform(400.0, 500.0, Q),
                   rng.uniform(400.0, 1800.0, Q)).astype(np.float32)
    return pmz, charge


def _block_layout():
    """Blocks of charges 2 and 3, each a run ascending in pmz: 70 Da wide
    for charge 2, 110 Da for charge 3."""
    mins, maxs, chg = [], [], []
    for c, width in ((2, 70.0), (3, 110.0)):
        lo = np.arange(400.0, 1800.0, width)
        mins += lo.tolist()
        maxs += (lo + width - 0.5).tolist()
        chg += [c] * len(lo)
    return types.SimpleNamespace(
        block_min=np.asarray(mins, np.float32),
        block_max=np.asarray(maxs, np.float32),
        block_charge=np.asarray(chg, np.int32), n_blocks=len(mins))


@pytest.mark.parametrize("runs", ["one", "two"])
@pytest.mark.parametrize("q_block", [8, 16])
@pytest.mark.parametrize("Q", [1, 15, 16, 17])
def test_sort_pad_plan_and_plan_search_agree(Q, q_block, runs):
    pmz, charge = _queries(Q, runs, seed=Q * q_block)
    gather, unpad = sort_pad_plan(jnp.asarray(pmz), jnp.asarray(charge),
                                  q_block)
    g, u = np.asarray(gather), np.asarray(unpad)

    # A permutation of the batch plus padding rows; unpad inverts it.
    assert sorted(set(g.tolist())) == list(range(Q))
    assert g[u].tolist() == list(range(Q))
    x = np.arange(Q) * 7 + 3
    assert (x[g][u] == x).all()

    # Each charge run padded to q_block, so every q-block is charge-pure,
    # and (charge, pmz) ascending through the padded order.
    counts = {int(c): int((charge == c).sum()) for c in np.unique(charge)}
    assert len(g) == sum(-(-n // q_block) * q_block for n in counts.values())
    blocks = g.reshape(-1, q_block)
    assert all(len(set(charge[b].tolist())) == 1 for b in blocks)
    key = [(int(charge[i]), float(pmz[i])) for i in g]
    assert key == sorted(key)
    # Padding repeats the last (highest-pmz) query of its run.
    real = np.zeros(len(g), bool)
    real[u] = True
    for pos in np.flatnonzero(~real):
        assert g[pos] == g[pos - 1]

    # plan_search's k_blocks is the widest span of blocks any device
    # q-block's open window meets.
    db = _block_layout()
    spans = [1]
    for b in blocks:
        c, lo, hi = charge[b[0]], pmz[b].min() - OPEN_TOL, pmz[b].max() + OPEN_TOL
        hit = ((db.block_charge == c) & (db.block_max >= lo)
               & (db.block_min <= hi))
        spans.append(int(hit.sum()))
    got = plan_search(db, pmz, charge, open_tol_da=OPEN_TOL,
                      q_block=q_block, safety_blocks=0)
    assert got == min(max(spans), db.n_blocks)
    assert plan_search(db, pmz, charge, open_tol_da=OPEN_TOL,
                       q_block=q_block) == min(got + 2, db.n_blocks)
