"""Cross-shard top-k merge: the sharded search's ``collectives._merge_best``
and the round-robin streaming engine's ``engine._merge_by_row`` both equal
the global top-k by (sim desc, row asc).

``_merge_best`` runs under ``jax.vmap(..., axis_name=...)``, which gives its
``all_gather`` the semantics of a mesh axis in one process. Each shard holds
a contiguous row range, as ``shard_reference_db`` lays the library out, and
hands in its own ranked list with some ranks empty (-1). Varied: shards
{1, 2, 4} x k {1, 4} x ties {none, equal sims across shards}.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.collectives import _merge_best
from repro.serve.engine import _merge_by_row

Q, ROWS_PER_SHARD = 6, 50


def _shard_lists(rng, shards: int, k: int, ties: bool):
    """(S, Q, k) sims and global rows, each shard's list ranked by
    (sim desc, row asc), -1 where a shard has fewer than k candidates."""
    sims = np.full((shards, Q, k), -1, np.int32)
    rows = np.full((shards, Q, k), -1, np.int32)
    distinct = iter(rng.permutation(10_000))
    for s in range(shards):
        for q in range(Q):
            n = int(rng.integers(1 if q < Q // 2 else 0, k + 1))
            r = s * ROWS_PER_SHARD + rng.choice(ROWS_PER_SHARD, n,
                                                replace=False)
            if ties:
                # few distinct values, and the best one shared by every
                # shard in the first half of the queries
                v = rng.integers(100, 103, n)
                if q < Q // 2:
                    v[0] = 200
            else:
                v = np.asarray([next(distinct) for _ in range(n)])
            order = np.lexsort((r, -v))
            sims[s, q, :n] = v[order]
            rows[s, q, :n] = r[order]
    return sims, rows


def _global_topk(sims, rows, k: int):
    want_s = np.full((Q, k), -1, np.int32)
    want_r = np.full((Q, k), -1, np.int32)
    for q in range(Q):
        s, r = sims[:, q].ravel(), rows[:, q].ravel()
        ok = r >= 0
        order = np.lexsort((r[ok], -s[ok]))[:k]
        want_s[q, :len(order)] = s[ok][order]
        want_r[q, :len(order)] = r[ok][order]
    return want_s, want_r


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_shard_merges_equal_global_topk(shards, k, ties):
    rng = np.random.default_rng(100 * shards + 10 * k + ties)
    std = _shard_lists(rng, shards, k, ties)
    opn = _shard_lists(rng, shards, k, ties)
    if ties and shards > 1:
        top = std[0][:, :Q // 2, 0]
        assert (top == 200).all()      # the tie really crosses shards

    gathered = jax.vmap(lambda s, r: _merge_best(s, r, "model", k),
                        axis_name="model")
    for sims, rows in (std, opn):
        want = _global_topk(sims, rows, k)
        got_s, got_r = (np.asarray(x) for x in
                        gathered(jnp.asarray(sims), jnp.asarray(rows)))
        for s in range(shards):        # every shard ends with the same list
            assert got_s[s].tolist() == want[0].tolist(), s
            assert got_r[s].tolist() == want[1].tolist(), s

    # The engine merges device runs whose slabs interleave, so their order
    # in the list must not matter: hand them in reversed.
    runs = [(std[0][s], std[1][s], opn[0][s], opn[1][s])
            for s in reversed(range(shards))]
    out = [np.asarray(x) for x in _merge_by_row(runs, k)]
    for (sims, rows), got in ((std, out[:2]), (opn, out[2:])):
        want = _global_topk(sims, rows, k)
        assert got[0].tolist() == want[0].tolist()
        assert got[1].tolist() == want[1].tolist()
