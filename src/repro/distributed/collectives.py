"""Sharded OMS search: the paper's SmartSSD scale-out on a TPU mesh.

The paper deploys up to 24 SmartSSDs, each holding a DB slab and searching
independently; results merge on the host. On a TPU pod the analogue is:

  * the blocked ReferenceDB is split into contiguous PMZ slabs, one per
    ``model``-axis device (shard_reference_db pads to a block boundary);
  * queries are replicated over ``model`` (sharded over ``data``);
  * each device runs the *same* blocked dual-window search on its slab;
  * per-device winners (sim, row) merge with an all-gather + argmax over the
    model axis — 16 bytes/query of ICI traffic, negligible vs the scan.

Implemented with shard_map so the per-device program is literally the
single-device search (same code path as the paper's per-SSD kernel).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.blocking import ReferenceDB, shard_reference_db
from repro.core.search import SearchParams, _search_sorted_padded
from repro.kernels.topk import select_topk as _select_topk


def _merge_best(sim, row, axis_name, k: int):
    """Combine per-shard (Q, k) ranked winners into global (Q, k).

    Shards are gathered in ascending-offset order and each shard's list is
    already (sim desc, row asc), so the running-argmax selection keeps the
    global first-maximum tie-break; at k=1 this is the historical
    max-sim/first-shard merge. ICI traffic is 8*k bytes/query/shard.
    """
    sims = jax.lax.all_gather(sim, axis_name)    # (S, Q, k)
    rows = jax.lax.all_gather(row, axis_name)
    S, Q = sims.shape[0], sims.shape[1]
    sims = jnp.moveaxis(sims, 0, 1).reshape(Q, S * k)
    rows = jnp.moveaxis(rows, 0, 1).reshape(Q, S * k)
    return _select_topk(sims, k, payload=rows)   # (Q, k) sims + rows


def sharded_search(db: ReferenceDB, q_hvs, q_pmz, q_charge,
                   params: SearchParams, *, dim: int, mesh: Mesh,
                   model_axis: str = "model", data_axes=("data",)):
    """Distributed blocked search. Queries must be (charge,pmz)-sorted and
    padded to q_block (the pipeline wrapper handles that).

    Returns (std_sim, std_row, open_sim, open_row) with rows GLOBAL over the
    sharded DB.
    """
    n_model = mesh.shape[model_axis]
    db = shard_reference_db(db, n_model)
    rows_per_shard = db.n_rows // n_model
    blocks_per_shard = db.n_blocks // n_model

    db_specs = ReferenceDB(
        hvs=P(model_axis, None), pmz=P(model_axis), charge=P(model_axis),
        is_decoy=P(model_axis), orig_idx=P(model_axis),
        block_min=P(model_axis), block_max=P(model_axis),
        block_charge=P(model_axis), max_r=db.max_r,
    )

    local_params = params._replace(
        k_blocks=min(params.k_blocks, blocks_per_shard),
        exhaustive=params.exhaustive,
    )

    def local(db_local: ReferenceDB, qh, qp, qc):
        shard = jax.lax.axis_index(model_axis)
        std_b, std_row, open_b, open_row = _search_sorted_padded(
            db_local, qh, qp, qc, params=local_params, dim=dim)
        offset = shard.astype(jnp.int32) * rows_per_shard
        std_row = jnp.where(std_row >= 0, std_row + offset, std_row)
        open_row = jnp.where(open_row >= 0, open_row + offset, open_row)
        std_b, std_row = _merge_best(std_b, std_row, model_axis, params.top_k)
        open_b, open_row = _merge_best(open_b, open_row, model_axis, params.top_k)
        return std_b, std_row, open_b, open_row

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(db_specs, P(), P(), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    return fn(db, q_hvs, q_pmz, q_charge), db


# ---------------------------------------------------------------------------
# Near-storage loading: store shards -> mesh slabs
# ---------------------------------------------------------------------------


def streaming_engine_for_mesh(store_or_layout, mesh: Mesh, *, max_r: int,
                              slab_rows: int, model_axis: str = "model",
                              prefetch: bool = True):
    """Streaming per-mesh-slab serving: a :class:`~repro.serve.
    StreamingEngine` whose slab stream is dealt round-robin across the
    ``model``-axis devices — the multi-SmartSSD scale-out with the library
    *streamed* instead of slab-resident (`sharded_db_from_store`). Each
    device scans its slabs independently (async dispatch overlaps them) and
    keeps the running best of its own slabs; at the end the devices' bests
    merge on the first model-axis device by (sim desc, row asc) — the same
    tie discipline as ``_merge_best`` — so results stay bit-identical to
    the single-device engine and to a resident search.
    """
    from repro.serve import StreamingEngine
    devs = np.asarray(mesh.devices)
    axis = list(mesh.axis_names).index(model_axis)
    devs = np.moveaxis(devs, axis, 0).reshape(mesh.shape[model_axis], -1)[:, 0]
    return StreamingEngine(store_or_layout, max_r=max_r, slab_rows=slab_rows,
                           devices=list(devs), prefetch=prefetch)


def sharded_db_from_store(store, mesh: Mesh, *, max_r: int,
                          model_axis: str = "model") -> ReferenceDB:
    """Cold-start the sharded serving DB straight from a LibraryStore.

    The store's (charge, pmz)-sorted shards are merged into the blocked
    layout (memory-mapped reads, zero re-encoding), the block dimension is
    padded so the DB splits into ``mesh.shape[model_axis]`` contiguous
    slabs, and each slab is placed on its model-axis device with an
    explicit NamedSharding — the TPU analogue of the paper's per-SmartSSD
    DB slab residency. The result feeds ``sharded_search`` directly (which
    re-applies the now-no-op block padding).
    """
    n_model = mesh.shape[model_axis]
    db = shard_reference_db(store.load_reference_db(max_r=max_r), n_model)

    def _place(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    return ReferenceDB(
        hvs=_place(db.hvs, P(model_axis, None)),
        pmz=_place(db.pmz, P(model_axis)),
        charge=_place(db.charge, P(model_axis)),
        is_decoy=_place(db.is_decoy, P(model_axis)),
        orig_idx=_place(db.orig_idx, P(model_axis)),
        block_min=_place(db.block_min, P(model_axis)),
        block_max=_place(db.block_max, P(model_axis)),
        block_charge=_place(db.block_charge, P(model_axis)),
        max_r=db.max_r,
    )
