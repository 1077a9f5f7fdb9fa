"""Compile the main-path kernels and search for a described TPU v5e.

JAX's TPU compiler compiles for a chip that is described and not attached,
and refuses what the chip would refuse (an unaligned slice, a block that
breaks the tiling rule, more VMEM than a kernel may use). Nothing runs
here, so these tests say nothing about results or times; the interpret-
mode tests check results. The topology is described inside a fixture,
never at import: only one process at a time may load the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels
from repro.analysis import contracts, registry
from repro.core.blocking import ReferenceDB
from repro.core.search import SearchParams, _search_sorted_padded
from repro.kernels.hamming import ops as hops
from repro.kernels.hamming_mxu import ops as mops

DIM = 4096
W = DIM // 32
Q = 16                      # OMSConfig.q_block: queries per kernel call
R = 40 * 4096               # ~k_blocks x max_r rows scanned per query block
IPRG_ROWS = 2_322_432       # 2 x 1.16M iPRG2012 rows, padded to 4096-blocks
HEK_ROWS = 6_001_664        # 2 x 3M HEK293 rows, padded to 1024-blocks
HBM_BYTES = 16e9            # one v5e chip


@pytest.fixture(scope="module")
def v5e():
    """The four devices of a described v5e:2x2."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo.devices
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits_one_chip(compiled):
    m = compiled.memory_analysis()
    used = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert used < HBM_BYTES, used


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("ops", [hops, mops], ids=["fused", "fused_mxu"])
def test_fused_kernel_compiles(one_chip, ops, k):
    def search(*a):
        return ops.fused_search(*a, dim=DIM, k=k, interpret=False)

    s = functools.partial(_spec, one_chip)
    compiled = jax.jit(search).lower(
        s((Q, W), jnp.uint32), s((R, W), jnp.uint32),
        s((Q,), jnp.float32), s((R,), jnp.float32),
        s((Q,), jnp.int32), s((R,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


@pytest.mark.parametrize("name", ["kernel_vpu", "kernel_mxu"])
def test_matrix_kernel_compiles(one_chip, name):
    if name == "kernel_vpu":
        def tile(q, r):
            return hops.hamming_matrix(q, r, interpret=False)
    else:
        def tile(q, r):
            return mops.hamming_matrix(q, r, DIM, interpret=False)
    compiled = jax.jit(tile).lower(_spec(one_chip, (Q, W), jnp.uint32),
                                   _spec(one_chip, (R, W), jnp.uint32)
                                   ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_search_compiles_at_iprg2012_rows(one_chip):
    """The default-backend blocked scan over the whole iPRG2012 library
    (targets + decoys resident on one chip) with a 2048-query batch."""
    s = functools.partial(_spec, one_chip)
    nb = IPRG_ROWS // 4096
    db = ReferenceDB(
        hvs=s((IPRG_ROWS, W), jnp.uint32), pmz=s((IPRG_ROWS,), jnp.float32),
        charge=s((IPRG_ROWS,), jnp.int32),
        is_decoy=s((IPRG_ROWS,), jnp.bool_),
        orig_idx=s((IPRG_ROWS,), jnp.int32),
        block_min=s((nb,), jnp.float32), block_max=s((nb,), jnp.float32),
        block_charge=s((nb,), jnp.int32), max_r=4096)
    qp = 2048 + 2 * Q            # two charge groups padded to q_block
    compiled = _search_sorted_padded.lower(
        db, s((qp, W), jnp.uint32), s((qp,), jnp.float32),
        s((qp,), jnp.int32),
        params=SearchParams(k_blocks=40, backend="vpu"), dim=DIM).compile()
    _fits_one_chip(compiled)


@pytest.fixture
def tpu_platform(monkeypatch):
    """Steer the platform check that picks the vpu scan step's lowering: the
    CPU here would choose the XLA tile. The lowering is decided when a
    jitted program is traced, so JAX's caches are cleared on both sides."""
    not_cpu = lambda: False  # noqa: E731
    monkeypatch.setattr(repro.kernels, "interpret_default", not_cpu)
    monkeypatch.setattr(hops, "interpret_default", not_cpu)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _library(s, rows, max_r=1024):
    nb = rows // max_r
    return ReferenceDB(
        hvs=s((rows, W), jnp.uint32), pmz=s((rows,), jnp.float32),
        charge=s((rows,), jnp.int32), is_decoy=s((rows,), jnp.bool_),
        orig_idx=s((rows,), jnp.int32),
        block_min=s((nb,), jnp.float32), block_max=s((nb,), jnp.float32),
        block_charge=s((nb,), jnp.int32), max_r=max_r)


def _queries(s, n):
    return s((n, W), jnp.uint32), s((n,), jnp.float32), s((n,), jnp.int32)


def _keeps_contracts(fn, args, targets, ctx):
    """The traced program keeps every declared contract of ``targets``
    (``peak_intermediate`` among them) and holds no (q_block, rk) distance
    tile outside a kernel: the scan step returns winners, not a tile."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    results = [contracts.evaluate(d, jaxpr, ctx) for t in targets
               for d in registry.declarations(t)
               if d.contract != "recompile_guard"]
    results.append(contracts.check_no_materialize(
        jaxpr, q_block=Q, r_rows=ctx["rk"], target="scan step"))
    for r in results:
        assert r.passed, r.as_dict()


def _search_ctx(k_blocks, **extra):
    return {"dim": DIM, "n_words": W, "q_block": Q, "rk": k_blocks * 1024,
            "top_k": 1, **extra}


@pytest.mark.parametrize("rows,k_blocks", [(IPRG_ROWS, 129), (HEK_ROWS, 323)],
                         ids=["iprg2012", "hek293"])
def test_vpu_search_runs_the_scan_kernel(one_chip, tpu_platform, rows,
                                         k_blocks):
    """The vpu blocked scan at the benchmark's row shapes (``max_r`` 1024)
    holds the in-place scan kernel, with the dual-window top-k inside it,
    keeps the ``search:vpu`` contracts and fits one chip."""
    s = functools.partial(_spec, one_chip)
    args = (_library(s, rows), *_queries(s, 2048 + 2 * Q))
    params = SearchParams(k_blocks=k_blocks, backend="vpu")
    compiled = _search_sorted_padded.lower(*args, params=params,
                                           dim=DIM).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)
    _keeps_contracts(
        functools.partial(_search_sorted_padded, params=params, dim=DIM),
        args, ["search:vpu"], _search_ctx(k_blocks))


@pytest.mark.parametrize("slab_rows,n_queries,n_qb,k_blocks", [
    (1 << 16, 2 * Q, 2, 64), (1 << 18, 2 * Q, 2, 129),
    (1 << 18, 47_008, 288, 256)],
    ids=["capped_to_slab", "default_slab", "hek293_streamed"])
def test_vpu_slab_search_runs_the_scan_kernel(one_chip, tpu_platform,
                                              slab_rows, n_queries, n_qb,
                                              k_blocks):
    """The streamed engine's slab step (``serve/engine.py``
    ``_search_sorted_padded_slab``): one slab of the library, the plan's
    ``k_blocks`` capped to the slab's blocks (iPRG2012's 129; HEK293's
    ~320), over a serve-sized query batch or the HEK293 run's 47k queries
    with 288 of its q-blocks selected, folded into the running best."""
    from repro.serve.engine import _search_sorted_padded_slab

    s = functools.partial(_spec, one_chip)
    run = tuple(s((n_queries, 1), jnp.int32) for _ in range(4))
    args = (run, _library(s, slab_rows), *_queries(s, n_queries),
            s((), jnp.int32), s((), jnp.int32))
    params = SearchParams(k_blocks=k_blocks, backend="vpu")
    compiled = _search_sorted_padded_slab.lower(
        *args, params=params, dim=DIM, n_qb=n_qb).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)
    _keeps_contracts(
        functools.partial(_search_sorted_padded_slab, params=params,
                          dim=DIM, n_qb=n_qb),
        args, ["search:vpu", "serve:slab_step"],
        _search_ctx(k_blocks, slab_rows=slab_rows))


def test_sharded_vpu_search_runs_the_scan_kernel(v5e, tpu_platform):
    """``distributed.collectives.sharded_search`` over the four chips of a
    v5e:2x2: the iPRG2012 library split into four slabs, each chip's scan
    through the in-place kernel inside ``shard_map``."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.distributed.collectives import sharded_search

    mesh = Mesh(np.asarray(v5e).reshape(1, 4), ("data", "model"))

    def by_row(shape, dtype):
        spec = P("model", *[None] * (len(shape) - 1))
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def replicated(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P()))

    params = SearchParams(k_blocks=129, backend="vpu")

    def search(db, qh, qp, qc):
        return sharded_search(db, qh, qp, qc, params, dim=DIM, mesh=mesh)[0]

    args = (_library(by_row, IPRG_ROWS), *_queries(replicated, 2048 + 2 * Q))
    compiled = jax.jit(search).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)
    jaxpr = jax.make_jaxpr(search)(*args)
    assert contracts.check_no_materialize(
        jaxpr, q_block=Q, r_rows=129 * 1024).passed
