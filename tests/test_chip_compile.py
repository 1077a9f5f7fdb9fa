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
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.blocking import ReferenceDB
from repro.core.search import SearchParams, _search_sorted_padded
from repro.kernels.hamming import ops as hops
from repro.kernels.hamming_mxu import ops as mops

DIM = 4096
W = DIM // 32
Q = 16                      # OMSConfig.q_block: queries per kernel call
R = 40 * 4096               # ~k_blocks x max_r rows scanned per query block
IPRG_ROWS = 2_322_432       # 2 x 1.16M iPRG2012 rows, padded to 4096-blocks
HBM_BYTES = 16e9            # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
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
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


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
