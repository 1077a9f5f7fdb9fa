"""Multi-device behaviours — each case runs in a subprocess with forced host
devices so the main pytest process keeps its single-device view."""
import os
import subprocess
import sys
import textwrap


SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_sharded_search_equals_single_device():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import OMSConfig, OMSPipeline
        from repro.core.search import _CHARGE_KEY
        from repro.data.spectra import LibraryConfig, make_dataset
        from repro.distributed.collectives import sharded_search

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = OMSConfig(dim=512, max_r=64, q_block=8, n_levels=16)
        ds = make_dataset(LibraryConfig(n_refs=1024, n_queries=64, seed=4))
        pipe = OMSPipeline(cfg, ds.refs)
        hvs, qp, qc = pipe.encode_queries(ds.queries)
        ref = pipe.search(ds.queries)
        params = pipe.search_params(qp, qc)
        order = jnp.argsort(jnp.clip(qp,0,_CHARGE_KEY-1.0)+qc*_CHARGE_KEY)
        with mesh:
            (sb, sr_, ob, orow), padded = sharded_search(
                pipe.db, hvs[order], qp[order], qc[order], params,
                dim=cfg.dim, mesh=mesh)
        inv = jnp.argsort(order)
        ob = np.asarray(ob)[inv]; orow = np.asarray(orow)[inv]
        orig = np.asarray(padded.orig_idx)
        got = np.where(orow>=0, orig[np.clip(orow,0,len(orig)-1)], -1)
        want_idx = np.asarray(ref.result.open_idx)
        want_sim = np.asarray(ref.result.open_sim)
        ok = (got == want_idx) | (ob == want_sim)
        assert ok.all(), np.flatnonzero(~ok)[:5]
        print("SHARDED_OK")
    """)
    assert "SHARDED_OK" in out


def test_streaming_engine_per_mesh_slab():
    """Streaming serve across mesh devices: the slab stream dealt round-robin
    over the model axis must stay bit-identical to the resident search."""
    out = _run("""
        import jax, numpy as np, tempfile
        from repro.core import OMSConfig, OMSPipeline
        from repro.core.search import oms_search
        from repro.data.spectra import LibraryConfig, make_dataset
        from repro.distributed.collectives import streaming_engine_for_mesh

        cfg = OMSConfig(dim=512, max_r=32, q_block=8, n_levels=16)
        ds = make_dataset(LibraryConfig(n_refs=500, n_queries=40, seed=5))
        pipe = OMSPipeline(cfg, ds.refs)
        hvs, qp, qc = pipe.encode_queries(ds.queries)
        params = pipe.search_params(qp, qc, top_k=3)
        want = oms_search(pipe.db, hvs, qp, qc, params, dim=cfg.dim)
        with tempfile.TemporaryDirectory() as tmp:
            store = OMSPipeline.ingest(cfg, ds.refs, tmp + "/s")
            mesh = jax.make_mesh((2, 4), ("data", "model"))
            eng = streaming_engine_for_mesh(store, mesh, max_r=cfg.max_r,
                                            slab_rows=96)
            assert len(eng.devices) == 4
            got = eng.search_encoded(hvs, qp, qc, params, dim=cfg.dim)
        for f in want._fields:
            assert (np.asarray(getattr(want, f))
                    == np.asarray(getattr(got, f))).all(), f
        print("STREAM_MESH_OK")
    """)
    assert "STREAM_MESH_OK" in out


def test_pipeline_parallel_forward():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.pipeline import pipeline_forward

        n_stages, n_micro, mb, d = 4, 8, 2, 16
        mesh = jax.make_mesh((4,), ("pipe",))
        key = jax.random.PRNGKey(0)
        ws = jax.random.normal(key, (n_stages, d, d)) / np.sqrt(d)

        def layer_fn(w, x):
            return jnp.tanh(x @ w)

        x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))

        def staged(ws_local, x):
            return pipeline_forward(layer_fn, ws_local[0], x,
                                    n_stages=n_stages, n_micro=n_micro)

        fn = jax.shard_map(staged, mesh=mesh,
                           in_specs=(P("pipe"), P()), out_specs=P("pipe"),
                           check_vma=False)
        with mesh:
            stacked = fn(ws, x)          # (n_stages*n_micro, mb, d)
        got = stacked[:n_micro]          # stage 0 holds the final outputs
        # reference: sequential through all stages
        ref = x
        for s in range(n_stages):
            ref = jnp.tanh(ref @ ws[s])
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        print("PIPELINE_OK")
    """)
    assert "PIPELINE_OK" in out


def test_elastic_remesh_and_reshard():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.distributed.elastic import (remesh, reshard_tree,
                                               simulate_node_failure)

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        tree = {"w": x}
        specs = {"w": P("data", "model")}
        sharded = reshard_tree(tree, specs, mesh)

        survivors = simulate_node_failure(mesh, n_lost_nodes=2)
        new_mesh = remesh(survivors, model_axis_size=2)
        assert new_mesh.shape["data"] == 3
        resharded = reshard_tree(sharded, specs, new_mesh)
        np.testing.assert_array_equal(np.asarray(resharded["w"]),
                                      np.asarray(x))
        print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in out


def test_zero1_opt_state_sharding_lowers():
    """Train step lowers+compiles on a small (2,4) mesh with ZeRO-1 opt
    sharding — the miniature of the production dry-run."""
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.configs import get_config
        from repro.launch.specs import make_cell, make_step_fn

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cell = make_cell("whisper-base", "train_4k", mesh=mesh,
                         n_microbatches=2)
        step = make_step_fn(cell, n_microbatches=2)
        def sh(t):
            return jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), t,
                is_leaf=lambda x: isinstance(x, PartitionSpec))
        j = jax.jit(step, in_shardings=tuple(sh(s) for s in cell.in_specs),
                    donate_argnums=cell.donate)
        with mesh:
            c = j.lower(*cell.args).compile()
        assert c is not None
        print("ZERO1_OK")
    """)
    assert "ZERO1_OK" in out
