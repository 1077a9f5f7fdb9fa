"""Contract analyzer: true-positive detection + registry/import-graph checks.

The analyzer's value is catching real violations, so the core of this file
is a set of toy functions that each commit one forbidden act — materialise
a (Qb, Rk) score matrix, promote to int64, call back to the host inside
jit, blow a byte bound, churn the jit cache — and must each trip exactly
their contract with a readable error that names the offending equation.

The import-graph half pins the layering: the repo graph stays cycle-free,
the two declared leaf modules import nothing from ``repro``, and the cycle
detector itself is exercised on a synthetic cyclic package.
"""
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import contracts as C
from repro.analysis import registry
from repro.analysis.imports import (LEAF_MODULES, build_import_graph,
                                    check_imports, find_cycles)
from repro.analysis.jaxpr_walk import (find_shape_carriers, format_eqn,
                                       max_intermediate_bytes,
                                       peak_intermediate)

SRC_ROOT = os.path.join(os.path.dirname(__file__), "..", "src")

# Distinct sizes so shape-membership tests cannot collide.
QB, RK, W = 8, 96, 16


# ---------------------------------------------------------------------------
# True positives: each toy function must trip exactly its contract
# ---------------------------------------------------------------------------


class TestNoMaterializeTruePositive:
    def test_score_matrix_is_caught(self):
        def scores(q, r):
            return jnp.einsum("qw,rw->qr", q, r)   # the (Qb, Rk) matrix

        jaxpr = jax.make_jaxpr(scores)(jnp.zeros((QB, W), jnp.float32),
                                       jnp.zeros((RK, W), jnp.float32))
        res = C.check_no_materialize(jaxpr, q_block=QB, r_rows=RK,
                                     target="test:toy")
        assert not res.passed
        assert res.eqn is not None and "dot_general" in res.eqn
        assert f"Qb={QB}" in res.detail and f"Rk={RK}" in res.detail

    def test_reference_slice_alone_does_not_trip(self):
        # Loading the (Rk, W) reference slice is every path's obligation —
        # only something carrying BOTH Qb and Rk is a score matrix.
        def reduce_refs(q, r):
            return q.sum() + (r * 2).sum()

        jaxpr = jax.make_jaxpr(reduce_refs)(jnp.zeros((QB, W), jnp.float32),
                                            jnp.zeros((RK, W), jnp.float32))
        res = C.check_no_materialize(jaxpr, q_block=QB, r_rows=RK)
        assert res.passed

    def test_xor_tensor_inside_scan_is_caught(self):
        # The walker must recurse into scan bodies: a per-step (Qb, Rk)
        # intermediate hidden in a lax.scan is still a materialisation.
        def scanned(q, r):
            def step(carry, _):
                return carry + (q[:, None, :] * r[None, :, :]).sum(-1), None
            out, _ = jax.lax.scan(step, jnp.zeros((QB, RK)), jnp.arange(3))
            return out.sum()

        jaxpr = jax.make_jaxpr(scanned)(jnp.zeros((QB, W), jnp.float32),
                                        jnp.zeros((RK, W), jnp.float32))
        res = C.check_no_materialize(jaxpr, q_block=QB, r_rows=RK)
        assert not res.passed


class TestDtypeStabilityTruePositive:
    def test_int64_promotion_is_caught(self):
        # Under default x64-disabled jax the promotion is silently
        # truncated, so the toy must run with x64 enabled to produce the
        # real 64-bit equation the contract exists to catch.
        with jax.enable_x64(True):
            jaxpr = jax.make_jaxpr(
                lambda x: x.astype(jnp.int64) + 1)(np.zeros(4, np.int32))
        res = C.check_dtype_stability(jaxpr, target="test:toy")
        assert not res.passed
        assert "64-bit" in res.detail and "int64" in res.detail
        assert res.eqn is not None and "convert_element_type" in res.eqn

    def test_packed_hv_carrier_change_is_caught(self):
        # A [..., W]-shaped unsigned tensor that is not uint32 means the
        # packed-HV carrier dtype changed on its way to the xor/popcount.
        def narrow(x):
            return x.astype(jnp.uint8) ^ 1

        jaxpr = jax.make_jaxpr(narrow)(jnp.zeros((QB, W), jnp.uint32))
        res = C.check_dtype_stability(jaxpr, hv_words=W)
        assert not res.passed
        assert "carrier dtype" in res.detail and "uint8" in res.detail

    def test_signed_popcount_result_is_not_a_carrier(self):
        # popcount results are signed int32 with trailing dim W — NOT HV
        # carriers; the unsigned-only clause must leave them alone.
        def popcnt(x):
            return jax.lax.population_count(x).astype(jnp.int32)

        jaxpr = jax.make_jaxpr(popcnt)(jnp.zeros((QB, RK, W), jnp.uint32))
        res = C.check_dtype_stability(jaxpr, hv_words=W)
        assert res.passed


class TestNoHostTransferTruePositive:
    def test_pure_callback_inside_jit_is_caught(self):
        # A literal jax.device_get on a tracer already fails at trace time;
        # the host call that CAN sneak into a jitted hot loop is a callback.
        @jax.jit
        def leaky(x):
            y = jax.pure_callback(
                lambda v: np.asarray(v) * 2,
                jax.ShapeDtypeStruct((4,), jnp.float32), x)
            return y + 1

        jaxpr = jax.make_jaxpr(leaky)(jnp.zeros(4, jnp.float32))
        res = C.check_no_host_transfer(jaxpr, target="test:toy")
        assert not res.passed
        assert "pure_callback" in res.detail
        assert res.eqn is not None and "pure_callback" in res.eqn

    def test_clean_jit_passes(self):
        jaxpr = jax.make_jaxpr(jax.jit(lambda x: x * 2 + 1))(jnp.zeros(4))
        assert C.check_no_host_transfer(jaxpr).passed


class TestPeakIntermediateTruePositive:
    def test_bound_violation_names_the_equation(self):
        def blowup(q, r):
            return (q[:, None] * r[None, :]).sum()   # (QB, RK) f32

        jaxpr = jax.make_jaxpr(blowup)(jnp.zeros(QB), jnp.zeros(RK))
        res = C.check_peak_intermediate(jaxpr, bound_bytes=64,
                                        target="test:toy")
        assert not res.passed
        assert f"peak {QB * RK * 4} B" in res.detail
        assert res.eqn is not None and "mul" in res.eqn

    def test_generous_bound_passes(self):
        jaxpr = jax.make_jaxpr(
            lambda q, r: (q[:, None] * r[None, :]).sum())(
                jnp.zeros(QB), jnp.zeros(RK))
        assert C.check_peak_intermediate(jaxpr,
                                         bound_bytes=QB * RK * 4).passed


class TestRecompileGuard:
    def test_same_shape_repeats_pass(self):
        @jax.jit
        def f(x):
            return x + 1

        f(jnp.zeros(4))
        guard = C.RecompileGuard([("f", f)])
        guard.arm()
        f(jnp.zeros(4))
        f(jnp.zeros(4))
        assert guard.check(target="test:loop").passed

    def test_shape_churn_is_caught(self):
        @jax.jit
        def g(x):
            return x * 2

        g(jnp.zeros(4))
        guard = C.RecompileGuard([("g", g)])
        guard.arm()
        g(jnp.zeros(8))          # new abstract signature -> cache growth
        res = guard.check(target="test:loop")
        assert not res.passed
        assert "g(+1)" in res.detail
        assert res.eqn == "recompiled: g"

    def test_churn_before_arm_raises(self):
        guard = C.RecompileGuard([])
        with pytest.raises(RuntimeError, match="arm"):
            guard.churn()


# ---------------------------------------------------------------------------
# Walker + registry mechanics
# ---------------------------------------------------------------------------


class TestWalker:
    def test_peak_recurses_into_scan_bodies(self):
        def scanned(x):
            def step(c, _):
                return c, jnp.outer(x, x)            # (RK, RK) per step
            _, ys = jax.lax.scan(step, 0.0, jnp.arange(2))
            return ys.sum()

        jaxpr = jax.make_jaxpr(scanned)(jnp.zeros(RK))
        assert max_intermediate_bytes(jaxpr) >= RK * RK * 4
        peak, eqn = peak_intermediate(jaxpr)
        assert peak >= RK * RK * 4 and eqn is not None

    def test_format_eqn_names_primitive_and_shape(self):
        jaxpr = jax.make_jaxpr(lambda x, y: x @ y)(jnp.zeros((QB, W)),
                                                   jnp.zeros((W, QB)))
        hits = find_shape_carriers(jaxpr, (QB, QB), min_rank=2)
        assert hits
        line = format_eqn(hits[0])
        assert "dot_general" in line and str(QB) in line

    def test_empty_jaxpr_peak_is_zero(self):
        peak, eqn = peak_intermediate(jax.make_jaxpr(lambda x: x)(1.0))
        assert peak == 0 and eqn is None


class TestRegistry:
    def test_unknown_contract_rejected(self):
        with pytest.raises(ValueError, match="unknown contract"):
            registry.declare("test:x", "no_such_contract")

    def test_peak_bound_required(self):
        with pytest.raises(ValueError, match="bound"):
            registry.declare("test:x", "peak_intermediate")

    def test_hot_paths_all_declare_contracts(self):
        # Importing the protected modules registers their declarations;
        # every registered backend must have stated its memory story.
        from repro.core import backends, encode_backends  # noqa: F401
        import repro.serve.engine                          # noqa: F401

        for be in backends.names():
            assert registry.declarations(f"search:{be}"), be
        for be in encode_backends.names():
            assert registry.declarations(f"encode:{be}"), be
        assert registry.declarations("serve:slab_step")
        assert registry.declarations("serve:loop", "recompile_guard")
        assert "serve:slab_step" in registry.targets("serve")

    def test_expected_violation_passes_with_note(self):
        # expect=False documents an exemption: the observed violation is
        # reported as passing, annotated with the declaration's note.
        decl = registry.ContractDecl("test:exempt", "no_materialize",
                                     note="by design", expect=False)
        jaxpr = jax.make_jaxpr(
            lambda q, r: jnp.einsum("qw,rw->qr", q, r))(
                jnp.zeros((QB, W)), jnp.zeros((RK, W)))
        res = C.evaluate(decl, jaxpr, {"q_block": QB, "rk": RK})
        assert res.passed
        assert "documented exemption" in res.detail and "by design" in res.detail
        assert res.eqn is not None    # still reports what it measured

    def test_stale_exemption_is_flagged(self):
        decl = registry.ContractDecl("test:exempt", "no_materialize",
                                     expect=False)
        jaxpr = jax.make_jaxpr(lambda q: q.sum())(jnp.zeros((QB, W)))
        res = C.evaluate(decl, jaxpr, {"q_block": QB, "rk": RK})
        assert not res.passed
        assert "stale exemption" in res.detail

    def test_evaluate_rejects_recompile_guard(self):
        decl = registry.ContractDecl("test:x", "recompile_guard")
        with pytest.raises(ValueError, match="recompile_guard"):
            C.evaluate(decl, None, {})


# ---------------------------------------------------------------------------
# Import graph: the repo layering regression test + detector exercises
# ---------------------------------------------------------------------------


class TestImportGraph:
    def test_repo_graph_is_cycle_free(self):
        report = check_imports(SRC_ROOT)
        assert report["cycles"] == [], report["cycles"]
        assert report["ok"], report

    def test_leaf_modules_import_nothing_from_repro(self):
        # repro.store.format and repro.analysis.registry are imported at
        # module level from both sides of a package boundary — one repro
        # import in either re-opens the core<->store / core<->analysis
        # cycle the layering exists to prevent.
        graph = build_import_graph(SRC_ROOT)
        for leaf in LEAF_MODULES:
            assert leaf in graph, f"{leaf} vanished — update LEAF_MODULES"
            assert graph[leaf] == [], (leaf, graph[leaf])

    def test_synthetic_cycle_is_detected(self, tmp_path):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "a.py").write_text("from pkg import b\n")
        (pkg / "b.py").write_text("import pkg.a\n")
        graph = build_import_graph(str(tmp_path), package="pkg")
        assert find_cycles(graph) == [["pkg.a", "pkg.b"]]

    def test_lazy_and_type_checking_imports_are_not_edges(self, tmp_path):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "a.py").write_text(textwrap.dedent("""\
            from typing import TYPE_CHECKING
            if TYPE_CHECKING:
                from pkg import b

            def f():
                from pkg import b
                return b
        """))
        (pkg / "b.py").write_text("from pkg import a\n")
        graph = build_import_graph(str(tmp_path), package="pkg")
        assert graph["pkg.a"] == []          # both imports are lazy
        assert graph["pkg.b"] == ["pkg.a"]   # no cycle at import time
        assert find_cycles(graph) == []

    def test_self_loop_is_a_cycle(self):
        assert find_cycles({"m": ["m"]}) == [["m"]]


# ---------------------------------------------------------------------------
# End-to-end: the runner's report shape on the real matrix (tiny smoke)
# ---------------------------------------------------------------------------


class TestRunnerSmoke:
    def test_full_matrix_holds(self):
        # The real acceptance check, at the same smoke shapes the CLI uses
        # but without the (slow) runtime recompile pass — the structural
        # contracts across every combination must hold in CI.
        from repro.analysis import runner

        report = runner.run(with_recompile=False)
        assert report["ok"], runner.summarize(report)
        # 4 encode x 7 search x 2 path x (cascade on/off + prefix on),
        # plus the trace_transparency pass as its own "obs" combo
        assert report["n_combinations"] == 169
        assert report["n_checks"] > report["n_combinations"]
        sample = report["combos"][0]
        assert {"encode", "search", "path", "cascade", "prefix",
                "contracts", "passed"} <= set(sample)
        assert any(c["prefix"] for c in report["combos"])
        (obs,) = [c for c in report["combos"] if c["path"] == "obs"]
        assert obs["passed"]
        assert all(r["contract"] == "trace_transparency"
                   for r in obs["contracts"])
