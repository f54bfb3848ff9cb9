"""SpmmPlan tests: bit-identity with the unplanned path, executable-cache
behavior (traces stay flat), values substitution, and the plan-backed
engine / serving / SparseLinear integration."""

import numpy as np
import jax.numpy as jnp
import pytest

import repro.sparse_api as sp
from repro.core.sparse import power_law_sparse, random_sparse, spmm_reference


def _packed(seed=1, m=512, k=512, n=64):
    rng = np.random.default_rng(seed)
    a = power_law_sparse(m, k, 6, seed=seed)
    A = sp.from_sparse_matrix(a, tm=128, k0=128, chunk=8, bucket=True)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    return a, A, b, c


class TestPlanCorrectness:
    def test_bit_identical_to_unplanned_jnp(self):
        _, A, b, c = _packed()
        P = sp.plan(A, 64, backend="jnp")
        y_p = np.asarray(P.run(b, c, 1.25, -0.5))
        y_u = np.asarray(sp.spmm(A, b, c, 1.25, -0.5, backend="jnp"))
        assert np.array_equal(y_p, y_u)

    def test_bit_identical_to_unplanned_pallas(self):
        _, A, b, c = _packed()
        opts = dict(tn=64, interpret=True)
        P = sp.plan(A, 64, backend="pallas", **opts)
        y_p = np.asarray(P.run(b, c, 2.0, 0.5))
        y_u = np.asarray(sp.spmm(A, b, c, 2.0, 0.5, backend="pallas", **opts))
        assert np.array_equal(y_p, y_u)

    def test_matches_reference(self):
        a, A, b, c = _packed(seed=3)
        P = sp.plan(A, 64, backend="jnp")
        ref = spmm_reference(a, np.asarray(b), np.asarray(c), 1.5, -0.25)
        np.testing.assert_allclose(np.asarray(P.run(b, c, 1.5, -0.25)), ref,
                                   rtol=2e-4, atol=2e-4 * np.abs(ref).max())

    def test_values_substitution(self):
        _, A, b, _ = _packed(seed=4)
        P = sp.plan(A, 64, backend="jnp")
        v2 = A.values * 3.0
        y = np.asarray(P.run(b, values=v2))
        y_ref = np.asarray(sp.spmm(A.with_values(v2), b, backend="jnp"))
        assert np.array_equal(y, y_ref)

    def test_bsr_plan(self):
        rng = np.random.default_rng(0)
        w = rng.standard_normal((64, 96)).astype(np.float32)
        B = sp.from_dense(w, format=sp.Format.BSR, block=(16, 16))
        b = jnp.asarray(rng.standard_normal((96, 8)), jnp.float32)
        P = sp.plan(B, 8, backend="jnp")
        np.testing.assert_allclose(np.asarray(P.run(b)), w @ np.asarray(b),
                                   rtol=2e-4, atol=1e-4)

    def test_operand_validation(self):
        _, A, b, _ = _packed()
        P = sp.plan(A, 64, backend="jnp")
        with pytest.raises(ValueError):
            P.run(b[:, :32])               # wrong N
        with pytest.raises(ValueError):
            sp.plan(A, 0)


class TestPlanCache:
    def test_traces_flat_across_runs(self):
        """Repeated plan.run calls (including alpha/beta sweeps) never
        re-trace a backend body."""
        _, A, b, c = _packed(seed=5)
        P = sp.plan(A, 64, backend="jnp")
        t0 = sp.BACKEND_STATS["traces"]
        for alpha, beta in [(1.0, 0.0), (0.5, 0.5), (2.0, -1.0)]:
            P.run(b, c, alpha, beta)
        assert sp.BACKEND_STATS["traces"] == t0

    def test_bucket_mates_share_executable(self):
        """Distinct matrices packed into the same bucketed geometry share
        one compiled executable: planning the second is trace-free."""
        a1, A1, b, c = _packed(seed=6)
        a2 = power_law_sparse(512, 512, 6, seed=60)
        A2 = sp.from_sparse_matrix(a2, tm=128, k0=128, chunk=8, bucket=True)
        assert A1.geometry == A2.geometry, "bucket precondition"
        sp.plan(A1, 64, backend="jnp")
        t0 = sp.BACKEND_STATS["traces"]
        P2 = sp.plan(A2, 64, backend="jnp")
        assert sp.BACKEND_STATS["traces"] == t0
        ref = spmm_reference(a2, np.asarray(b), np.asarray(c), 1.0, 1.0)
        np.testing.assert_allclose(np.asarray(P2.run(b, c, 1.0, 1.0)), ref,
                                   rtol=2e-4, atol=2e-4 * np.abs(ref).max())

    def test_exec_cache_stats_and_clear(self):
        _, A, b, _ = _packed(seed=7)
        sp.clear_plan_cache()
        m0 = sp.PLAN_STATS["exec_misses"]
        sp.plan(A, 64, backend="jnp")
        assert sp.PLAN_STATS["exec_misses"] == m0 + 1
        h0 = sp.PLAN_STATS["exec_hits"]
        sp.plan(A, 64, backend="jnp")
        assert sp.PLAN_STATS["exec_hits"] == h0 + 1


class TestPlanIntegration:
    def test_engine_spmm_is_plan_backed_and_bit_identical(self):
        from repro.core.engine import SextansEngine

        rng = np.random.default_rng(1)
        a = random_sparse(100, 128, 0.05, seed=1)
        b = jnp.asarray(rng.standard_normal((128, 16)), jnp.float32)
        eng_p = SextansEngine(tm=32, k0=64, chunk=8, impl="jnp",
                              use_plans=True)
        eng_u = SextansEngine(tm=32, k0=64, chunk=8, impl="jnp",
                              use_plans=False)
        t = eng_p.pack(a)
        y_p = np.asarray(eng_p.spmm(t, b, alpha=1.5, beta=0.0))
        y_u = np.asarray(eng_u.spmm(t, b, alpha=1.5, beta=0.0))
        assert np.array_equal(y_p, y_u)
        assert len(eng_p._plans) == 1
        eng_p.spmm(t, b)                      # same (matrix, N): cached plan
        assert len(eng_p._plans) == 1

    def test_legacy_packed_input_hits_plan_cache(self):
        """PackedSpMM callers get a fresh SparseTensor wrapper per call; the
        plan cache must key on the caller's object, not the wrapper
        (regression: one leaked plan per spmm call)."""
        from repro.core.engine import SextansEngine

        rng = np.random.default_rng(3)
        a = random_sparse(64, 64, 0.1, seed=1)
        packed = sp.pack_hflex(a, tm=32, k0=32, chunk=8)
        eng = SextansEngine(tm=32, k0=32, chunk=8, impl="jnp")
        b = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
        for _ in range(5):
            out = eng.spmm(packed, b)
        assert len(eng._plans) == 1
        ref = spmm_reference(a, np.asarray(b), np.zeros((64, 8), np.float32))
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=1e-4)

    def test_serving_reports_plan_compiles(self):
        from repro.core.engine import SextansEngine
        from repro.launch.serve import SpmmRequest, serve_spmm_requests

        rng = np.random.default_rng(2)
        a = random_sparse(96, 96, 0.05, seed=3)
        reqs = [SpmmRequest(a=a,
                            b=rng.standard_normal((96, 8)).astype(np.float32))
                for _ in range(3)]
        outs, stats = serve_spmm_requests(
            reqs, SextansEngine(tm=32, k0=32, chunk=8, impl="jnp"))
        assert "plan_executables_compiled" in stats
        for r, o in zip(reqs, outs):
            ref = spmm_reference(r.a, r.b, np.zeros_like(o))
            np.testing.assert_allclose(o, ref, rtol=2e-4,
                                       atol=2e-4 * max(np.abs(ref).max(), 1))

    def test_sparse_linear_use_plan(self):
        from repro.models.common import Initializer
        from repro.models.layers import SparseLinear

        rng = np.random.default_rng(0)
        init = Initializer(seed=0, dtype=jnp.float32)
        layer, params = SparseLinear.create(init, 32, 48, block=(16, 16),
                                            density=0.5)
        x = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
        y0 = np.asarray(layer(params, x, backend="jnp"))
        y1 = np.asarray(layer(params, x, backend="jnp", use_plan=True))
        np.testing.assert_allclose(y0, y1, rtol=1e-6, atol=1e-6)
        # live weight update flows through the plan's values operand
        p2 = {"w": params["w"] * 1.5}
        y2 = np.asarray(layer(p2, x, backend="jnp", use_plan=True))
        y2r = np.asarray(layer(p2, x, backend="jnp"))
        np.testing.assert_allclose(y2, y2r, rtol=1e-6, atol=1e-6)
        assert len(layer._plans) == 1          # one plan per batch size


class TestInterpretDefault:
    def test_platform_aware_resolution(self):
        from repro.kernels._compat import resolve_interpret
        import jax

        expected = jax.default_backend() != "tpu"
        assert resolve_interpret(None) is expected
        assert resolve_interpret(True) is True
        assert resolve_interpret(False) is False


class TestBsrLaneTiles:
    def test_compiled_bsr_kernel_refuses_narrow_blocks_at_plan_time(self):
        """The TPU BSR kernel tiles x and the output by whole lane tiles:
        a 16x16-block weight is refused when the plan is built, with the
        block size in the message, instead of failing in the compiler."""
        rng = np.random.default_rng(0)
        B = sp.from_dense(rng.standard_normal((64, 96)).astype(np.float32),
                          format=sp.Format.BSR, block=(16, 16))
        with pytest.raises(ValueError, match="16x16.*multiples of 128"):
            sp.plan(B, 8, backend="pallas", interpret=False)
        # interpreted, the same weight still runs (CPU development path)
        sp.plan(B, 8, backend="pallas", interpret=True)
