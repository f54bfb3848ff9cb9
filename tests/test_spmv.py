"""Skinny-N SpMV fast-lane tests.

Acceptance criteria of the vector lane:

* the NT-less ``spmv`` kernel is **bit-identical** to the tall-N Sextans
  kernel (per-column math is shared discipline) and ``spmv_jnp`` is
  bit-identical to ``jnp`` (same function, own routing name);
* the default ``auto`` policy routes HFLEX requests with
  N <= ``SKINNY_N_MAX`` to the lane — ``spmv`` on TPU, ``spmv_jnp``
  elsewhere — without disturbing the existing platform/format/density
  rules (the policy table is pinned below);
* plans, the engine and the serving scheduler resolve/route/count the lane
  (``skinny_dispatches``), and the lane streams and differentiates.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.sparse_api as sp
from repro.core.sparse import power_law_sparse, spmm_reference
from repro.sparse_api.backends import _default_auto_policy, _operand_width

TALL_OPTS = dict(tn=16, interpret=True)


def _packed(m=300, k=500, seed=1, n=5, tm=64, k0=64):
    rng = np.random.default_rng(seed)
    a = power_law_sparse(m, k, 6, seed=seed)
    A = sp.from_sparse_matrix(a, tm=tm, k0=k0, chunk=8, bucket=True)
    b = rng.standard_normal((k, n)).astype(np.float32)
    c = rng.standard_normal((m, n)).astype(np.float32)
    return a, A, b, c


class TestSpmvKernel:
    @pytest.mark.parametrize("n", [1, 3, 5, 8])
    def test_bit_identical_to_tall_n_kernel(self, n):
        """The lane drops the NT grid dimension but keeps the per-column
        math — results match the tall-N kernel bit for bit."""
        _, A, b, c = _packed(n=n)
        y_tall = np.asarray(sp.spmm(A, b, c, 1.25, -0.5, backend="pallas",
                                    **TALL_OPTS))
        y_v = np.asarray(sp.spmm(A, b, c, 1.25, -0.5, backend="spmv",
                                 interpret=True))
        np.testing.assert_array_equal(y_v, y_tall)

    def test_onehot_gather_variant(self):
        _, A, b, c = _packed()
        y_tall = np.asarray(sp.spmm(A, b, c, 2.0, 0.5,
                                    backend="pallas_onehot", **TALL_OPTS))
        y_v = np.asarray(sp.spmm(A, b, c, 2.0, 0.5, backend="spmv",
                                 gather="onehot", interpret=True))
        np.testing.assert_array_equal(y_v, y_tall)

    def test_matches_reference(self):
        a, A, b, c = _packed(seed=3)
        ref = spmm_reference(a, b, c, 1.5, -0.25)
        y = np.asarray(sp.spmm(A, b, c, 1.5, -0.25, backend="spmv",
                               interpret=True))
        np.testing.assert_allclose(y, ref, rtol=2e-4,
                                   atol=2e-4 * max(1, np.abs(ref).max()))

    def test_batched_group_bit_identical_per_member(self):
        rng = np.random.default_rng(0)
        _, A1, b1, _ = _packed(seed=1)
        _, A2, _, _ = _packed(seed=2)
        S = sp.stack_hflex([A1, A2])
        bg = np.stack([b1, rng.standard_normal(b1.shape).astype(np.float32)])
        yg = np.asarray(sp.spmm(S, bg, backend="spmv", interpret=True))
        for i, Ai in enumerate((A1, A2)):
            np.testing.assert_array_equal(
                yg[i], np.asarray(sp.spmm(Ai, bg[i], backend="spmv",
                                          interpret=True)))

    def test_streams_through_spmv_hooks(self):
        """The lane's StreamOps carry the raw f32 accumulator bit-exactly —
        the out-of-core tier works at vector widths too."""
        _, A, b, c = _packed()
        y_res = np.asarray(sp.spmm(A, b, c, 1.25, -0.5, backend="spmv",
                                   interpret=True))
        P = sp.plan(A, b.shape[1], backend="spmv", stream=True,
                    window_chunk=3, interpret=True)
        np.testing.assert_array_equal(np.asarray(P.run(b, c, 1.25, -0.5)),
                                      y_res)

    def test_rejects_bsr(self):
        rng = np.random.default_rng(0)
        B = sp.from_dense(rng.standard_normal((64, 96)).astype(np.float32),
                          format=sp.Format.BSR, block=(16, 16))
        with pytest.raises(ValueError):
            sp.spmm(B, rng.standard_normal((96, 4)).astype(np.float32),
                    backend="spmv")


class TestSpmvJnpTwin:
    def test_bit_identical_to_jnp(self):
        _, A, b, c = _packed()
        y_j = np.asarray(sp.spmm(A, b, c, 1.25, -0.5, backend="jnp"))
        y_v = np.asarray(sp.spmm(A, b, c, 1.25, -0.5, backend="spmv_jnp"))
        np.testing.assert_array_equal(y_v, y_j)

    def test_grads_match_dense_oracle(self):
        _, A, b_np, c_np = _packed(seed=2)
        b, c = jnp.asarray(b_np), jnp.asarray(c_np)

        def loss(v):
            return jnp.sum(jnp.sin(sp.spmm(A.with_values(v), b, c, 1.3, 0.7,
                                           backend="spmv_jnp")))

        def loss_dense(v):
            return jnp.sum(jnp.sin(1.3 * A.with_values(v).todense() @ b
                                   + 0.7 * c))

        g = jax.grad(loss)(A.values)
        gd = jax.grad(loss_dense)(A.values)
        valid = (np.arange(A.data.lw).reshape(A.data.vals.shape[-2:])
                 < np.asarray(A.data.nse)[:, :, None, None])
        np.testing.assert_allclose(np.asarray(g)[valid],
                                   np.asarray(gd)[valid],
                                   rtol=1e-4, atol=1e-4)


class TestAutoPolicyTable:
    """Pins the default ``auto`` dispatch table, N-awareness included."""

    def _A(self, density=0.05):
        m, k = 64, 128
        rng = np.random.default_rng(0)
        nnz = max(1, int(m * k * density))
        d = np.zeros((m, k), np.float32)
        d[rng.integers(0, m, nnz), rng.integers(0, k, nnz)] = 1.0
        return sp.from_dense(d, tm=32, k0=32, chunk=8)

    def _b(self, n):
        return np.zeros((128, n), np.float32)

    @pytest.mark.parametrize("platform,n,expect", [
        # skinny HFLEX: the vector lane, platform-split
        ("tpu", 1, "spmv"),
        ("tpu", sp.SKINNY_N_MAX, "spmv"),
        ("cpu", 1, "spmv_jnp"),
        ("cpu", sp.SKINNY_N_MAX, "spmv_jnp"),
        # one past the threshold: the old rules verbatim
        ("tpu", sp.SKINNY_N_MAX + 1, "pallas_onehot"),
        ("cpu", sp.SKINNY_N_MAX + 1, "jnp"),
        ("gpu", 64, "jnp"),
    ])
    def test_hflex_width_split(self, platform, n, expect):
        assert _default_auto_policy(self._A(), self._b(n),
                                    platform=platform) == expect

    def test_unknown_width_keeps_old_rules(self):
        A = self._A()
        assert _default_auto_policy(A, None, platform="tpu") == "pallas_onehot"
        assert _default_auto_policy(A, None, platform="cpu") == "jnp"

    def test_dense_ish_tpu_overrides_skinny(self):
        """On TPU the density>0.25 rule wins over the skinny lane (slab
        padding blows up either kernel); off-TPU the flat twin has no slab
        padding, so skinny still applies."""
        A = self._A(density=0.5)
        assert A.density > 0.25
        assert _default_auto_policy(A, self._b(4), platform="tpu") == "jnp"
        assert _default_auto_policy(A, self._b(4),
                                    platform="cpu") == "spmv_jnp"

    def test_bsr_never_takes_the_lane(self):
        rng = np.random.default_rng(0)
        B = sp.from_dense(rng.standard_normal((64, 96)).astype(np.float32),
                          format=sp.Format.BSR, block=(16, 16))
        assert _default_auto_policy(B, self._b(4), platform="tpu") == "pallas"
        assert _default_auto_policy(B, self._b(4), platform="cpu") == "jnp"

    def test_operand_width(self):
        assert _operand_width(np.zeros((128, 4))) == 4
        assert _operand_width(np.zeros(128)) == 1        # matvec path
        assert _operand_width(jax.ShapeDtypeStruct((128, 7),
                                                   jnp.float32)) == 7
        assert _operand_width(None) is None

    def test_resolve_backend_n_stub(self):
        """``resolve_backend(..., n=)`` synthesizes a shape stub so N-aware
        resolution works before the operand exists."""
        A = self._A()
        assert sp.resolve_backend("auto", A, n=4,
                                  platform="tpu") == "spmv"
        assert sp.resolve_backend("auto", A, n=4,
                                  platform="cpu") == "spmv_jnp"
        assert sp.resolve_backend("auto", A, n=64,
                                  platform="tpu") == "pallas_onehot"
        # no operand, no n: pre-operand resolution keeps the old rules
        assert sp.resolve_backend("auto", A,
                                  platform="tpu") == "pallas_onehot"


class TestSkinnyThresholdTunable:
    """The skinny-N routing boundary is live-tunable: a
    ``set_skinny_n_max`` override (what ``apply_skinny_from_db`` pushes)
    beats ``$SEXTANS_SKINNY_N_MAX`` beats the built-in 8."""

    def _A(self):
        m, k = 64, 128
        rng = np.random.default_rng(0)
        d = np.zeros((m, k), np.float32)
        nnz = max(1, int(m * k * 0.05))
        d[rng.integers(0, m, nnz), rng.integers(0, k, nnz)] = 1.0
        return sp.from_dense(d, tm=32, k0=32, chunk=8)

    def _b(self, n):
        return np.zeros((128, n), np.float32)

    @pytest.mark.parametrize("thr", [2, 12])
    def test_override_moves_the_boundary(self, thr):
        A = self._A()
        try:
            sp.set_skinny_n_max(thr)
            assert sp.skinny_n_max() == thr
            assert _default_auto_policy(A, self._b(thr),
                                        platform="cpu") == "spmv_jnp"
            assert _default_auto_policy(A, self._b(thr + 1),
                                        platform="cpu") == "jnp"
            assert _default_auto_policy(A, self._b(thr),
                                        platform="tpu") == "spmv"
            assert _default_auto_policy(A, self._b(thr + 1),
                                        platform="tpu") == "pallas_onehot"
        finally:
            sp.set_skinny_n_max(None)

    def test_zero_disables_the_lane(self):
        A = self._A()
        try:
            sp.set_skinny_n_max(0)
            assert _default_auto_policy(A, self._b(1),
                                        platform="cpu") == "jnp"
        finally:
            sp.set_skinny_n_max(None)

    def test_env_beats_default_override_beats_env(self, monkeypatch):
        monkeypatch.setenv("SEXTANS_SKINNY_N_MAX", "12")
        assert sp.skinny_n_max() == 12
        A = self._A()
        assert _default_auto_policy(A, self._b(12),
                                    platform="cpu") == "spmv_jnp"
        try:
            sp.set_skinny_n_max(3)
            assert sp.skinny_n_max() == 3       # override wins over env
            assert _default_auto_policy(A, self._b(12),
                                        platform="cpu") == "jnp"
        finally:
            sp.set_skinny_n_max(None)
        assert sp.skinny_n_max() == 12          # env chain restored

    def test_bad_env_falls_back_to_default(self, monkeypatch):
        monkeypatch.setenv("SEXTANS_SKINNY_N_MAX", "not-a-number")
        assert sp.skinny_n_max() == sp.SKINNY_N_MAX

    def test_plan_routing_follows_live_threshold(self):
        """``plan(backend="auto")`` consults the live threshold, so a
        DB-tuned value changes routing without re-imports."""
        _, A, _, _ = _packed()
        try:
            sp.set_skinny_n_max(2)
            assert sp.plan(A, 4).backend not in sp.SKINNY_BACKENDS
            sp.set_skinny_n_max(16)
            assert sp.plan(A, 16).backend in sp.SKINNY_BACKENDS
        finally:
            sp.set_skinny_n_max(None)


class TestSkinnyRouting:
    def test_plan_resolves_lane(self):
        _, A, _, _ = _packed()
        P = sp.plan(A, 4, backend="auto")
        assert P.backend in sp.SKINNY_BACKENDS
        P_tall = sp.plan(A, 64, backend="auto")
        assert P_tall.backend not in sp.SKINNY_BACKENDS

    def test_engine_counts_skinny_dispatches(self):
        from repro.core.engine import SextansEngine

        rng = np.random.default_rng(0)
        a = power_law_sparse(200, 300, 5, seed=0)
        eng = SextansEngine(tm=64, k0=64, chunk=8, impl="auto")
        t = eng.pack(a)
        y = eng.spmm(t, jnp.asarray(
            rng.standard_normal((300, 4)).astype(np.float32)))
        assert eng.stats.skinny_dispatches == 1
        eng.spmm(t, jnp.asarray(
            rng.standard_normal((300, 64)).astype(np.float32)))
        assert eng.stats.skinny_dispatches == 1      # tall call: not skinny
        assert np.isfinite(np.asarray(y)).all()

    def test_scheduler_pool_reports_skinny(self):
        from repro.core.engine import SextansEngine
        from repro.launch.serve import SpmmRequest, serve_spmm_requests

        rng = np.random.default_rng(0)
        reqs = [SpmmRequest(
            a=power_law_sparse(128, 160, 5, seed=i),
            b=rng.standard_normal((160, 4)).astype(np.float32))
            for i in range(4)]
        eng = SextansEngine(tm=64, k0=64, chunk=8, impl="auto")
        outs, stats = serve_spmm_requests(reqs, eng)
        assert stats["skinny_dispatches"] > 0
        for r, o in zip(reqs, outs):
            ref = spmm_reference(
                r.a, r.b, np.zeros((r.a.shape[0], r.b.shape[1]), np.float32))
            np.testing.assert_allclose(
                o, ref, rtol=2e-4, atol=2e-4 * max(1, np.abs(ref).max()))
