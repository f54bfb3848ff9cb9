"""Unified sparse front-end tests: SparseTensor, spmm, autodiff, registry.

Covers the api_redesign acceptance criteria:
* HFlex-slab and BSR formats through one spmm/__matmul__ entry point;
* registered pytree surviving jax.jit boundaries;
* jax.grad through spmm (w.r.t. b, c, vals, alpha, beta) matching the
  dense oracle to 1e-4;
* backend-registry dispatch (auto + explicit + custom);
* legacy shim parity.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.sparse_api as sp
from repro.core.sparse import random_sparse, spmm_reference, to_dense


def _tensor(m=60, k=70, density=0.08, seed=1, tm=32, k0=32):
    a = random_sparse(m, k, density, seed=seed)
    return a, sp.from_sparse_matrix(a, tm=tm, k0=k0, chunk=8, bucket=False)


class TestSparseTensor:
    def test_forward_all_backends(self, rng):
        a, A = _tensor()
        b = rng.standard_normal((70, 16)).astype(np.float32)
        c = rng.standard_normal((60, 16)).astype(np.float32)
        ref = spmm_reference(a, b, c, 1.25, -0.5)
        for backend in ("pallas", "jnp"):
            opts = {"tn": 16} if backend != "jnp" else {}
            out = sp.spmm(A, b, c, 1.25, -0.5, backend=backend, **opts)
            np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4,
                                       atol=2e-4 * np.abs(ref).max())

    def test_matmul_operator_parity(self, rng):
        _, A = _tensor()
        b = rng.standard_normal((70, 8)).astype(np.float32)
        np.testing.assert_array_equal(
            np.asarray(A @ b), np.asarray(sp.spmm(A, b)))
        # 1-D operand
        v = rng.standard_normal(70).astype(np.float32)
        got = np.asarray(A @ v)
        assert got.shape == (60,)
        np.testing.assert_allclose(got, np.asarray(sp.spmm(A, v[:, None]))[:, 0])

    def test_todense_roundtrip(self):
        a, A = _tensor()
        np.testing.assert_allclose(np.asarray(A.todense()), to_dense(a),
                                   atol=1e-7)

    def test_pytree_survives_jit(self, rng):
        _, A = _tensor()
        b = jnp.asarray(rng.standard_normal((70, 8)), jnp.float32)

        @jax.jit
        def f(t, b_):
            return sp.spmm_raw("jnp", t, b_,
                               jnp.zeros((60, 8), jnp.float32), 1.0, 0.0)

        np.testing.assert_allclose(np.asarray(f(A, b)), np.asarray(A @ b),
                                   atol=1e-6)
        leaves, treedef = jax.tree.flatten(A)
        assert jax.tree.unflatten(treedef, leaves).shape == A.shape

    def test_bsr_format_one_entry_point(self, rng):
        w = rng.standard_normal((40, 48)).astype(np.float32)
        A = sp.from_dense(w, format=sp.Format.BSR, block=(16, 16))
        assert A.format is sp.Format.BSR and A.shape == (40, 48)
        b = rng.standard_normal((48, 8)).astype(np.float32)
        c = rng.standard_normal((40, 8)).astype(np.float32)
        ref = 1.5 * (w @ b) - 0.5 * c
        for backend in ("jnp", "pallas"):
            opts = {"tn": 8} if backend == "pallas" else {}
            out = sp.spmm(A, b, c, 1.5, -0.5, backend=backend, **opts)
            np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4,
                                       atol=2e-4 * np.abs(ref).max())
        np.testing.assert_allclose(np.asarray(A.todense()), w, atol=1e-7)

    def test_bsr_nonmultiple_shape_padded(self, rng):
        w = rng.standard_normal((30, 35)).astype(np.float32)
        A = sp.from_dense(w, format=sp.Format.BSR, block=(16, 16))
        b = rng.standard_normal((35, 4)).astype(np.float32)
        np.testing.assert_allclose(np.asarray(A @ b), w @ b, rtol=2e-4,
                                   atol=1e-4)


class TestAutodiff:
    @pytest.mark.parametrize("backend", ["pallas", "jnp"])
    def test_grad_matches_dense_oracle(self, rng, backend):
        """d loss/d {vals, b, c, alpha, beta} vs jax.grad on the dense
        compute — including beta != 0."""
        _, A = _tensor()
        b = jnp.asarray(rng.standard_normal((70, 8)), jnp.float32)
        c = jnp.asarray(rng.standard_normal((60, 8)), jnp.float32)
        opts = {"tn": 8} if backend != "jnp" else {}

        def loss(vals, b_, c_, al, be):
            out = sp.spmm(A.with_values(vals), b_, c_, al, be,
                          backend=backend, **opts)
            return jnp.sum(jnp.sin(out))

        def loss_dense(vals, b_, c_, al, be):
            dense = A.with_values(vals).todense()
            return jnp.sum(jnp.sin(al * dense @ b_ + be * c_))

        args = (A.values, b, c, jnp.float32(1.3), jnp.float32(0.7))
        g = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2, 3, 4))(*args)
        # vals: compare on real slots only — the dense oracle also has
        # partials w.r.t. structural padding slots, which spmm (correctly)
        # pins to zero; that is asserted separately below.
        valid = (np.arange(A.data.lw).reshape(A.data.vals.shape[-2:])
                 < np.asarray(A.data.nse)[:, :, None, None])
        np.testing.assert_allclose(np.asarray(g[0])[valid],
                                   np.asarray(gd[0])[valid],
                                   rtol=1e-4, atol=1e-4, err_msg="vals")
        assert np.all(np.asarray(g[0])[~valid] == 0.0)
        for name, x, y in zip(("b", "c", "alpha", "beta"), g[1:], gd[1:]):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-4, atol=1e-4, err_msg=name)

    def test_training_step_preserves_padding(self, rng):
        """One SGD step on A.values must not leak mass into padding slots:
        the forward after the update still matches the dense oracle."""
        a, A = _tensor()
        b = jnp.asarray(rng.standard_normal((70, 8)), jnp.float32)
        g = jax.grad(lambda v: jnp.sum(
            sp.spmm(A.with_values(v), b, backend="jnp") ** 2))(A.values)
        v2 = A.values - 0.01 * g
        A2 = A.with_values(v2)
        np.testing.assert_allclose(
            np.asarray(sp.spmm(A2, b, backend="jnp")),
            np.asarray(A2.todense() @ b), rtol=1e-4, atol=1e-4)
        valid = (np.arange(A.data.lw).reshape(A.data.vals.shape[-2:])
                 < np.asarray(A.data.nse)[:, :, None, None])
        assert np.all(np.asarray(v2)[~valid] == 0.0)

    def test_grad_through_bsr(self, rng):
        w = rng.standard_normal((32, 48)).astype(np.float32)
        A = sp.from_dense(w, format=sp.Format.BSR, block=(16, 16))
        b = jnp.asarray(rng.standard_normal((48, 4)), jnp.float32)

        g = jax.grad(lambda v: jnp.sum(
            sp.spmm(A.with_values(v), b, backend="jnp") ** 2))(A.values)
        gd = jax.grad(lambda v: jnp.sum(
            (A.with_values(v).todense() @ b) ** 2))(A.values)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gd),
                                   rtol=1e-4, atol=1e-4)

    def test_grad_through_matmul_sugar(self, rng):
        a, A = _tensor()
        b = jnp.asarray(rng.standard_normal((70, 8)), jnp.float32)
        g = jax.grad(lambda b_: jnp.sum((A @ b_) ** 2))(b)
        dense = jnp.asarray(to_dense(a))
        gd = jax.grad(lambda b_: jnp.sum((dense @ b_) ** 2))(b)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gd),
                                   rtol=1e-4, atol=1e-4)


class TestBackendRegistry:
    def test_explicit_dispatch_and_validation(self):
        from repro.sparse_api import backends as _bk

        _, A = _tensor()
        assert sp.resolve_backend("jnp", A) == "jnp"
        # one chip kernel per format, and its XLA reference
        assert sp.list_backends() == ["jnp", "pallas"]
        with pytest.raises(KeyError):
            sp.get_backend("no_such_backend")
        w = np.ones((16, 16), np.float32)
        B = sp.from_dense(w, format=sp.Format.BSR, block=(16, 16))
        sp.register_backend("test_hflex_only", _bk._backend_jnp,
                            formats=(sp.Format.HFLEX,), overwrite=True)
        try:
            assert sp.resolve_backend("test_hflex_only", A) == (
                "test_hflex_only")
            with pytest.raises(ValueError):       # HFLEX-only backend
                sp.resolve_backend("test_hflex_only", B)
        finally:
            _bk._REGISTRY.pop("test_hflex_only")

    def test_auto_policy(self):
        _, A = _tensor()                           # density 0.08
        assert sp.resolve_backend("auto", A, platform="cpu") == "jnp"
        assert sp.resolve_backend("auto", A, platform="tpu") == "pallas"
        a_dense, = (random_sparse(32, 32, 0.5, seed=0),)
        D = sp.from_sparse_matrix(a_dense, tm=32, k0=32, bucket=False)
        assert sp.resolve_backend("auto", D, platform="tpu") == "jnp"
        w = np.ones((16, 16), np.float32)
        B = sp.from_dense(w, format=sp.Format.BSR, block=(16, 16))
        assert sp.resolve_backend("auto", B, platform="tpu") == "pallas"

    def test_custom_backend_registration(self, rng):
        calls = []

        def fake_backend(a, b, c, alpha, beta, **opts):
            calls.append(a.format)
            return (alpha * a.todense() @ b
                    + beta * c.astype(jnp.float32)).astype(b.dtype)

        from repro.sparse_api import backends as _bk

        sp.register_backend("test_dense", fake_backend, overwrite=True)
        try:
            a, A = _tensor()
            b = rng.standard_normal((70, 8)).astype(np.float32)
            out = sp.spmm(A, b, backend="test_dense")
            ref = spmm_reference(a, b, np.zeros((60, 8), np.float32))
            np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4,
                                       atol=1e-5)
            assert calls == [sp.Format.HFLEX]
            with pytest.raises(ValueError):        # no silent clobbering
                sp.register_backend("test_dense", fake_backend)
        finally:
            _bk._REGISTRY.pop("test_dense")

    def test_auto_policy_override(self):
        _, A = _tensor()
        try:
            sp.set_auto_policy(lambda a, b, platform=None: "jnp")
            assert sp.resolve_backend("auto", A, platform="tpu") == "jnp"
        finally:
            sp.set_auto_policy(None)


class TestSparseLinear:
    def test_trains(self, rng):
        from repro.models.common import Initializer
        from repro.models.layers import SparseLinear

        init = Initializer(seed=0, dtype=jnp.float32)
        layer, params = SparseLinear.create(init, 32, 48, block=(16, 16),
                                            density=0.5)
        assert 0.3 < layer.density <= 0.75
        x = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
        y_t = jnp.asarray(rng.standard_normal((16, 48)), jnp.float32)

        def loss_fn(p):
            return jnp.mean((layer(p, x, backend="jnp") - y_t) ** 2)

        grad_fn = jax.jit(jax.value_and_grad(loss_fn))
        l0, _ = grad_fn(params)
        for _ in range(25):
            l, g = grad_fn(params)
            params = jax.tree.map(lambda p, gg: p - 0.5 * gg, params, g)
        l1, _ = grad_fn(params)
        assert float(l1) < 0.9 * float(l0)
