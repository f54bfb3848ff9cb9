"""Pallas kernel validation: shape/dtype sweeps against the float64
oracle (interpret mode), in the single and batched launch modes, plus BSR
and property tests."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.sparse import (
    banded_sparse, power_law_sparse, random_sparse, spmm_reference,
)
import repro.sparse_api as sp


def _spmm(packed, b, c=None, alpha=1.0, beta=0.0, *, impl, tn):
    opts = {"tn": tn} if impl != "jnp" else {}
    return sp.spmm(packed, jnp.asarray(b),
                   None if c is None else jnp.asarray(c), alpha, beta,
                   backend=impl, **opts)


def _check(a, b, c, alpha, beta, tm, k0, tn, impl, interleave=True, atol=2e-4):
    ref = spmm_reference(a, b, c, alpha, beta)
    packed = sp.from_sparse_matrix(a, tm=tm, k0=k0, chunk=8,
                                   interleave=interleave, bucket=False)
    out = _spmm(packed, b, c, alpha, beta, impl=impl, tn=tn)
    np.testing.assert_allclose(np.asarray(out), ref,
                               rtol=2e-4, atol=atol * max(1, np.abs(ref).max()))


def _bsr_matmul(x, w, impl):
    """``x @ W`` for a packed (K, F) BSR weight, through ``spmm`` on the
    transposed view ``(W^T @ x^T)^T``; leading axes of ``x`` pass
    through."""
    a = sp.from_bsr_weight(w)                     # W^T, shape (F, K)
    lead = x.shape[:-1]
    y = sp.spmm(a, jnp.asarray(x).reshape(-1, w.k).T, backend=impl).T
    return y.reshape(*lead, w.f)


SHAPE_SWEEP = [
    # (M, K, N, density, tm, k0, tn)
    (64, 64, 8, 0.3, 32, 32, 8),
    (128, 128, 16, 0.1, 128, 128, 16),
    (200, 300, 40, 0.05, 64, 128, 32),
    (513, 257, 17, 0.02, 128, 64, 128),
    (33, 1000, 100, 0.01, 32, 256, 64),
    (1000, 33, 7, 0.2, 128, 32, 8),
]


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
@pytest.mark.parametrize("m,k,n,d,tm,k0,tn", SHAPE_SWEEP)
def test_shape_sweep(impl, m, k, n, d, tm, k0, tn, rng):
    a = random_sparse(m, k, d, seed=m + k)
    b = rng.standard_normal((k, n)).astype(np.float32)
    c = rng.standard_normal((m, n)).astype(np.float32)
    _check(a, b, c, 1.25, -0.5, tm, k0, tn, impl)


@pytest.mark.parametrize("m,k,n,d,tm,k0,tn", SHAPE_SWEEP)
def test_shape_sweep_batched(m, k, n, d, tm, k0, tn, rng):
    """The kernel's batched launch mode at the sweep's geometries: the
    sweep matrix and a second seeded one, stacked (LW widened to the
    pair's maximum), each member checked against its float64 reference
    with its own epilogue."""
    mats = [random_sparse(m, k, d, seed=m + k + s) for s in (0, 1)]
    ts = [sp.from_sparse_matrix(a, tm=tm, k0=k0, chunk=8) for a in mats]
    lw = max(t.data.lw for t in ts)
    S = sp.stack_hflex([sp.repad_lw(t, lw) if t.data.lw < lw else t
                        for t in ts])
    b = rng.standard_normal((2, k, n)).astype(np.float32)
    c = rng.standard_normal((2, m, n)).astype(np.float32)
    alpha, beta = np.float32([1.25, -0.75]), np.float32([-0.5, 2.0])
    out = np.asarray(_spmm(S, b, c, alpha, beta, impl="pallas", tn=tn))
    for i, a in enumerate(mats):
        ref = spmm_reference(a, b[i], c[i], alpha[i], beta[i])
        np.testing.assert_allclose(out[i], ref, rtol=2e-4,
                                   atol=2e-4 * max(1, np.abs(ref).max()))


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
@pytest.mark.parametrize("alpha,beta", [(1.0, 0.0), (1.0, 1.0), (0.0, 2.0),
                                        (-1.5, 0.25)])
def test_alpha_beta_epilogue(impl, alpha, beta, rng):
    """The general C = αAB + βC epilogue of the paper (not just AB)."""
    a = random_sparse(100, 80, 0.1, seed=1)
    b = rng.standard_normal((80, 24)).astype(np.float32)
    c = rng.standard_normal((100, 24)).astype(np.float32)
    _check(a, b, c, alpha, beta, 64, 64, 8, impl)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_b_dtypes(dtype, rng):
    a = random_sparse(96, 96, 0.1, seed=7)
    b = jnp.asarray(rng.standard_normal((96, 16)), dtype)
    c = jnp.zeros((96, 16), dtype)
    packed = sp.from_sparse_matrix(a, tm=32, k0=32, chunk=8, bucket=False)
    out = _spmm(packed, b, c, impl="pallas", tn=16)
    ref = spmm_reference(a, np.asarray(b, np.float32),
                         np.zeros((96, 16), np.float32))
    tol = 5e-2 if dtype == jnp.bfloat16 else 5e-5
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               rtol=tol, atol=tol * np.abs(ref).max())


@pytest.mark.parametrize("gen,args", [
    (power_law_sparse, (256, 256, 6)),
    (banded_sparse, (200, 200, 5)),
])
def test_matrix_families(gen, args, rng):
    a = gen(*args, seed=3)
    m, k = a.shape
    b = rng.standard_normal((k, 32)).astype(np.float32)
    c = rng.standard_normal((m, 32)).astype(np.float32)
    _check(a, b, c, 1.0, 1.0, 64, 64, 32, "pallas")


def test_empty_window_and_empty_rows(rng):
    """Matrices with fully-empty K windows (Q has zero-length segments)."""
    m, k = 64, 256
    row = np.array([0, 1, 63], np.int32)
    col = np.array([0, 1, 255], np.int32)   # middle windows empty
    val = np.array([1.0, 2.0, 3.0], np.float32)
    from repro.core.sparse import SparseMatrix
    a = SparseMatrix((m, k), row, col, val).sorted_column_major()
    b = rng.standard_normal((k, 8)).astype(np.float32)
    c = np.zeros((m, 8), np.float32)
    _check(a, b, c, 1.0, 0.0, 32, 64, 8, "pallas")


def test_chunk_sizes(rng):
    """CHUNK is the PU-lane analogue; sweep it."""
    a = random_sparse(128, 128, 0.08, seed=9)
    b = rng.standard_normal((128, 16)).astype(np.float32)
    ref = spmm_reference(a, b, np.zeros((128, 16), np.float32))
    for chunk in (8, 16, 32, 128):
        packed = sp.from_sparse_matrix(a, tm=64, k0=64, chunk=chunk,
                                       bucket=False)
        out = _spmm(packed, b, impl="pallas", tn=16)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4,
                                   atol=2e-4 * np.abs(ref).max())


@settings(max_examples=25, deadline=None)
@given(m=st.integers(8, 150), k=st.integers(8, 150),
       n=st.integers(1, 40), dens=st.floats(0.01, 0.4),
       seed=st.integers(0, 10_000))
def test_property_pallas_matches_oracle(m, k, n, dens, seed):
    rng = np.random.default_rng(seed)
    a = random_sparse(m, k, dens, seed)
    b = rng.standard_normal((k, n)).astype(np.float32)
    c = rng.standard_normal((m, n)).astype(np.float32)
    _check(a, b, c, 1.0, 1.0, 32, 32, 8, "pallas")


class TestBsr:
    def test_against_dense(self, rng):
        k, f = 256, 384
        w = rng.standard_normal((k, f)).astype(np.float32)
        mask = rng.random((k // 128, f // 128)) < 0.5
        w = (w.reshape(k // 128, 128, f // 128, 128)
             * mask[:, None, :, None]).reshape(k, f)
        bw = sp.pack_bsr_weight(w, 128, 128)
        x = rng.standard_normal((100, k)).astype(np.float32)
        for impl in ("pallas", "jnp"):
            y = _bsr_matmul(x, bw, impl)
            np.testing.assert_allclose(np.asarray(y), x @ w, rtol=2e-4,
                                       atol=1e-3)

    def test_all_blocks_dropped_row(self, rng):
        k, f = 128, 256
        w = np.zeros((k, f), np.float32)
        w[:, :128] = rng.standard_normal((k, 128))
        bw = sp.pack_bsr_weight(w, 128, 128)
        assert bw.blocks.shape[0] == 1
        x = rng.standard_normal((32, k)).astype(np.float32)
        y = _bsr_matmul(x, bw, "pallas")
        np.testing.assert_allclose(np.asarray(y), x @ w, rtol=2e-4, atol=1e-3)

    def test_batch_leading_dims(self, rng):
        k, f = 128, 128
        w = rng.standard_normal((k, f)).astype(np.float32)
        bw = sp.pack_bsr_weight(w, 128, 128)
        x = rng.standard_normal((2, 5, k)).astype(np.float32)
        y = _bsr_matmul(x, bw, "pallas")
        assert y.shape == (2, 5, f)
        np.testing.assert_allclose(np.asarray(y), x @ w, rtol=2e-4, atol=1e-3)
