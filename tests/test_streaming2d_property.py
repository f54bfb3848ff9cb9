"""Property tests for the 2-D (K-window x N-tile) streaming grid: EVERY
(window_chunk x n_tile x backend x epilogue) combination is bit-identical
to single-shot ``spmm``, and tiled gradients match the dense oracle.

Column tiling never reassociates a column's add sequence (per-column math
is independent), and the K decomposition carries the raw f32 accumulator —
so the invariant stays ``np.array_equal``, not allclose, across BOTH grid
dimensions at once.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

import repro.sparse_api as sp
from repro.core.sparse import power_law_sparse

_CACHE = {}


def _fixture(seed):
    if seed not in _CACHE:
        rng = np.random.default_rng(seed)
        a = power_law_sparse(220, 512, 6, seed=seed)
        A = sp.from_sparse_matrix(a, tm=64, k0=64, chunk=8, bucket=True)
        b = rng.standard_normal((512, 8)).astype(np.float32)
        c = rng.standard_normal((220, 8)).astype(np.float32)
        _CACHE[seed] = (A, b, c)
    return _CACHE[seed]


# NW is 8 for the fixture geometry (512 cols / K0=64) and N is 8, so both
# grid dimensions sweep their full range, tail tiles included (n_tile in
# {3, 5, 7} leaves a ragged final stripe).
@settings(max_examples=24, deadline=None)
@given(
    wc=st.integers(min_value=1, max_value=8),
    nt=st.integers(min_value=1, max_value=8),
    seed=st.integers(min_value=0, max_value=2),
    alpha=st.sampled_from([1.0, 0.5, -2.0, 1.25]),
    beta=st.sampled_from([0.0, 1.0, -0.5]),
    backend=st.sampled_from(["jnp", "pallas"]),
)
def test_2d_grid_bit_identical(wc, nt, seed, alpha, beta, backend):
    A, b, c = _fixture(seed)
    assert A.num_windows == 8
    opts = {} if backend == "jnp" else dict(tn=8, interpret=True)
    y_ref = np.asarray(sp.spmm(A, b, c, alpha, beta, backend=backend,
                               **opts))
    # differentiable streaming entry, both loop dimensions forced
    y_s = np.asarray(sp.spmm_streaming(A, b, c, alpha, beta,
                                       window_chunk=wc, n_tile=nt,
                                       backend=backend, **opts))
    np.testing.assert_array_equal(y_s, y_ref)
    # AOT streaming plan (host-staged 2-D grid, donated accumulator)
    P = sp.plan(A, 8, backend=backend, stream=True, window_chunk=wc,
                n_tile=nt, **opts)
    np.testing.assert_array_equal(np.asarray(P.run(b, c, alpha, beta)),
                                  y_ref)


@settings(max_examples=8, deadline=None)
@given(
    wc=st.integers(min_value=1, max_value=8),
    nt=st.integers(min_value=1, max_value=8),
    seed=st.integers(min_value=0, max_value=2),
)
def test_tiled_gradients_match_dense_oracle(wc, nt, seed):
    A, b, c = _fixture(seed)
    bj, cj = jnp.asarray(b), jnp.asarray(c)

    def loss_stream(v, b_, c_):
        return jnp.sum(sp.spmm_streaming(A.with_values(v), b_, c_, 1.3, 0.7,
                                         window_chunk=wc, n_tile=nt,
                                         backend="jnp") ** 2)

    def loss_dense(v, b_, c_):
        return jnp.sum((1.3 * A.with_values(v).todense() @ b_
                        + 0.7 * c_) ** 2)

    g_s = jax.grad(loss_stream, argnums=(0, 1, 2))(A.values, bj, cj)
    g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(A.values, bj, cj)
    valid = (np.arange(A.data.lw).reshape(A.data.vals.shape[-2:])
             < np.asarray(A.data.nse)[:, :, None, None])
    np.testing.assert_allclose(np.asarray(g_s[0])[valid],
                               np.asarray(g_d[0])[valid],
                               rtol=1e-4, atol=1e-4, err_msg="vals")
    for name, x, y in zip(("b", "c"), g_s[1:], g_d[1:]):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-4, atol=1e-4, err_msg=name)
