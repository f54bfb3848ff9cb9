"""Quickstart: general-purpose SpMM through the unified sparse front-end.

One ``SparseTensor`` + one ``spmm`` serves every packed format and backend
(the API analogue of the paper's one-accelerator-serves-any-SpMM claim):

* ``C = alpha * A @ B + beta * C`` with *traced* alpha/beta — sweeping the
  epilogue reuses one compiled executable (HFlex);
* ``A @ b`` operator sugar;
* differentiable end-to-end (``jax.grad`` reaches B, C and the packed
  non-zero values).

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np
import jax
import jax.numpy as jnp

import repro.sparse_api as sp
from repro.core.sparse import power_law_sparse, spmm_reference


def main():
    rng = np.random.default_rng(0)

    # A: a 1000x800 power-law (social-network-like) sparse matrix
    a = power_law_sparse(1000, 800, avg_nnz_per_row=6, seed=42)
    print(f"A: {a.shape}, nnz={a.nnz}, density={a.density:.4f}")

    n = 64
    b = rng.standard_normal((800, n)).astype(np.float32)
    c = rng.standard_normal((1000, n)).astype(np.float32)
    alpha, beta = 1.0, 0.5

    # Pack once; the Format/backend split is orthogonal: the same tensor
    # runs on "pallas" (the format's chip kernel), "jnp" (the XLA
    # reference), or "auto" dispatch.
    A = sp.from_sparse_matrix(a, tm=128, k0=256, chunk=8)
    print(f"packed: {A.format} geometry={A.geometry} "
          f"(padding handled by Q pointers — HFlex)")
    print(f"registered backends: {sp.list_backends()}")

    out = sp.spmm(A, b, c, alpha, beta, backend="pallas")
    ref = spmm_reference(a, b, c, alpha, beta)
    err = np.abs(np.asarray(out) - ref).max() / (np.abs(ref).max() + 1e-9)
    print(f"max relative error vs oracle: {err:.2e}")
    assert err < 1e-4

    # Operator sugar + autodiff: gradients reach the packed non-zeros.
    y = A @ b
    grad_vals = jax.grad(
        lambda v: jnp.sum(sp.spmm(A.with_values(v), jnp.asarray(b)) ** 2)
    )(A.values)
    print(f"A @ b -> {y.shape}; d(loss)/d(vals) -> {grad_vals.shape}")

    # Epilogue sweeps hit ONE executable: alpha/beta are traced scalars.
    sp.BACKEND_STATS["traces"] = 0
    for alpha_i in (0.1, 0.5, 1.0, 2.0, 4.0):
        sp.spmm(A, b, c, alpha_i, 1.0 - alpha_i, backend="pallas")
    print(f"5-point alpha/beta sweep -> {sp.BACKEND_STATS['traces']} new traces")
    print("OK")


if __name__ == "__main__":
    main()
