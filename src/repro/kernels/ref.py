"""Pure-jnp oracles for the SpMM kernels.

These are the ground truth every Pallas kernel is asserted against
(interpret mode, shape/dtype sweeps in tests/test_kernels.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["spmm_dense_ref", "spmm_coo_ref", "spmm_slabs_ref",
           "bsr_matmul_ref", "bsr_matmul_ref_batched", "bsr_dense_batched"]


def spmm_dense_ref(a_dense, b, c, alpha=1.0, beta=0.0):
    """C = alpha * A @ B + beta * C with fp32 accumulation."""
    acc = jnp.dot(
        a_dense.astype(jnp.float32),
        b.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return (alpha * acc + beta * c.astype(jnp.float32)).astype(b.dtype)


def spmm_coo_ref(row, col, val, b, c, m, alpha=1.0, beta=0.0):
    """COO SpMM via segment-sum (jax-native non-Pallas execution path)."""
    contrib = val[:, None].astype(jnp.float32) * b[col].astype(jnp.float32)
    acc = jax.ops.segment_sum(contrib, row, num_segments=m)
    return (alpha * acc + beta * c.astype(jnp.float32)).astype(b.dtype)


def spmm_slabs_ref(vals, cols, rows, q, b, c_in, k0, tm, alpha=1.0, beta=0.0):
    """Oracle on the *packed slab format* — computes exactly what the kernel
    must produce on its (possibly padded/permuted) operands.

    vals/cols/rows: (MB, NW, LW) or lane-major (MB, NW, R, L); q: (MB, NW);
    b: (NW*K0, N) padded; c_in: (MB*TM, N) padded (already block-permuted
    if interleaved).  Padding slots have val == 0 so they contribute
    nothing.
    """
    vals, cols, rows = (x.reshape(*x.shape[:2], -1)
                        for x in (vals, cols, rows))
    mb, nw, lw = vals.shape
    n = b.shape[1]

    def per_block(bi):
        def per_window(wi, acc):
            v = vals[bi, wi]                            # (LW,)
            c = cols[bi, wi] + wi * k0                  # global col
            r = rows[bi, wi]
            contrib = v[:, None].astype(jnp.float32) * b[c].astype(jnp.float32)
            return acc + jax.ops.segment_sum(contrib, r, num_segments=tm)

        acc0 = jnp.zeros((tm, n), jnp.float32)
        return jax.lax.fori_loop(0, nw, per_window, acc0)

    acc = jax.vmap(per_block)(jnp.arange(mb))           # (MB, TM, N)
    acc = acc.reshape(mb * tm, n)
    return (alpha * acc + beta * c_in.astype(jnp.float32)).astype(b.dtype)


def bsr_matmul_ref(x, blocks, block_row, block_col, nblk_rows, nblk_cols, alpha=1.0):
    """Block-sparse weight matmul oracle: y = alpha * x @ W.

    W is (K, F) = (nblk_rows*TK, nblk_cols*TF) with nonzero blocks
    ``blocks[i]`` at (block_row[i], block_col[i]).
    """
    nb, tk, tf = blocks.shape
    k, f = nblk_rows * tk, nblk_cols * tf
    w = jnp.zeros((nblk_rows, nblk_cols, tk, tf), jnp.float32)
    w = w.at[block_row, block_col].add(blocks.astype(jnp.float32))
    w = w.transpose(0, 2, 1, 3).reshape(k, f)
    y = jnp.dot(x.astype(jnp.float32), w, preferred_element_type=jnp.float32)
    return (alpha * y).astype(x.dtype)


def bsr_matmul_ref_batched(x, blocks, block_row, block_col,
                           nblk_rows, nblk_cols, alpha=1.0):
    """Batched oracle over a stacked BSR group: y[g] = alpha * x[g] @ W[g].

    The group axis folds into the scatter's leading index and the dense
    contraction's batch dimension, so each member sees exactly the op
    sequence of :func:`bsr_matmul_ref` — results are bit-identical
    member-wise.  Out-of-range ``block_col`` entries (the zero padding
    slots of a stacked group) are dropped by the scatter.
    """
    w = bsr_dense_batched(blocks, block_row, block_col, nblk_rows,
                          nblk_cols)
    y = jax.lax.dot_general(
        x.astype(jnp.float32), w,
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    return (alpha * y).astype(x.dtype)


def bsr_dense_batched(blocks, block_row, block_col, nblk_rows, nblk_cols):
    """The dense ``(G, K, F)`` float32 weights of a stacked BSR group;
    out-of-range ``block_col`` entries (padding slots) are dropped."""
    g, nb, tk, tf = blocks.shape
    gi = jnp.arange(g, dtype=jnp.int32)[:, None]
    w = jnp.zeros((g, nblk_rows, nblk_cols, tk, tf), jnp.float32)
    w = w.at[gi, block_row, block_col].add(blocks.astype(jnp.float32))
    return w.transpose(0, 1, 3, 2, 4).reshape(g, nblk_rows * tk,
                                               nblk_cols * tf)
