"""Block-sparse weight matmul (BSR) Pallas kernel — beyond-paper extension.

The Sextans dataflow targets *unstructured* sparsity (scientific/graph
matrices). For pruned **model weights** on TPU, the MXU strongly prefers
block-structured sparsity: we keep the paper's two signature mechanisms —
the HFlex pointer list (here: per-output-tile block pointers, scalar
prefetched) and the streaming window with a resident accumulator — but the
unit of sparsity becomes a (TK × TF) tile that feeds the MXU densely.

y[bm, f_tile] = Σ_{i ∈ Q[f_tile]} x[bm, brow(i)] @ W_block(i)

Layout: blocks sorted by block-column (output tile); ``indptr`` (NF+1) is
the CSR-style pointer list over output tiles; ``brow`` gives each block's
K-tile. Grid: (BM tiles, NF tiles, J block slots).  Slot ``j`` of output
tile ``f`` is block ``indptr[f] + j``: the index maps read ``indptr`` and
``brow`` from scalar prefetch, so each grid step streams exactly one
``(TB, TK)`` tile of ``x`` and one ``(TK, TF)`` weight block HBM→VMEM
into a resident f32 accumulator.  Slots past a tile's block count repeat
the last block index (no new DMA) and skip the matmul.  One compiled
kernel serves any sparsity pattern of the same bucketed geometry (HFlex).

The *ragged* mode (:func:`bsr_matmul_pallas_ragged`, Pallas name
``bsr_spmm_ragged``) serves a dropless mixture of experts: the rows of
``x`` are token-expert pairs sorted by expert into 128-row-aligned
segments of one static buffer, and each row tile multiplies by the
weight of the expert that a scalar-prefetched table names for it.  Tiles
past the used count repeat the last used tile's blocks (no DMA), skip the
matmul and store zeros.

On TPU the blocks are lane tiles: ``TK`` and ``TF`` must be multiples of
128 (``repro.sparse_api.plan`` refuses other BSR tilings there).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._compat import resolve_interpret as _resolve_interpret

__all__ = ["bsr_matmul_pallas", "bsr_matmul_pallas_batched",
           "bsr_matmul_pallas_ragged"]


def _kernel(indptr_ref, brow_ref, x_ref, w_ref, o_ref, acc_ref, *,
            batched: bool):
    # refs: x ([1,] TB, TK), w ([1,] 1, TK, TF), o ([1,] TB, TF)
    off = 1 if batched else 0
    f = pl.program_id(1 + off)
    j = pl.program_id(2 + off)
    if batched:
        g = pl.program_id(0)
        count = indptr_ref[g, f + 1] - indptr_ref[g, f]
    else:
        count = indptr_ref[f + 1] - indptr_ref[f]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < count)
    def _accumulate():
        x = (x_ref[0] if batched else x_ref[...]).astype(jnp.float32)
        w = (w_ref[0, 0] if batched else w_ref[0]).astype(jnp.float32)
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)

    @pl.when(j == pl.num_programs(2 + off) - 1)
    def _store():
        res = acc_ref[...].astype(o_ref.dtype)
        if batched:
            o_ref[0] = res
        else:
            o_ref[...] = res


def _slot(ip, f, j, nb: int):
    """Block index of slot ``j`` of output tile ``f``: ``indptr[f] + j``,
    held at the tile's last block once ``j`` passes its count (so the
    pipeline issues no new DMA) and clamped into ``[0, NB)`` for empty
    tiles."""
    start, stop = ip(f), ip(f + 1)
    return jnp.clip(jnp.minimum(start + j, stop - 1), 0, nb - 1)


def _call(x, blocks, brow, indptr, *, tb, tk, tf, interpret, batched):
    interpret = _resolve_interpret(interpret)
    bsz, k = x.shape[-2:]
    nb = blocks.shape[-3]
    nf = indptr.shape[-1] - 1
    assert bsz % tb == 0 and k % tk == 0
    assert blocks.shape[-2:] == (tk, tf)
    # slots per output tile: a tile holds at most min(NB, K/TK) blocks
    nj = max(1, min(nb, k // tk))
    if batched:
        g_sz = x.shape[0]
        assert blocks.shape[0] == g_sz
        grid = (g_sz, bsz // tb, nf, nj)

        def x_map(g, b, f, j, ip, br):
            return (g, b, br[g, _slot(lambda i: ip[g, i], f, j, nb)])

        def w_map(g, b, f, j, ip, br):
            return (g, _slot(lambda i: ip[g, i], f, j, nb), 0, 0)

        in_specs = [pl.BlockSpec((1, tb, tk), x_map),
                    pl.BlockSpec((1, 1, tk, tf), w_map)]
        out_specs = pl.BlockSpec((1, tb, tf), lambda g, b, f, j, ip, br:
                                 (g, b, f))
        out_shape = jax.ShapeDtypeStruct((g_sz, bsz, nf * tf), x.dtype)
        semantics = ("parallel", "parallel", "parallel", "arbitrary")
    else:
        grid = (bsz // tb, nf, nj)

        def x_map(b, f, j, ip, br):
            return (b, br[_slot(lambda i: ip[i], f, j, nb)])

        def w_map(b, f, j, ip, br):
            return (_slot(lambda i: ip[i], f, j, nb), 0, 0)

        in_specs = [pl.BlockSpec((tb, tk), x_map),
                    pl.BlockSpec((1, tk, tf), w_map)]
        out_specs = pl.BlockSpec((tb, tf), lambda b, f, j, ip, br: (b, f))
        out_shape = jax.ShapeDtypeStruct((bsz, nf * tf), x.dtype)
        semantics = ("parallel", "parallel", "arbitrary")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((tb, tf), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, batched=batched),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        name="bsr_spmm",
    )(indptr, brow, x, blocks)


@functools.partial(
    jax.jit, static_argnames=("tb", "tk", "tf", "interpret")
)
def bsr_matmul_pallas(
    x: jax.Array,         # (B, K)
    blocks: jax.Array,    # (NB, TK, TF), sorted by block-col
    brow: jax.Array,      # (NB,) i32
    indptr: jax.Array,    # (NF+1,) i32 pointers into blocks per out tile
    *,
    tb: int = 128,
    tk: int = 128,
    tf: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """y = x @ W for block-sparse W. x padded to (B % tb == 0, K % tk == 0);
    output (B, NF*tf). ``interpret=None`` interprets only off-TPU."""
    return _call(x, blocks, brow, indptr, tb=tb, tk=tk, tf=tf,
                 interpret=interpret, batched=False)


@functools.partial(
    jax.jit, static_argnames=("tb", "tk", "tf", "interpret")
)
def bsr_matmul_pallas_batched(
    x: jax.Array,         # (G, B, K)
    blocks: jax.Array,    # (G, NB, TK, TF), per member sorted by block-col
    brow: jax.Array,      # (G, NB) i32
    indptr: jax.Array,    # (G, NF+1) i32 pointers into blocks per out tile
    *,
    tb: int = 128,
    tk: int = 128,
    tf: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Batched ``y[g] = x[g] @ W[g]`` over a stacked BSR group — ONE kernel
    launch for the whole group (leading batch grid dimension, leading-1
    block specs).  Member ``g`` truly stores ``indptr[g, -1] <= NB``
    blocks; the pointer walk never reaches the zero padding, so each
    member's result is bit-identical to :func:`bsr_matmul_pallas` on its
    own payload.  Output ``(G, B, NF*tf)``."""
    return _call(x, blocks, brow, indptr, tb=tb, tk=tk, tf=tf,
                 interpret=interpret, batched=True)


def _ragged_kernel(indptr_ref, brow_ref, te_ref, used_ref, x_ref, w_ref,
                   o_ref, acc_ref):
    # refs: x (TB, TK), w (1, 1, TK, TF), o (TB, TF)
    b = pl.program_id(0)
    f = pl.program_id(1)
    j = pl.program_id(2)
    e = te_ref[b]
    count = indptr_ref[e, f + 1] - indptr_ref[e, f]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((b < used_ref[0]) & (j < count))
    def _accumulate():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.float32),
            w_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)

    @pl.when(j == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("tb", "tk", "tf", "interpret")
)
def bsr_matmul_pallas_ragged(
    x: jax.Array,         # (R, K), rows grouped by expert in TB-row tiles
    blocks: jax.Array,    # (E, NB, TK, TF), per expert sorted by block-col
    brow: jax.Array,      # (E, NB) i32
    indptr: jax.Array,    # (E, NF+1) i32 pointers into blocks per out tile
    te: jax.Array,        # (R/TB,) i32 expert of each row tile
    used: jax.Array,      # (1,) i32 row tiles in use
    *,
    tb: int = 128,
    tk: int = 128,
    tf: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Ragged grouped ``y[rows of tile b] = x[rows of tile b] @ W[te[b]]``
    over a stacked BSR group — ONE launch for every expert, whatever rows
    each holds.  Grid ``(R/TB, NF, NJ)``; the index maps pick member
    ``te[b]``'s blocks exactly as the batched mode picks member ``g``'s,
    so a tile's rows are bit-identical to :func:`bsr_matmul_pallas_batched`
    on the same rows.  Tiles ``b >= used[0]`` map every slot to the last
    used tile's last block (no new DMA), skip the matmul and store zeros.
    Output ``(R, NF*tf)``."""
    interpret = _resolve_interpret(interpret)
    rows, k = x.shape
    nb = blocks.shape[-3]
    nf = indptr.shape[-1] - 1
    assert rows % tb == 0 and k % tk == 0
    assert blocks.shape[-2:] == (tk, tf) and te.shape == (rows // tb,)
    nj = max(1, min(nb, k // tk))

    def where(b, f, j, ip, br, te, used):
        """(expert, block) that grid step (b, f, j) reads."""
        last = jnp.maximum(used[0] - 1, 0)
        live = b < used[0]
        e = te[jnp.minimum(b, last)]
        f = jnp.where(live, f, nf - 1)
        j = jnp.where(live, j, nj - 1)
        return jnp.minimum(b, last), e, _slot(lambda i: ip[e, i], f, j, nb)

    def x_map(b, f, j, ip, br, te, used):
        bb, e, s = where(b, f, j, ip, br, te, used)
        return (bb, br[e, s])

    def w_map(b, f, j, ip, br, te, used):
        _, e, s = where(b, f, j, ip, br, te, used)
        return (e, s, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(rows // tb, nf, nj),
        in_specs=[pl.BlockSpec((tb, tk), x_map),
                  pl.BlockSpec((1, 1, tk, tf), w_map)],
        out_specs=pl.BlockSpec((tb, tf), lambda b, f, j, *_: (b, f)),
        scratch_shapes=[pltpu.VMEM((tb, tf), jnp.float32)],
    )
    return pl.pallas_call(
        _ragged_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, nf * tf), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="bsr_spmm_ragged",
    )(indptr, brow, te, used, x, blocks)
