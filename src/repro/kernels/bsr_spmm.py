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
K-tile. Grid: (BM tiles, L walk steps).  Each row tile walks only the
stored blocks (:func:`walk_tables`): output tile ``f`` takes
``max(count_f, 1)`` consecutive steps, block ``indptr[f] + j`` at its
``j``-th, so each step streams exactly one ``(TB, TK)`` tile of ``x`` and
one ``(TK, TF)`` weight block HBM→VMEM into a resident f32 accumulator,
zeroed at the tile's first step and stored at its last.  An empty tile's
one step repeats the previous block index (no new DMA), skips the matmul
and stores zeros.  ``L = min(NF·min(NB, K/TK), NB + NF)`` comes from the
shapes; steps past a member's walk repeat its last step (no DMA, no
matmul).  The index maps read the walk from scalar prefetch, so one
compiled kernel serves any sparsity pattern of the same bucketed geometry
(HFlex).

The *ragged* mode (:func:`bsr_matmul_pallas_ragged`, Pallas name
``bsr_spmm_ragged``) serves a dropless mixture of experts: the rows of
``x`` are token-expert pairs sorted by expert into 128-row-aligned
segments of one static buffer, and each row tile multiplies by the
weight of the expert that a scalar-prefetched table names for it.  Tiles
past the used count repeat the last used tile's last block (no DMA), skip
the matmul and store zeros.

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
           "bsr_matmul_pallas_ragged", "walk_tables"]

# A walk step is four int32 words: the weight block, the K tile of ``x``
# it multiplies, the output tile, and the step's flags.
_BLK, _XK, _TILE, _FLAGS, _WORDS = 0, 1, 2, 3, 4
_FIRST, _LIVE, _LAST = 1, 2, 4


def _member_walk(ip, br, length: int):
    """``(length, 4)`` walk of one member's ``indptr`` and ``brow``."""
    nb = br.shape[0]
    count = ip[1:] - ip[:-1]
    steps = jnp.maximum(count, 1)
    start = jnp.cumsum(steps) - steps                  # each tile's 1st step
    t = jnp.arange(length, dtype=jnp.int32)
    f = (jnp.searchsorted(start, t, side="right",
                          method="compare_all") - 1).astype(jnp.int32)
    j = t - start[f]
    # held at the tile's last block once j passes its count, and at the
    # previous tile's last block for an empty tile: no new DMA
    blk = jnp.clip(jnp.minimum(ip[f] + j, ip[f + 1] - 1), 0,
                   max(nb - 1, 0))
    last = jnp.append(f[1:] != f[:-1], True)
    flags = (jnp.where(j == 0, _FIRST, 0) | jnp.where(j < count[f], _LIVE, 0)
             | jnp.where(last, _LAST, 0))
    return jnp.stack([blk, br[blk], f, flags], axis=-1).astype(jnp.int32)


def walk_tables(indptr, brow, k_tiles: int):
    """The walk of every member, flat for scalar prefetch (a 2-D SMEM
    array pads its minor dimension to 128 words).  ``indptr`` is
    ``([G,] NF+1)`` and ``brow`` ``([G,] NB)``; step ``t`` of member ``g``
    is words ``(g·L + t)·4 …`` of the result, ``L = min(NF·min(NB,
    k_tiles), NB + NF)``.  Returns ``(walk, L)``."""
    nb, nf = brow.shape[-1], indptr.shape[-1] - 1
    # the stored blocks plus one step per empty tile, and never more than
    # the dense walk of NF·min(NB, K/TK) slots
    length = min(nf * max(1, min(nb, k_tiles)), nb + nf)
    build = functools.partial(_member_walk, length=length)
    if indptr.ndim == 2:
        build = jax.vmap(build)
    return build(indptr, brow).reshape(-1), length


def _word(walk, i, word):
    """Word ``word`` of flat walk step ``i`` (``g·L + t``)."""
    return walk[i * _WORDS + word]


def _step(flags, live, x_ref, w_ref, o_ref, acc_ref):
    """One walk step: zero the accumulator at a tile's first step, add the
    block's product where ``live``, store at the tile's last step.  The
    refs may carry leading unit dimensions (the batched and ragged
    modes' block specs)."""
    @pl.when(flags & _FIRST != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _accumulate():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...].reshape(x_ref.shape[-2:]).astype(jnp.float32),
            w_ref[...].reshape(w_ref.shape[-2:]).astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)

    @pl.when(flags & _LAST != 0)
    def _store():
        o_ref[...] = acc_ref[...].reshape(o_ref.shape).astype(o_ref.dtype)


def _kernel(walk_ref, x_ref, w_ref, o_ref, acc_ref, *, batched: bool,
            length: int):
    # refs: x ([1,] TB, TK), w ([1,] 1, TK, TF), o ([1,] TB, TF)
    g = pl.program_id(0) if batched else 0
    t = pl.program_id(2 if batched else 1)
    flags = _word(walk_ref, g * length + t, _FLAGS)
    _step(flags, flags & _LIVE != 0, x_ref, w_ref, o_ref, acc_ref)


def _call(x, blocks, brow, indptr, *, tb, tk, tf, interpret, batched):
    interpret = _resolve_interpret(interpret)
    bsz, k = x.shape[-2:]
    nf = indptr.shape[-1] - 1
    assert bsz % tb == 0 and k % tk == 0
    assert blocks.shape[-2:] == (tk, tf)
    walk, length = walk_tables(indptr, brow, k // tk)
    if batched:
        g_sz = x.shape[0]
        assert blocks.shape[0] == g_sz
        grid = (g_sz, bsz // tb, length)
        in_specs = [
            pl.BlockSpec((1, tb, tk), lambda g, b, t, wk:
                         (g, b, _word(wk, g * length + t, _XK))),
            pl.BlockSpec((1, 1, tk, tf), lambda g, b, t, wk:
                         (g, _word(wk, g * length + t, _BLK), 0, 0))]
        out_specs = pl.BlockSpec((1, tb, tf), lambda g, b, t, wk:
                                 (g, b, _word(wk, g * length + t, _TILE)))
        out_shape = jax.ShapeDtypeStruct((g_sz, bsz, nf * tf), x.dtype)
        semantics = ("parallel", "parallel", "arbitrary")
    else:
        grid = (bsz // tb, length)
        in_specs = [
            pl.BlockSpec((tb, tk), lambda b, t, wk: (b, _word(wk, t, _XK))),
            pl.BlockSpec((1, tk, tf), lambda b, t, wk:
                         (_word(wk, t, _BLK), 0, 0))]
        out_specs = pl.BlockSpec((tb, tf), lambda b, t, wk:
                                 (b, _word(wk, t, _TILE)))
        out_shape = jax.ShapeDtypeStruct((bsz, nf * tf), x.dtype)
        semantics = ("parallel", "arbitrary")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((tb, tf), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, batched=batched, length=length),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        name="bsr_spmm",
    )(walk, x, blocks)


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


def _ragged_kernel(walk_ref, te_ref, used_ref, x_ref, w_ref, o_ref,
                   acc_ref, *, length: int):
    # refs: x (TB, TK), w (1, 1, TK, TF), o (TB, TF)
    b, t = pl.program_id(0), pl.program_id(1)
    e = te_ref[jnp.minimum(b, jnp.maximum(used_ref[0] - 1, 0))]
    flags = _word(walk_ref, e * length + t, _FLAGS)
    _step(flags, (b < used_ref[0]) & (flags & _LIVE != 0), x_ref, w_ref,
          o_ref, acc_ref)


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
    each holds.  Grid ``(R/TB, L)``; row tile ``b`` walks member
    ``te[b]``'s blocks exactly as the batched mode walks member ``g``'s,
    so a tile's rows are bit-identical to :func:`bsr_matmul_pallas_batched`
    on the same rows.  Tiles ``b >= used[0]`` hold every step at the last
    used tile's last block (no new DMA), skip the matmul and store zeros
    in every output tile.
    Output ``(R, NF*tf)``."""
    interpret = _resolve_interpret(interpret)
    rows, k = x.shape
    nf = indptr.shape[-1] - 1
    assert rows % tb == 0 and k % tk == 0
    assert blocks.shape[-2:] == (tk, tf) and te.shape == (rows // tb,)
    walk, length = walk_tables(indptr, brow, k // tk)

    def where(b, t, wk, te, used):
        """(row tile, expert, flat walk step) that grid step (b, t) reads:
        a tile past the used count holds the last used tile's last step."""
        bb = jnp.minimum(b, jnp.maximum(used[0] - 1, 0))
        e = te[bb]
        return bb, e, e * length + jnp.where(b < used[0], t, length - 1)

    def x_map(b, t, wk, te, used):
        bb, _, i = where(b, t, wk, te, used)
        return (bb, _word(wk, i, _XK))

    def w_map(b, t, wk, te, used):
        _, e, i = where(b, t, wk, te, used)
        return (e, _word(wk, i, _BLK), 0, 0)

    def o_map(b, t, wk, te, used):
        _, e, _ = where(b, t, wk, te, used)
        return (b, _word(wk, e * length + t, _TILE))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(rows // tb, length),
        in_specs=[pl.BlockSpec((tb, tk), x_map),
                  pl.BlockSpec((1, 1, tk, tf), w_map)],
        out_specs=pl.BlockSpec((tb, tf), o_map),
        scratch_shapes=[pltpu.VMEM((tb, tf), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_ragged_kernel, length=length),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, nf * tf), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="bsr_spmm_ragged",
    )(walk, te, used, x, blocks)
