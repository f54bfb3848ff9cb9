"""The unified, differentiable SpMM entry point.

``spmm(A, b, c=None, alpha=1.0, beta=0.0, backend="auto")`` computes
``alpha * A @ b + beta * c`` for any :class:`SparseTensor` format through
the backend registry.  Three properties the legacy ``sextans_spmm`` /
``bsr_matmul`` pair lacked:

1. **Traced epilogue** — ``alpha``/``beta`` are dynamic f32 scalars all the
   way into the kernel's SMEM, so sweeping them reuses one compiled
   executable (HFlex semantics; see the recompile-count test).
2. **Differentiable** — a ``jax.custom_vjp`` routes cotangents to ``b``,
   ``c``, ``alpha``/``beta`` and the packed non-zero values (``A.values``),
   regardless of which backend ran the forward.  The backward pass is the
   VJP of the XLA reference path (the standard surrogate-gradient pattern
   for opaque kernels), which opens sparse-layer *training*.
3. **Format-agnostic** — HFlex slabs and BSR tiles go through the same call;
   new formats plug in via ``register_backend``.

Gradient w.r.t. ``A.values`` only flows to *stored* non-zeros: the sparsity
structure (including slab padding slots, which hold exact 0.0) is treated
as constant, matching the semantics of training a pruned layer.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import backends as _bk
from .tensor import Format, SparseTensor

__all__ = ["spmm", "spmm_raw", "spmm_streaming"]


def _raw_reference(a: SparseTensor, b: jax.Array) -> jax.Array:
    """A @ b through the XLA path (differentiable-by-construction).

    Leading (group) axes of ``b`` pass through: a batched tensor gets a
    batched reference of shape ``(G, M, N)``.
    """
    zeros = jnp.zeros((*b.shape[:-2], a.shape[0], b.shape[-1]), b.dtype)
    one = jnp.asarray(1.0, jnp.float32)
    zero = jnp.asarray(0.0, jnp.float32)
    if a.format is Format.HFLEX:
        return _bk._hflex_jnp(a, b, zeros, one, zero)
    return _bk._bsr_jnp(a, b, zeros, one, zero)


def _run_backend(name, okey, a, b, c, alpha, beta):
    return _bk.get_backend(name).fn(a, b, c, alpha, beta, **dict(okey))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _spmm_core(name, okey, a, b, c, alpha, beta):
    return _run_backend(name, okey, a, b, c, alpha, beta)


def _spmm_fwd(name, okey, a, b, c, alpha, beta):
    out = _run_backend(name, okey, a, b, c, alpha, beta)
    return out, (a, b, c, alpha, beta)


def _float0_zeros(x):
    if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
        return jnp.zeros_like(x)
    return np.zeros(jnp.shape(x), dtype=jax.dtypes.float0)


def _spmm_bwd(name, okey, res, g):
    a, b, c, alpha, beta = res
    g32 = g.astype(jnp.float32)

    def raw_fn(vals, b_):
        return _raw_reference(a.with_values(vals), b_)

    raw, vjp = jax.vjp(raw_fn, a.values, b)
    # alpha/beta may be per-member (G,) vectors on a batched tensor: expand
    # against the (G, M, N) cotangent so each member scales with its own
    # coefficient (scalars pass through unchanged).
    ct = (_bk._ab_expand(alpha, g32.ndim) * g32).astype(raw.dtype)
    dvals, db = vjp(ct)

    if a.format is Format.HFLEX:
        # Padding slots (position >= true per-slab count) are structural:
        # their primal value is exactly 0.0 and must stay 0.0 under training,
        # but the reference computes d out/d val_pad = alpha*g[row0]*b[col0]
        # != 0 for them.  Mask by the true counts carried in the packing
        # (per-member counts for a batched tensor — nse carries the group
        # axis, so the mask is per-member too).
        dvals = jnp.where(a.data.valid_slots(), dvals, 0)
    elif a.batch is not None:
        # Stacked BSR: the padded block slots (position >= the true member
        # count indptr[g, -1]) alias real (brow=0, bcol-dropped) positions
        # in the reference scatter and would pick up nonzero dW cotangents;
        # mask them per member like HFLEX's nse mask.
        d = a.data
        valid = (jax.lax.broadcasted_iota(jnp.int32, d.blocks.shape, 1)
                 < d.indptr[:, -1][:, None, None, None])
        dvals = jnp.where(valid, dvals, 0)
    # Unbatched-BSR tile-padding cells need no mask: padded b rows are zero
    # and out-of-bounds output columns have zero cotangent, so their grads
    # vanish by construction.

    dc = (_bk._ab_expand(beta, g32.ndim) * g32).astype(c.dtype)
    # Vector coefficients keep their per-member axis: reduce only over the
    # trailing (M, N) axes so d alpha / d beta match the (G,) primal shape.
    ax_a = tuple(range(1, g32.ndim)) if jnp.ndim(alpha) > 0 else None
    ax_b = tuple(range(1, g32.ndim)) if jnp.ndim(beta) > 0 else None
    dalpha = jnp.sum(g32 * raw.astype(jnp.float32),
                     axis=ax_a).astype(alpha.dtype)
    dbeta = jnp.sum(g32 * c.astype(jnp.float32), axis=ax_b).astype(beta.dtype)

    da = jax.tree.map(_float0_zeros, a).with_values(dvals.astype(a.values.dtype))
    return (da, db.astype(b.dtype), dc, dalpha, dbeta)


_spmm_core.defvjp(_spmm_fwd, _spmm_bwd)

_spmm_jit = jax.jit(_spmm_core, static_argnums=(0, 1))


def spmm_raw(backend_name: str, a: SparseTensor, b, c, alpha, beta, **opts):
    """Un-jitted dispatch core (still differentiable) — for composing into
    outer jits with explicit shardings (see SextansEngine.sharded_spmm_fn)."""
    okey = tuple(sorted(opts.items()))
    return _spmm_core(backend_name, okey, a, b, c,
                      jnp.asarray(alpha, jnp.float32),
                      jnp.asarray(beta, jnp.float32))


# ---------------------------------------------------------------------------
# Out-of-core streaming (differentiable)
# ---------------------------------------------------------------------------


def _stream_bounds(nw: int, wchunk: int):
    return [(w0, min(nw, w0 + wchunk)) for w0 in range(0, nw, wchunk)]


def _tile_bounds(n: int, ntile: int):
    return [(n0, min(n, n0 + ntile)) for n0 in range(0, n, ntile)]


def _stream_raw(name, okey, wchunk, ntile, a, b):
    """Raw accumulated ``A @ b`` (logical (M, N) f32) via the backend's
    streaming hooks over the 2-D (N-tile × K-window-chunk) grid — column
    tiles outer, window chunks inner, the same walk :class:`StreamingPlan`
    makes.  Per-column math is independent and each column's add sequence
    is the resident path's, so the result is bit-identical for every
    (wchunk, ntile) — see backends.StreamOps.  Tiles are sliced at their
    true width (no padding needed in-trace); hooks receive the column-tile
    index as ``tile=``."""
    stream = _bk.get_backend(name).stream
    opts = dict(okey)
    d = a.data
    n = b.shape[-1]
    stripes = []
    for j, (n0, n1) in enumerate(_tile_bounds(n, ntile)):
        b_t = (b if (n0, n1) == (0, n)
               else jax.lax.slice_in_dim(b, n0, n1, axis=1))
        acc = stream.init(a, n1 - n0, tile=j, **opts)
        for w0, w1 in _stream_bounds(d.nw, wchunk):
            a_w = a.windows(w0, w1)
            b_w = jax.lax.slice_in_dim(b_t, w0 * d.k0, w0 * d.k0 + a_w.k,
                                       axis=0)
            acc = stream.step(a_w, b_w, acc, tile=j, **opts)
        stripes.append(stream.collect(a, acc, n1 - n0, tile=j, **opts))
    if len(stripes) == 1:
        return stripes[0]
    return jnp.concatenate(stripes, axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _stream_core(name, okey, wchunk, ntile, a, b, c, alpha, beta):
    raw = _stream_raw(name, okey, wchunk, ntile, a, b)
    return _bk.stream_finish(raw, c, alpha, beta, b.dtype)


def _stream_fwd(name, okey, wchunk, ntile, a, b, c, alpha, beta):
    raw = _stream_raw(name, okey, wchunk, ntile, a, b)
    out = _bk.stream_finish(raw, c, alpha, beta, b.dtype)
    return out, (a, b, c, alpha, beta, raw)


def _stream_bwd(name, okey, wchunk, ntile, res, g):
    """Per-tile, per-chunk cotangent accumulation: the backward pass walks
    the same 2-D (N-tile × K-window-chunk) grid as the forward, so at no
    point does it need more than one tile-chunk's slab payload / ``b``
    block in flight — streaming stays differentiable without resurrecting
    the resident working set.  Each chunk's ``d vals`` is masked by its
    own true counts (``nse`` rides the window slice), exactly like the
    single-shot VJP; tiles contribute disjoint ``d b`` columns
    (concatenated) and sum into the shared ``d vals``."""
    a, b, c, alpha, beta, raw = res
    g32 = g.astype(jnp.float32)
    ct_full = alpha * g32
    d = a.data
    n = b.shape[-1]
    dvals = None
    db_tiles = []
    for n0, n1 in _tile_bounds(n, ntile):
        ct = (ct_full if (n0, n1) == (0, n)
              else jax.lax.slice_in_dim(ct_full, n0, n1, axis=1))
        b_t = (b if (n0, n1) == (0, n)
               else jax.lax.slice_in_dim(b, n0, n1, axis=1))
        dvals_chunks = []
        db_chunks = []
        for w0, w1 in _stream_bounds(d.nw, wchunk):
            a_w = a.windows(w0, w1)
            b_w = jax.lax.slice_in_dim(b_t, w0 * d.k0, w0 * d.k0 + a_w.k,
                                       axis=0)

            def raw_fn(vals, b_, a_w=a_w):
                return _raw_reference(a_w.with_values(vals), b_)

            _, vjp = jax.vjp(raw_fn, a_w.values, b_w)
            dv, db_w = vjp(ct)
            dvals_chunks.append(jnp.where(a_w.data.valid_slots(), dv, 0))
            db_chunks.append(db_w)
        dv_t = jnp.concatenate(dvals_chunks, axis=-3)
        dvals = dv_t if dvals is None else dvals + dv_t
        db_tiles.append(jnp.concatenate(db_chunks, axis=0))
    db = (db_tiles[0] if len(db_tiles) == 1
          else jnp.concatenate(db_tiles, axis=1)).astype(b.dtype)
    dc = (beta * g32).astype(c.dtype)
    dalpha = jnp.sum(g32 * raw).astype(alpha.dtype)
    dbeta = jnp.sum(g32 * c.astype(jnp.float32)).astype(beta.dtype)
    da = jax.tree.map(_float0_zeros, a).with_values(
        dvals.astype(a.values.dtype))
    return (da, db, dc, dalpha, dbeta)


_stream_core.defvjp(_stream_fwd, _stream_bwd)

_stream_jit = jax.jit(_stream_core, static_argnums=(0, 1, 2, 3))


def spmm_streaming(
    a: SparseTensor,
    b,
    c=None,
    alpha=1.0,
    beta=0.0,
    *,
    window_chunk: int = 1,
    n_tile: Optional[int] = None,
    backend: str = "auto",
    **opts,
) -> jax.Array:
    """``alpha * A @ b + beta * c`` executed as a 2-D (K-window × N-tile)
    stream.

    The differentiable twin of :class:`repro.sparse_api.StreamingPlan`:
    the matrix is consumed ``window_chunk`` K0-windows at a time against a
    carried f32 accumulator — per column tile of ``n_tile`` B columns
    (default: all of them, the 1-D K-only stream) — with the epilogue
    applied once per tile at the end of its window walk.  Results are
    **bit-identical** to :func:`spmm` on the same backend for every
    (chunk size, tile width): per-column math is independent, so tiling N
    never reassociates any column's add sequence.  The custom VJP walks
    the same 2-D grid, accumulating cotangents tile by tile and chunk by
    chunk (see ``_stream_bwd``).

    Scope: this bounds the per-tile-chunk *intermediates* (the block of
    ``b`` in flight, the contribution scatter, each chunk's cotangent) —
    ``a``, ``b`` and the saved residuals are still whole-array jit
    operands, and the trace unrolls ``ceil(N / n_tile) *
    ceil(NW / window_chunk)`` chunk bodies.  For matrices that genuinely
    exceed device memory use :func:`plan` with ``device_bytes=``
    (host-side payload staging, one compiled window-step executable);
    this entry point is for *training* with windowed-execution semantics
    and for pinning the streaming tier's bit-identity.

    Unbatched ``Format.HFLEX`` only; ``backend`` must provide streaming
    hooks (all built-in HFLEX backends do).
    """
    if not isinstance(a, SparseTensor):
        raise TypeError(
            f"spmm_streaming expects a SparseTensor, got {type(a).__name__}")
    if a.format is not Format.HFLEX:
        raise ValueError("spmm_streaming supports Format.HFLEX only")
    from repro.analysis.validate import maybe_validate

    maybe_validate(a)   # SEXTANS_CHECK=1: packed-artifact invariants
    if a.batch is not None:
        raise ValueError("spmm_streaming takes one matrix at a time")
    b = jnp.asarray(b)
    m, k = a.shape
    if b.ndim != 2:
        raise ValueError(f"b must be 2-D (K, N), got shape {b.shape}")
    if b.shape[0] != k:
        raise ValueError(f"B rows {b.shape[0]} != A cols {k}")
    wchunk = int(window_chunk)
    if not 1 <= wchunk <= a.data.nw:
        raise ValueError(
            f"window_chunk must be in [1, NW={a.data.nw}], got {wchunk}")
    ntile = b.shape[1] if n_tile is None else int(n_tile)
    if not 1 <= ntile <= b.shape[1]:
        raise ValueError(
            f"n_tile must be in [1, N={b.shape[1]}], got {ntile}")
    cshape = (m, b.shape[1])
    c_ = jnp.zeros(cshape, b.dtype) if c is None else jnp.asarray(c)
    if c_.shape != cshape:
        raise ValueError(f"c must have shape {cshape}, got {c_.shape}")
    name = _bk.resolve_backend(backend, a, b)
    if _bk.get_backend(name).stream is None:
        raise ValueError(f"backend {name!r} has no streaming hooks")
    okey = tuple(sorted(_bk.with_lane(a, name, ntile, opts).items()))
    return _stream_jit(name, okey, wchunk, ntile, a, b, c_,
                       jnp.asarray(alpha, jnp.float32),
                       jnp.asarray(beta, jnp.float32))


def spmm(
    a: SparseTensor,
    b,
    c=None,
    alpha=1.0,
    beta=0.0,
    *,
    backend: str = "auto",
    **opts,
) -> jax.Array:
    """``alpha * A @ b + beta * c`` for a device SparseTensor ``A``.

    Args:
      a: SparseTensor of shape (M, K), any registered format.  A *batched*
        tensor (``a.batch == G``, see ``stack_hflex``) computes G SpMMs in
        one dispatch.
      b: dense (K, N) array — (G, K, N) for a batched ``a``.
      c: optional dense (M, N) array (defaults to zeros) — (G, M, N) when
        batched.
      alpha, beta: epilogue scalars — *traced*; sweeping them does not
        recompile.  For a batched ``a`` each may instead be a ``(G,)``
        vector giving every group member its own epilogue, bit-identical
        per member to running it alone with the scalar (the serving tier's
        epilogue-folding hook).
      backend: a registered backend name, or "auto" (platform/format/density
        heuristic; see ``repro.sparse_api.backends``).
      **opts: static backend options (e.g. ``tn``, ``interpret``) — part of
        the executable identity.
    """
    if not isinstance(a, SparseTensor):
        raise TypeError(f"spmm expects a SparseTensor, got {type(a).__name__}")
    from repro.analysis.validate import maybe_validate

    maybe_validate(a)   # SEXTANS_CHECK=1: packed-artifact invariants
    b = jnp.asarray(b)
    m, k = a.shape
    g = a.batch
    if g is None:
        if b.ndim != 2:
            raise ValueError(f"b must be 2-D (K, N), got shape {b.shape}")
    else:
        if b.ndim != 3 or b.shape[0] != g:
            raise ValueError(
                f"batched spmm (G={g}) needs b of shape (G, K, N), got "
                f"{b.shape}")
    if b.shape[-2] != k:
        raise ValueError(f"B rows {b.shape[-2]} != A cols {k}")
    cshape = (m, b.shape[-1]) if g is None else (g, m, b.shape[-1])
    c_ = jnp.zeros(cshape, b.dtype) if c is None else jnp.asarray(c)
    if c_.shape != cshape:
        raise ValueError(f"c must have shape {cshape}, got {c_.shape}")
    alpha_ = jnp.asarray(alpha, jnp.float32)
    beta_ = jnp.asarray(beta, jnp.float32)
    for nm, x in (("alpha", alpha_), ("beta", beta_)):
        if x.ndim == 0:
            continue
        if g is None:
            raise ValueError(
                f"vector {nm} needs a batched tensor; got shape {x.shape} "
                "on an unbatched spmm")
        if x.shape != (g,):
            raise ValueError(
                f"vector {nm} must have shape (G,)=({g},), got {x.shape}")
    name = _bk.resolve_backend(backend, a, b)
    okey = tuple(sorted(_bk.with_lane(a, name, b.shape[-1], opts).items()))
    return _spmm_jit(name, okey, a, b, c_, alpha_, beta_)
