"""SpMM backend registry: pluggable execution strategies for SparseTensor.

A *backend* is a callable ``fn(A, b, c, alpha, beta, **opts) -> jax.Array``
computing ``alpha * A @ b + beta * c`` on padded-consistent operands, where
``alpha``/``beta`` are traced scalars (no recompile per value — HFlex).
Backends declare which :class:`Format` s they support and are registered by
name.  Two are built in, one chip kernel per format and its reference:

* ``pallas`` — the format's Pallas kernel: the Sextans streaming kernel
  with its one-hot MXU gather (HFLEX) or the BSR tile kernel (single,
  batched and ragged modes).  On HFLEX its column tile comes from the
  dense width N (:func:`hflex_lane`): a narrow lane of 8·⌈N/8⌉ columns
  for SpMV-shaped calls (N ≤ :func:`skinny_n_max`), else 128.  On
  BSR its row tile comes from N too (:func:`bsr_row_tile`): up to 2,048
  token rows a tile, so a prefill chunk streams each weight block once.
* ``jnp``    — segment-sum / einsum XLA path: the reference, and the
  off-TPU production and autodiff path.
* ``auto``   — resolves to one of the above from platform, format and
  density (override with :func:`set_auto_policy`).

This module is the one place that knows what the names mean: callers ask
:func:`hflex_lane`, :func:`bsr_row_tile`, :func:`is_skinny` and
:func:`is_kernel` instead of testing names.  ``register_backend`` stays
the extension point: a new format plugs in as (new Format, new backend)
without another API fork.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Callable, Dict, FrozenSet, List, Optional

import jax
import jax.numpy as jnp

from repro.core.partition import cdiv
from repro.kernels.bsr_spmm import (bsr_matmul_pallas,
                                    bsr_matmul_pallas_batched,
                                    bsr_matmul_pallas_ragged)
from repro.kernels.ref import (bsr_dense_batched, bsr_matmul_ref,
                               bsr_matmul_ref_batched)
from repro.kernels.sextans_spmm import sextans_spmm_pallas

from .tensor import Format, SparseTensor

__all__ = [
    "Backend",
    "StreamOps",
    "stream_finish",
    "register_backend",
    "get_backend",
    "list_backends",
    "resolve_backend",
    "set_auto_policy",
    "BACKEND_STATS",
    "SKINNY_N_MAX",
    "skinny_n_max",
    "set_skinny_n_max",
    "hflex_lane",
    "bsr_row_tile",
    "with_lane",
    "is_skinny",
    "is_kernel",
]

# Default skinny-N width: HFLEX calls with N at or below it are SpMV-shaped
# and the kernel runs them at the narrow lane (:func:`hflex_lane`) — the
# paper's SNAP/SuiteSparse graph workloads live at N = 1..8.  The *live*
# threshold is ``skinny_n_max()``: this constant is only its
# lowest-precedence fallback.
SKINNY_N_MAX = 8

_SKINNY_OVERRIDE: Optional[int] = None


def skinny_n_max() -> int:
    """The auto policy's live skinny-N routing threshold.

    Precedence: a :func:`set_skinny_n_max` override (the autotuner pushes
    DB-tuned values through it — see
    ``repro.sparse_api.autotune.apply_skinny_from_db``) >
    ``$SEXTANS_SKINNY_N_MAX`` > the built-in ``SKINNY_N_MAX`` (8).
    """
    if _SKINNY_OVERRIDE is not None:
        return _SKINNY_OVERRIDE
    env = os.environ.get("SEXTANS_SKINNY_N_MAX")
    if env:
        try:
            return max(0, int(env))
        except ValueError:
            pass
    return SKINNY_N_MAX


def set_skinny_n_max(value: Optional[int]) -> None:
    """Override the skinny-N threshold (``None`` restores the
    env/default precedence chain).  ``0`` disables the narrow lane."""
    global _SKINNY_OVERRIDE
    _SKINNY_OVERRIDE = None if value is None else max(0, int(value))


#: sublane multiple the narrow HFLEX lane pads N up to
_NARROW_LANE = 8
#: column tile of the HFLEX kernel above the skinny threshold
_WIDE_LANE = 128


def hflex_lane(n: int, narrow: Optional[bool] = None) -> int:
    """Column tile (TN) of the HFLEX kernel for dense width ``n``.

    SpMV-shaped calls (``n`` ≤ :func:`skinny_n_max`, unless ``narrow``
    says otherwise) take the narrow lane, ``8·⌈n/8⌉`` columns: the NT
    grid axis has one tile and each B window streams once.  Wider calls
    take 128-column tiles.  An explicit ``tn=`` backend option overrides
    this."""
    if narrow is None:
        narrow = n <= skinny_n_max()
    if narrow:
        return cdiv(n, _NARROW_LANE) * _NARROW_LANE
    return _WIDE_LANE


#: the BSR kernel's widest row tile: its (TB, 128) x and output tiles,
#: double-buffered, and the f32 accumulator grow with TB, and at 4,096
#: rows the kernel needs 16.82 MiB of scoped VMEM, over v5e's 16 MiB
_BSR_ROW_TILE_MAX = 2048


def bsr_row_tile(n: int) -> int:
    """Row tile (TB) of the BSR kernel for dense width ``n`` (the token
    count): the largest multiple of 128 that divides ``n`` rounded up to
    128, at most :data:`_BSR_ROW_TILE_MAX`, so no row is padded beyond
    what 128-row tiles pad.  Every row tile walks the whole block list,
    so a wider tile streams each weight block fewer times and takes
    fewer grid steps.  An explicit ``tn=`` backend option overrides
    this; the ragged mode keeps :data:`RAGGED_TILE`."""
    units = cdiv(max(int(n), 1), 128)
    cap = min(units, _BSR_ROW_TILE_MAX // 128)
    return 128 * max(d for d in range(1, cap + 1) if units % d == 0)


def is_kernel(backend: str) -> bool:
    """True when ``backend`` runs the format's Pallas kernel (which XLA
    cannot partition, and which interprets off-TPU)."""
    return backend == "pallas"


def with_lane(a: SparseTensor, backend: str, n: int,
              opts: Dict) -> Dict:
    """``opts`` with the kernel's tile along the dense width pinned for
    width ``n`` (``tn``: :func:`hflex_lane` on HFLEX, :func:`bsr_row_tile`
    on BSR, unless the caller set one), so an executable key records the
    tile the trace will use."""
    if is_kernel(backend) and opts.get("tn") is None:
        tile = hflex_lane if a.format is Format.HFLEX else bsr_row_tile
        return dict(opts, tn=tile(n))
    return dict(opts)


def is_skinny(a: SparseTensor, backend: str, n: int) -> bool:
    """Whether a dispatch of ``a`` at dense width ``n`` on ``backend`` is
    SpMV-shaped: an HFLEX matrix, a built-in backend, and ``n`` ≤
    :func:`skinny_n_max` (the kernel's narrow lane; ``jnp`` serves every
    width with one body).  Engine and scheduler stats count these as
    ``skinny_dispatches``."""
    return (a.format is Format.HFLEX and backend in ("pallas", "jnp")
            and n <= skinny_n_max())


# Incremented once per *trace* of a backend body (i.e. per compiled
# executable, not per call) — the JAX analogue of the paper counting
# avoided synthesis/place/route runs.  Tests assert alpha/beta sweeps do
# not grow this.  The async serving pipeline traces from its dispatch
# thread while the owning thread may trace too, so the bump is
# lock-guarded (``bump_trace``).
BACKEND_STATS: Dict[str, int] = {"traces": 0}

_STATS_LOCK = threading.Lock()


def bump_trace() -> None:
    """Thread-safe ``BACKEND_STATS['traces'] += 1`` (called per trace of a
    backend body, possibly from an async dispatch thread)."""
    with _STATS_LOCK:
        BACKEND_STATS["traces"] += 1


@dataclasses.dataclass(frozen=True)
class StreamOps:
    """Out-of-core K0-window streaming hooks of a backend.

    A streaming execution carries a backend-layout raw f32 accumulator
    across window-chunk dispatches and applies the alpha/beta epilogue once
    at the end — the only decomposition that keeps the per-row floating-
    point add sequence identical to the resident (single-shot) path, hence
    bit-identical results:

    * ``init(a, n, **opts) -> acc``          — fresh accumulator (backend
      layout: logical (M, N) for ``jnp``, padded/permuted kernel layout for
      ``pallas``), always f32.
    * ``step(a_chunk, b_chunk, acc, **opts) -> acc`` — accumulate one
      window-chunk (``a_chunk = a.windows(w0, w1)``, ``b_chunk`` the
      matching rows of ``b``).  Traceable; the chunk payload is the only
      slab data touched, so it is the unit an out-of-core plan keeps on
      device.
    * ``collect(a, acc, n) -> raw``          — accumulator back to the
      logical (M, N) f32 array (un-permute/slice for kernel layouts).

    2-D (K-window × N-tile) streaming calls each hook once **per column
    tile**, with ``n`` the tile's true width and ``b_chunk`` carrying only
    that tile's columns; the traced streaming entry additionally passes the
    column-tile index as a ``tile=`` keyword (hooks must accept and may
    ignore it — all built-ins absorb it via ``**_unused``).  Hooks must be
    tile-position-independent: the plan tier compiles ONE step executable
    and reuses it for every tile, including an inertly column-padded tail
    tile (padding columns accumulate garbage that ``collect``'s final slice
    drops — per-column math is independent, so real columns are untouched).

    The epilogue ``(alpha * raw + beta * c).astype(b.dtype)`` is shared
    (:func:`stream_finish`), matching both backends' resident epilogues
    elementwise.
    """

    init: Callable
    step: Callable
    collect: Callable


def stream_finish(raw, c, alpha, beta, dtype):
    """Shared streaming epilogue on the collected raw accumulator —
    elementwise identical to the resident paths' fused epilogues.
    ``dtype`` is the dense operand ``b``'s dtype (the resident paths cast
    the result to it, whatever ``c`` carries)."""
    return (alpha * raw + beta * c.astype(jnp.float32)).astype(dtype)


def _ab_expand(x, out_ndim: int):
    """Broadcast an epilogue coefficient against a ``([G,] M, N)`` raw
    accumulator: scalars pass through, a ``(G,)`` per-member vector gains
    trailing singleton axes so each group member scales with its own
    coefficient — the elementwise math is identical to running that member
    alone with its scalar, so folding mixed epilogues into one group
    dispatch is bit-exact by construction."""
    x = jnp.asarray(x, jnp.float32)
    if x.ndim == 0:
        return x
    return x.reshape(x.shape + (1,) * (out_ndim - x.ndim))


@dataclasses.dataclass(frozen=True)
class Backend:
    name: str
    fn: Callable
    formats: FrozenSet[Format]
    description: str = ""
    stream: Optional[StreamOps] = None


_REGISTRY: Dict[str, Backend] = {}


def register_backend(
    name: str,
    fn: Callable,
    formats=(Format.HFLEX, Format.BSR),
    description: str = "",
    overwrite: bool = False,
    stream: Optional[StreamOps] = None,
) -> Backend:
    """Register an SpMM execution strategy under ``name``.

    ``fn(A: SparseTensor, b, c, alpha, beta, **opts) -> jax.Array`` must be
    traceable (it runs under jit with traced alpha/beta).  ``stream``
    optionally provides the out-of-core K0-window streaming hooks
    (:class:`StreamOps`); backends without them reject streaming plans.
    """
    if name == "auto":
        raise ValueError("'auto' is reserved; use set_auto_policy to change "
                         "auto dispatch")
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {name!r} already registered "
                         f"(pass overwrite=True to replace)")
    be = Backend(name=name, fn=fn, formats=frozenset(formats),
                 description=description, stream=stream)
    _REGISTRY[name] = be
    return be


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown backend {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def list_backends() -> List[str]:
    return sorted(_REGISTRY)


def _default_auto_policy(a: SparseTensor, b, platform: Optional[str] = None) -> str:
    """Pick a backend from platform / format / density.

    * off-TPU the Pallas kernels run in interpret mode — the XLA ``jnp``
      path is the production one;
    * on TPU, BSR always goes to the tile kernel (``pallas``);
    * on TPU, HFLEX goes to the one-hot kernel (``pallas``) unless the
      matrix is dense-ish (density > 0.25 blows up slab padding), which
      falls back to ``jnp``.

    The dense width N does not choose the backend: it chooses the
    kernel's lane (:func:`hflex_lane`).  ``b`` is accepted (and may be
    None) so custom policies can inspect the operand.
    """
    platform = platform or jax.default_backend()
    if platform != "tpu":
        return "jnp"
    if a.format is Format.BSR or a.density <= 0.25:
        return "pallas"
    return "jnp"


_AUTO_POLICY = _default_auto_policy


def set_auto_policy(policy: Optional[Callable]) -> None:
    """Replace the ``auto`` dispatch heuristic (None restores the default).

    ``policy(a, b, platform=None) -> name`` must tolerate ``b=None``:
    resolution can happen before the dense operand exists (e.g. when
    SextansEngine builds a sharded executable for a future N)."""
    global _AUTO_POLICY
    _AUTO_POLICY = policy or _default_auto_policy


def resolve_backend(name: str, a: SparseTensor, b=None,
                    platform: Optional[str] = None,
                    n: Optional[int] = None) -> str:
    """Resolve a requested backend name ('auto' included) for tensor ``a``,
    validating format support.  ``b`` may be None (pre-operand resolution);
    when only the dense width is known, pass ``n=`` and a shape-only stub
    operand is synthesized so N-aware policies (and custom policies with the
    ``(a, b, platform)`` signature) still see it."""
    if name == "auto":
        if b is None and n is not None:
            b = jax.ShapeDtypeStruct((a.shape[1], int(n)), jnp.float32)
        name = _AUTO_POLICY(a, b, platform)
    be = get_backend(name)
    if a.format not in be.formats:
        raise ValueError(
            f"backend {name!r} does not support format {a.format}; "
            f"supported: {sorted(f.value for f in be.formats)}")
    return name


# ---------------------------------------------------------------------------
# Built-in backends
# ---------------------------------------------------------------------------


def _permute_rows_fwd(x: jax.Array, mb: int, tm: int) -> jax.Array:
    """true-row layout -> interleaved block layout (r -> (r%mb)*tm + r//mb).

    Operates on the trailing (rows, n) axes; any leading (group) axes pass
    through untouched.
    """
    lead, n = x.shape[:-2], x.shape[-1]
    x = x.reshape(*lead, tm, mb, n)
    return jnp.swapaxes(x, -3, -2).reshape(*lead, mb * tm, n)


def _permute_rows_inv(x: jax.Array, mb: int, tm: int) -> jax.Array:
    lead, n = x.shape[:-2], x.shape[-1]
    x = x.reshape(*lead, mb, tm, n)
    return jnp.swapaxes(x, -3, -2).reshape(*lead, tm * mb, n)


def _hflex_global_ids(d, xp=jnp):
    """Flat global (row, col) index arrays of every slab slot.

    Padding slots (val == 0) resolve to legal in-bounds coordinates: their
    local col is 0 so the global col is ``wi * k0 < k`` (ceil-div), and
    their local row 0 maps below ``m`` in both block layouts — so the flat
    path needs **no operand padding and no row permutation at all**.

    The single source of truth for the slab->global layout math: the
    unplanned ``jnp`` backend derives the ids in-trace (``xp=jnp``, integer
    iota math), and :func:`repro.sparse_api.plan` precomputes them once on
    the host (``xp=numpy``) — same expressions, so planned and unplanned
    indices can never drift apart.

    Batched payloads (leading group axis) broadcast through: the returned
    ids are ``(G, MB*NW*LW)`` — each member carries its own structure.
    """
    mb, nw = d.mb, d.nw
    rows = d.flat_slabs(xp.asarray(d.rows))
    cols = d.flat_slabs(xp.asarray(d.cols))
    # (MB, 1, 1)/(1, NW, 1) broadcast against the *trailing* slab axes, so
    # the same expressions serve 3-D and group-stacked 4-D payloads.
    bi = xp.arange(mb, dtype=xp.int32).reshape(mb, 1, 1)
    wi = xp.arange(nw, dtype=xp.int32).reshape(1, nw, 1)
    if d.interleaved:
        rows_g = rows * mb + bi            # undo block interleave
    else:
        rows_g = bi * d.tm + rows
    cols_g = cols + wi * d.k0
    lead = rows_g.shape[:-3]
    return rows_g.reshape(*lead, -1), cols_g.reshape(*lead, -1)


def _hflex_flat_exec(vals, cols_g, rows_g, b, c, alpha, beta, m):
    """The shared flat segment-sum SpMM body.

    Both the unplanned ``jnp`` backend and :class:`SpmmPlan.run` execute this
    exact op sequence (one gather, one ``jax.ops.segment_sum``, fused
    epilogue), so planned and unplanned results are bit-identical; the plan
    merely feeds precomputed index operands and a cached executable.

    With a leading group axis (``b`` of rank 3) the group is *folded into
    the segment dimension*: member ``g`` scatters to segments
    ``[g*M, (g+1)*M)`` and gathers from rows ``[g*K, (g+1)*K)`` of the
    flattened ``b`` — one big gather + one big segment-sum for the whole
    group (a single dispatch, no vmap).  Each member's segments receive
    exactly the contributions the unbatched call would in the same order,
    so results stay bit-identical per member.  ``alpha``/``beta`` may be
    ``(G,)`` per-member vectors on the group path — the epilogue is applied
    at ``(G, M, N)`` with the coefficients broadcast along the group axis,
    elementwise identical to the scalar epilogue per member.
    """
    if b.ndim == 3:
        g, k, n = b.shape
        goff = jnp.arange(g, dtype=jnp.int32)[:, None]
        rows_f = (rows_g + goff * m).reshape(-1)
        cols_f = (cols_g + goff * k).reshape(-1)
        bf = b.reshape(g * k, n)
        contrib = (vals.reshape(-1)[:, None].astype(jnp.float32)
                   * bf[cols_f].astype(jnp.float32))
        acc = jax.ops.segment_sum(contrib, rows_f,
                                  num_segments=g * m).reshape(g, m, n)
        return (_ab_expand(alpha, 3) * acc
                + _ab_expand(beta, 3) * c.astype(jnp.float32)).astype(b.dtype)
    contrib = vals[:, None].astype(jnp.float32) * b[cols_g].astype(jnp.float32)
    acc = jax.ops.segment_sum(contrib, rows_g, num_segments=m)
    return (alpha * acc + beta * c.astype(jnp.float32)).astype(b.dtype)


def _hflex_jnp(a: SparseTensor, b, c, alpha, beta):
    """XLA segment-sum path on the slab format — no N/K/M padding, no row
    permutation: slab slots scatter straight to true output rows.  Batched
    tensors (leading group axis, ``b`` of shape (G, K, N)) execute as one
    vmapped call."""
    d = a.data
    rows_g, cols_g = _hflex_global_ids(d)
    lead = d.vals.shape[:-4]
    return _hflex_flat_exec(d.vals.reshape(*lead, -1), cols_g, rows_g,
                            b, c, alpha, beta, d.m)


def _hflex_pallas(a: SparseTensor, b, c, alpha, beta, *, tn, interpret):
    d = a.data
    m, k, tm, k0, mb, nw = d.m, d.k, d.tm, d.k0, d.mb, d.nw
    n = b.shape[-1]
    tn = tn or hflex_lane(n)
    npad = cdiv(n, tn) * tn
    lead_pad = ((0, 0),) if d.batch is not None else ()
    bp = jnp.pad(b, (*lead_pad, (0, nw * k0 - k), (0, npad - n)))
    cp = jnp.pad(c, (*lead_pad, (0, mb * tm - m), (0, npad - n)))
    if d.interleaved:
        cp = _permute_rows_fwd(cp, mb, tm)
    out = sextans_spmm_pallas(
        d.vals, d.cols, d.rows, d.q, bp, cp, alpha, beta,
        tm=tm, k0=k0, tn=tn, interpret=interpret,
    )
    if d.interleaved:
        out = _permute_rows_inv(out, mb, tm)
    return out[..., :m, :n]


# -- out-of-core streaming hooks (K0-window chunk accumulation) -------------


def _hflex_jnp_stream_init(a: SparseTensor, n: int, **_unused):
    return jnp.zeros((a.shape[0], n), jnp.float32)


def _hflex_jnp_stream_step(a_chunk: SparseTensor, b_chunk, acc, **_unused):
    """Scatter-add one window-chunk's contributions into the carried acc.

    ``acc.at[rows].add`` applies the chunk's updates *onto the carried
    values* in slot order, so chaining chunks reproduces the exact per-row
    add sequence of the resident path's single ``segment_sum`` over all
    slots — bit-identical accumulation (a partial-sum-per-chunk scheme
    would not be: float addition is non-associative).
    """
    d = a_chunk.data
    rows_g, cols_g = _hflex_global_ids(d)
    contrib = (d.vals.reshape(-1)[:, None].astype(jnp.float32)
               * b_chunk.astype(jnp.float32)[cols_g])
    # 'drop' lets a streaming plan pad the tail chunk with inert windows
    # whose rows point out of bounds; real slots always land in [0, M).
    return acc.at[rows_g].add(contrib, mode="drop")


def _hflex_jnp_stream_collect(a: SparseTensor, acc, n: int, **_unused):
    return acc


def _hflex_pallas_stream_init(a: SparseTensor, n: int, *, tn=None,
                              **_unused):
    d = a.data
    tn = tn or hflex_lane(n)
    npad = cdiv(n, tn) * tn
    return jnp.zeros((d.mb * d.tm, npad), jnp.float32)


def _hflex_pallas_stream_step(a_chunk: SparseTensor, b_chunk, acc, *,
                              tn=None, interpret=None, **_unused):
    """One accumulate-mode kernel launch over the chunk's NW grid.

    The carried acc stays in kernel layout (padded rows, interleave
    permutation) between dispatches; the kernel seeds its VMEM scratch from
    it and emits the raw f32 accumulator — the same add sequence a full-NW
    launch performs, split at chunk boundaries.  ``b_chunk`` carries the
    column tile's full width, so the lane is the one ``init`` chose.
    """
    d = a_chunk.data
    npad = acc.shape[-1]
    kc, nc = b_chunk.shape
    bp = jnp.pad(b_chunk, ((0, d.nw * d.k0 - kc), (0, npad - nc)))
    return sextans_spmm_pallas(
        d.vals, d.cols, d.rows, d.q, bp, acc,
        tm=d.tm, k0=d.k0, tn=tn or hflex_lane(nc),
        interpret=interpret, accumulate=True,
    )


def _hflex_pallas_stream_collect(a: SparseTensor, acc, n: int, **_unused):
    d = a.data
    if d.interleaved:
        acc = _permute_rows_inv(acc, d.mb, d.tm)
    return acc[..., :a.shape[0], :n]


_JNP_STREAM = StreamOps(init=_hflex_jnp_stream_init,
                        step=_hflex_jnp_stream_step,
                        collect=_hflex_jnp_stream_collect)
_PALLAS_STREAM = StreamOps(init=_hflex_pallas_stream_init,
                           step=_hflex_pallas_stream_step,
                           collect=_hflex_pallas_stream_collect)


def _bsr_raw_jnp(a: SparseTensor, b):
    """A @ b for BSR: (b^T @ A^T)^T on the stored transposed-weight layout.

    A stacked group (``a.batch``) takes ``b`` of shape ``(G, K, N)``: the
    group folds into the scatter/contraction batch dimension of
    :func:`bsr_matmul_ref_batched` — ONE XLA call, bit-identical per
    member.  Padding slots scatter out of range (``bcol == NBF``) and are
    dropped; their blocks are zero anyway.
    """
    w = a.data
    m, k = a.shape
    if a.batch is not None:
        xb = jnp.pad(b, ((0, 0), (0, w.k - k), (0, 0)))
        xb = xb.transpose(0, 2, 1)                   # (G, N, K')
        y = bsr_matmul_ref_batched(xb, w.blocks, w.brow, _bcol_batched(w),
                                   w.k // w.tk, w.f // w.tf)  # (G, N, M')
        return y.transpose(0, 2, 1)[:, :m]
    xb = jnp.pad(b, ((0, w.k - k), (0, 0))).T        # (N, K')
    bcol = jnp.searchsorted(
        w.indptr, jnp.arange(w.blocks.shape[0]), side="right") - 1
    y = bsr_matmul_ref(xb, w.blocks, w.brow, bcol,
                       w.k // w.tk, w.f // w.tf)     # (N, M')
    return y.T[:m]


def _bsr_jnp(a: SparseTensor, b, c, alpha, beta):
    raw = _bsr_raw_jnp(a, b).astype(jnp.float32)
    return (_ab_expand(alpha, raw.ndim) * raw
            + _ab_expand(beta, raw.ndim) * c.astype(jnp.float32)
            ).astype(b.dtype)


def _bsr_pallas(a: SparseTensor, b, c, alpha, beta, *, tn, interpret):
    w = a.data
    m, k = a.shape
    n = b.shape[-1]
    tn = tn or bsr_row_tile(n)
    npad = cdiv(n, tn) * tn
    if a.batch is not None:
        xb = jnp.pad(b, ((0, 0), (0, w.k - k), (0, 0)))
        xb = xb.transpose(0, 2, 1)                   # (G, N, K')
        xb = jnp.pad(xb, ((0, 0), (0, npad - n), (0, 0)))
        y = bsr_matmul_pallas_batched(xb, w.blocks, w.brow, w.indptr,
                                      tb=tn, tk=w.tk, tf=w.tf,
                                      interpret=interpret)
        raw = y[:, :n].transpose(0, 2, 1)[:, :m].astype(jnp.float32)
        return (_ab_expand(alpha, 3) * raw
                + _ab_expand(beta, 3) * c.astype(jnp.float32)
                ).astype(b.dtype)
    xb = jnp.pad(b, ((0, w.k - k), (0, 0))).T        # (N, K')
    xb = jnp.pad(xb, ((0, npad - n), (0, 0)))
    y = bsr_matmul_pallas(xb, w.blocks, w.brow, w.indptr,
                          tb=tn, tk=w.tk, tf=w.tf, interpret=interpret)
    raw = y[:n].T[:m].astype(jnp.float32)            # (M, N)
    return (alpha * raw + beta * c.astype(jnp.float32)).astype(b.dtype)


#: rows of one tile of the ragged grouped product (:func:`bsr_ragged`)
RAGGED_TILE = 128


def _bcol_batched(w):
    """Block column of every stored slot of a stacked BSR payload
    ``(G, NB)``; padding slots get ``NBF``, out of range."""
    nb = w.blocks.shape[1]
    return jax.vmap(lambda ip: jnp.searchsorted(ip, jnp.arange(nb),
                                                side="right") - 1)(w.indptr)


def bsr_ragged(backend: str, a: SparseTensor, x, te, used, *,
               interpret=None, **_unused):
    """Ragged grouped product of a stacked BSR tensor ``a`` (E members of
    logical shape (M, K)): ``y[r] = x[r] @ A[te[r // TB]]^T`` for the
    ``(R, K)`` rows ``x``, laid out by expert in tiles of
    ``TB = RAGGED_TILE`` rows; row tiles from ``used[0]`` on come back
    zero.  Returns ``(R, M)``.  ``"pallas"`` runs the kernel's ragged
    mode; ``"jnp"`` is its XLA twin (each tile against its member's dense
    weight)."""
    bump_trace()
    w = a.data
    m, k = a.shape
    xb = jnp.pad(x, ((0, 0), (0, w.k - k)))              # (R, K')
    if backend == "pallas":
        y = bsr_matmul_pallas_ragged(xb, w.blocks, w.brow, w.indptr, te,
                                     used, tb=RAGGED_TILE, tk=w.tk,
                                     tf=w.tf, interpret=interpret)
        return y[:, :m]
    if backend != "jnp":
        raise ValueError(f"no ragged BSR product on backend {backend!r}; "
                         f"use 'pallas' or 'jnp'")
    dense = bsr_dense_batched(w.blocks, w.brow, _bcol_batched(w),
                              w.k // w.tk, w.f // w.tf)  # (E, K', M')
    nt = x.shape[0] // RAGGED_TILE
    xt = xb.reshape(nt, RAGGED_TILE, w.k).astype(jnp.float32)
    y = jax.lax.dot_general(xt, dense[te], (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    live = (jnp.arange(nt) < used[0])[:, None, None]
    return jnp.where(live, y, 0.0).reshape(x.shape[0], w.f)[:, :m].astype(
        x.dtype)


def _backend_jnp(a, b, c, alpha, beta, **_unused):
    bump_trace()
    if a.format is Format.HFLEX:
        return _hflex_jnp(a, b, c, alpha, beta)
    return _bsr_jnp(a, b, c, alpha, beta)


def _backend_pallas(a, b, c, alpha, beta, *, tn=None, interpret=None,
                    **_unused):
    bump_trace()
    if a.format is Format.HFLEX:
        return _hflex_pallas(a, b, c, alpha, beta, tn=tn,
                             interpret=interpret)
    return _bsr_pallas(a, b, c, alpha, beta, tn=tn, interpret=interpret)


register_backend(
    "pallas", _backend_pallas,
    formats=(Format.HFLEX, Format.BSR),
    description="the format's Pallas kernel: Sextans one-hot kernel "
                "(HFLEX, lane from N) / BSR tile kernel",
    stream=_PALLAS_STREAM)
register_backend(
    "jnp", _backend_jnp,
    formats=(Format.HFLEX, Format.BSR),
    description="XLA segment-sum/einsum path (reference, CPU production, "
                "autodiff)",
    stream=_JNP_STREAM)
