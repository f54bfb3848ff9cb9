"""Sextans SpMM as a Pallas TPU kernel.

TPU re-derivation of the paper's streaming dataflow (DESIGN.md §2):

* the K dimension is windowed (K0); each grid step streams one B window
  (K0 × TN) HBM→VMEM — the BRAM window of the paper;
* the C tile (TM × TN, fp32) lives in a VMEM scratch accumulator across
  all windows — the URAM scratchpad of the paper;
* packed non-zero slabs (vals/cols/rows) arrive lane-major: each
  (block, window) slab is an ``(R, L)`` tile (``L`` = 128 lanes, see
  :func:`repro.core.hflex.slab_lanes`), and the kernel walks it one
  ``L``-wide row of non-zeros per trip.  The scatter ``c[row] += val *
  b[col]`` is a one-hot MXU matmul (TM × L) @ (L × TN) with the values
  folded into the one-hot — this *is* the resolution of the paper's RAW
  hazard on TPU (no D-cycle distance exists to schedule around);
* the per-(block, window) non-zero count matrix ``q`` is a scalar-prefetch
  operand driving data-dependent ``fori_loop`` trip counts — the paper's
  HFlex pointer list Q;
* the α/β epilogue is fused into the last window step (the paper's CompC
  module, without the extra C stream). α/β arrive as a *traced* (1, 2)
  SMEM operand, not compile-time constants: one compiled executable
  serves any epilogue scaling (HFlex — the hardware reads α/β from
  registers, it is not re-synthesized per scaling).

B rows are gathered as a second one-hot matmul, the form that lowers
through Mosaic (a vector row gather does not).  Once per non-empty grid
step the window is transposed and split exactly into three bfloat16
parts, ``x = hi + mid + lo`` (:func:`bf16_split3`), stacked into one
(3·TN × K0) operand.  Each trip contracts it against a bfloat16 (K0 × L)
one-hot in ONE default-precision MXU pass with float32 accumulation:
every output is ``1 · part`` plus exact zeros, so ``hi + mid + lo``
summed in float32 is ``bwin[c]`` bit for bit.

The scatter matmul runs at ``Precision.HIGHEST``: it forms the real
products ``v · b`` of float32 operands, which a default-precision TPU
matmul would round to bfloat16.

Grid: (MB, NT, NW), windows innermost so the output block and accumulator
stay resident while K streams — the exact loop nest of paper Algorithm 1
with (i ↔ NT, j ↔ NW, p·q ↔ intra-kernel parallelism).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._compat import resolve_interpret as _resolve_interpret

__all__ = ["sextans_spmm_pallas"]

_HI = jax.lax.Precision.HIGHEST


def _slab_row(ref, i, lead: int, tile_rows: int):
    """Row ``i`` (a ``(1, L)`` vector) of the ``(R, L)`` slab block ``ref``.

    Mosaic only loads sublane-aligned tiles at a dynamic offset, so the
    aligned ``(tile_rows, L)`` tile holding row ``i`` is loaded and the row
    is selected with a mask and a sum over the tile (exact: the other terms
    are zeros).  ``lead`` is the number of leading size-1 block axes."""
    idx = (0,) * lead
    if tile_rows == 1:
        return ref[idx + (pl.ds(i, 1), slice(None))]
    base = pl.multiple_of((i // tile_rows) * tile_rows, tile_rows)
    tile = ref[idx + (pl.ds(base, tile_rows), slice(None))]
    sel = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0) == i % tile_rows
    return jnp.sum(jnp.where(sel, tile, jnp.zeros_like(tile)), axis=0,
                   keepdims=True)


_BF16_BITS = -65536          # 0xFFFF0000: sign, exponent, 7 mantissa bits


def _bf16_head(x):
    """``x`` with its low 16 bits cleared: a float32 that bfloat16 holds
    exactly (truncated, so it never rounds up to infinity)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32) & _BF16_BITS
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def bf16_split3(x):
    """Split float32 ``x`` into bfloat16 ``(hi, mid, lo)`` with
    ``(hi + mid) + lo == x`` exactly in float32.

    ``hi`` holds the top 8 significant bits, ``mid`` the next 8 of the
    remainder and ``lo`` the rest (at most 8 bits), all with the sign of
    ``x``.  Exact for zero and every finite ``|x| >= 2**-103``; below
    that a part falls under the smallest normal and is flushed."""
    hi = _bf16_head(x)
    rem = x - hi                 # the low 16 bits of x: exact
    mid = _bf16_head(rem)
    lo = rem - mid               # exact, at most 8 significant bits
    return tuple(p.astype(jnp.bfloat16) for p in (hi, mid, lo))


def _scatter_trip(acc, v, c, r, bwin, *, tm: int):
    """One trip of ``L`` packed non-zeros: ``acc[r] += v * bwin[c]``.

    ``v``/``c``/``r`` are ``(1, L)`` rows and ``bwin`` is the stacked
    (3·W, K0) bfloat16 split of the window's transpose, so the gathered
    rows come out transposed, (W, L).  The row scatter is a (TM × L)
    one-hot matmul against them with the values folded into the one-hot,
    so every product ``v * b`` is formed once, exactly as the flat
    reference forms it."""
    lanes = v.shape[-1]
    k0 = bwin.shape[1]
    w = bwin.shape[0] // 3
    oh_c = (jax.lax.broadcasted_iota(jnp.int32, (k0, lanes), 0)
            == c).astype(jnp.bfloat16)                      # (K0, L)
    parts = jnp.dot(bwin, oh_c,
                    preferred_element_type=jnp.float32)     # (3W, L)
    brows = (parts[:w] + parts[w:2 * w]) + parts[2 * w:]    # (W, L)
    oh_rv = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (tm, lanes), 0) == r,
        v.astype(jnp.float32), 0.0)                         # (TM, L)
    return acc + jax.lax.dot_general(
        oh_rv, brows, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HI)


def _kernel(
    q_ref,            # ([G *] MB * NW,) int32, scalar prefetch (SMEM)
    vals_ref,         # ([1,] 1, 1, R, L) f32
    cols_ref,         # ([1,] 1, 1, R, L) i32
    rows_ref,         # ([1,] 1, 1, R, L) i32
    b_ref,            # ([1,] K0, TN)
    cin_ref,          # ([1,] TM, TN)
    ab_ref,           # ([G,] 2) f32 in SMEM: [alpha, beta] (traced
                      # epilogue; a batched run reads its group's row)
    out_ref,          # ([1,] TM, TN)
    acc_ref,          # VMEM scratch (TM, TN) f32
    *,
    tm: int,
    mb: int,
    nw: int,
    tile_rows: int,
    batched: bool,
    accumulate: bool,
):
    # Batched execution prepends a group dimension to the grid: every block
    # operand gains a leading size-1 axis and the program ids shift by one.
    # The per-(group, block, tile, window) body is otherwise identical — a
    # whole group of bucket-mates runs as ONE kernel launch.
    off = 1 if batched else 0
    w = pl.program_id(2 + off)

    def _tile(ref):
        return ref[0] if batched else ref[...]

    @pl.when(w == 0)
    def _init():
        if accumulate:
            # Out-of-core streaming: seed from the carried f32 accumulator
            # (c_in doubles as acc-in), so a chain of window-chunk dispatches
            # performs the exact add sequence of one full-NW launch.
            acc_ref[...] = _tile(cin_ref).astype(jnp.float32)
        else:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    # (program ids are read here, outside the pl.when bodies: the
    # interpreter only substitutes them at the kernel's top level)
    g = pl.program_id(0) if batched else 0
    m = g * mb + pl.program_id(off)
    count = q_ref[m * nw + w]                 # real (chunk-ceiled) nnz here

    # Empty-slab skip: a (block, window) pair with zero non-zeros (sparsity
    # structure, known from the prefetched pointer matrix q) contributes
    # nothing — skip the accumulate entirely.  The grid still visits the
    # step (the window stream is the ``arbitrary`` innermost dimension)
    # but executes no vector work.
    @pl.when(count > 0)
    def _process_window():
        lanes = vals_ref.shape[-1]
        bwin = _tile(b_ref).astype(jnp.float32)  # (K0, TN) window in VMEM
        # the gather's MXU operand, once per step: (3·TN, K0) bf16
        bwin = jnp.concatenate(bf16_split3(bwin.T), axis=0)
        lead = 3 if batched else 2

        def body(i, acc):
            v, c, r = (_slab_row(ref, i, lead, tile_rows)
                       for ref in (vals_ref, cols_ref, rows_ref))
            return _scatter_trip(acc, v, c, r, bwin, tm=tm)

        trips = (count + lanes - 1) // lanes
        acc_ref[...] = jax.lax.fori_loop(0, trips, body, acc_ref[...])

    @pl.when(w == nw - 1)
    def _epilogue():
        if accumulate:
            # No epilogue: emit the raw f32 accumulator for the next chunk
            # dispatch (alpha/beta are applied once, after the last chunk).
            res = acc_ref[...].astype(out_ref.dtype)
        else:
            alpha = ab_ref[g, 0]
            beta = ab_ref[g, 1]
            res = (
                alpha * acc_ref[...]
                + beta * _tile(cin_ref).astype(jnp.float32)
            ).astype(out_ref.dtype)
        if batched:
            out_ref[0] = res
        else:
            out_ref[...] = res


def _epilogue_operand(alpha, beta, g_sz: Optional[int]):
    """The SMEM epilogue operand: ``(1, 2)`` for one matrix, ``(G, 2)`` —
    one row per member — for a group.  Scalars broadcast, so uniform and
    mixed (scalar/vector) epilogues share one signature."""
    a_f = jnp.asarray(alpha, jnp.float32)
    b_f = jnp.asarray(beta, jnp.float32)
    rows = 1 if g_sz is None else g_sz
    return jnp.stack([jnp.broadcast_to(a_f, (rows,)),
                      jnp.broadcast_to(b_f, (rows,))], axis=-1)


def _slab_specs(batched: bool, r: int, lanes: int):
    """Block specs of the three slab operands: one (block, window) slab
    ``(R, L)`` per grid step, addressed by the [group and] block program
    ids and the window id — the last grid index, which the index map sees
    just before the scalar-prefetch ref."""
    if batched:
        spec = pl.BlockSpec((1, 1, 1, r, lanes),
                            lambda g, m, *rest: (g, m, rest[-2], 0, 0))
    else:
        spec = pl.BlockSpec((1, 1, r, lanes),
                            lambda m, *rest: (m, rest[-2], 0, 0))
    return [spec] * 3


@functools.partial(
    jax.jit,
    static_argnames=("tm", "k0", "tn", "interpret", "accumulate"),
)
def sextans_spmm_pallas(
    vals: jax.Array,      # ([G,] MB, NW, R, L) f32
    cols: jax.Array,      # ([G,] MB, NW, R, L) i32
    rows: jax.Array,      # ([G,] MB, NW, R, L) i32
    q: jax.Array,         # ([G,] MB, NW) i32
    b: jax.Array,         # ([G,] NW*K0, N_pad)
    c_in: jax.Array,      # ([G,] MB*TM, N_pad)
    alpha: jax.Array = 1.0,   # traced scalar, or (G,) vector when batched
    beta: jax.Array = 0.0,    # traced scalar, or (G,) vector when batched
    *,
    tm: int,
    k0: int,
    tn: int = 128,
    interpret: Optional[bool] = None,
    accumulate: bool = False,
) -> jax.Array:
    """Raw kernel entry on pre-padded operands. Use repro.sparse_api.spmm for
    the user-facing API (handles packing, padding, permutation, autodiff).

    ``alpha``/``beta`` are *dynamic* operands (delivered to the kernel as a
    (1, 2) SMEM table): sweeping them re-uses one compiled executable.  In
    batched mode they may also be ``(G,)`` vectors — each group member's
    epilogue reads its own SMEM row, bit-identical to running that member
    alone with its scalar (α, β), which lets a serving scheduler fold
    mixed-epilogue requests into one group dispatch.
    ``interpret=None`` (the default) interprets only off-TPU — on a TPU the
    kernel compiles through Mosaic without the caller opting in.

    5-D ``vals`` (and correspondingly 3-D ``b``/``c_in``/``q``) select the
    *batched* grid ``(G, MB, NT, NW)``: G stacked bucket-mate matrices run
    as one kernel launch — the dispatch-amortization analogue of the
    paper's multi-channel HBM parallelism, with the group as the outermost
    parallel grid dimension.

    ``accumulate=True`` is the out-of-core streaming step: ``c_in`` is a
    carried f32 accumulator that seeds the VMEM scratch at window 0, the
    epilogue is suppressed, and the raw f32 accumulator is emitted.  A
    chain of such dispatches over consecutive K0-window chunks performs the
    exact per-(row, tile) add sequence of one full-NW launch (apply
    alpha/beta once on the final accumulator).

    A slab's trips walk whole ``L``-wide rows of non-zeros, ``ceil(q /
    L)`` of them; padding slots hold zeros and add nothing.
    """
    interpret = _resolve_interpret(interpret)
    if accumulate:
        assert c_in.dtype == jnp.float32, "accumulate carries an f32 acc"
    batched = vals.ndim == 5
    mb, nw, r, lanes = vals.shape[-4:]
    kpad, npad = b.shape[-2:]
    assert kpad == nw * k0, (kpad, nw, k0)
    assert npad % tn == 0
    nt = npad // tn
    g_sz = vals.shape[0] if batched else None
    if batched:
        assert q.shape == (g_sz, mb, nw)
        assert b.shape == (g_sz, kpad, npad)
        assert c_in.shape == (g_sz, mb * tm, npad)
    else:
        assert c_in.shape == (mb * tm, npad)
    ab = _epilogue_operand(alpha, beta, g_sz)
    # the whole (rows, 2) table sits in SMEM (a trivial window: Mosaic
    # blocks SMEM operands only whole)
    ab_spec = pl.BlockSpec(ab.shape, lambda *_: (0, 0),
                           memory_space=pltpu.SMEM)

    kern = functools.partial(
        _kernel, tm=tm, mb=mb, nw=nw, tile_rows=min(8, r), batched=batched,
        accumulate=accumulate,
    )
    out_dtype = jnp.float32 if accumulate else b.dtype
    if batched:
        grid = (g_sz, mb, nt, nw)
        in_specs = _slab_specs(True, r, lanes) + [
            pl.BlockSpec((1, k0, tn), lambda g, m, n, w, q_: (g, w, n)),
            pl.BlockSpec((1, tm, tn), lambda g, m, n, w, q_: (g, m, n)),
            ab_spec,
        ]
        out_specs = pl.BlockSpec((1, tm, tn), lambda g, m, n, w, q_: (g, m, n))
        out_shape = jax.ShapeDtypeStruct((g_sz, mb * tm, npad), out_dtype)
        semantics = ("parallel", "parallel", "parallel", "arbitrary")
    else:
        grid = (mb, nt, nw)
        in_specs = _slab_specs(False, r, lanes) + [
            pl.BlockSpec((k0, tn), lambda m, n, w, q_: (w, n)),
            pl.BlockSpec((tm, tn), lambda m, n, w, q_: (m, n)),
            ab_spec,
        ]
        out_specs = pl.BlockSpec((tm, tn), lambda m, n, w, q_: (m, n))
        out_shape = jax.ShapeDtypeStruct((mb * tm, npad), out_dtype)
        semantics = ("parallel", "parallel", "arbitrary")
    # q goes to SMEM flat: a 2-D SMEM array pads its minor dim to 128
    # words, which would cap MB*NW far below the 1 MiB of SMEM
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
        ),
        name="sextans_spmm",
    )(q.reshape(-1), vals, cols, rows, b, c_in, ab)
