"""Device-resident sparse tensors: one front-end over every packed format.

``SparseTensor`` is the single user-facing sparse-matrix abstraction.  It is
a registered JAX pytree (survives ``jax.jit`` / ``jax.grad`` / sharding
boundaries) that wraps one of the packed device formats behind a
:class:`Format` tag:

* ``Format.HFLEX`` — the paper's HFlex slab packing (:class:`PackedSpMM`):
  per-(TM-row-block, K0-window) non-zero slabs plus the scalar-prefetched
  pointer matrix ``q``.  The general-purpose unstructured-sparsity format.
* ``Format.BSR``   — block-sparse rows (:class:`BsrWeight`): (TK x TF) dense
  tiles feeding the MXU, for pruned model weights.

Both execute through one entry point, :func:`repro.sparse_api.spmm`
(``C = alpha * A @ B + beta * C``), dispatched through the backend registry
(:mod:`repro.sparse_api.backends`).

Orientation convention for BSR: a ``SparseTensor`` always denotes the *left*
operand ``A`` of shape ``(M, K)``.  Internally the BSR payload stores
``A^T`` in the weight layout of :func:`pack_bsr_weight` (blocks sorted by
output tile), because the BSR kernel computes ``x @ W``; the spmm backends
apply ``A @ B = (B^T @ A^T)^T``.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Any, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hflex import lane_major, pack_block_slabs, slab_lw
from repro.core.partition import cdiv
from repro.core.sparse import SparseMatrix
from repro.core.sparse import from_dense as _coo_from_dense

__all__ = [
    "Format",
    "PackedSpMM",
    "BsrWeight",
    "SparseTensor",
    "pack_hflex",
    "pack_bsr_weight",
    "from_sparse_matrix",
    "from_coo",
    "from_dense",
    "from_bsr_weight",
    "stack_hflex",
    "stack_bsr",
    "bucket_block_count",
    "repad_lw",
]


class Format(enum.Enum):
    """Packed device format of a :class:`SparseTensor`."""

    HFLEX = "hflex"   # Sextans slab packing — unstructured sparsity
    BSR = "bsr"       # block-sparse tiles — structured (pruned-weight) sparsity


# ---------------------------------------------------------------------------
# Packed payloads (registered pytrees)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PackedSpMM:
    """Device-resident HFlex-packed sparse matrix (slab format).

    Slab arrays are ``(MB, NW, R, L)`` for a single matrix: slab ``(b, w)``
    holds ``LW = R * L`` slots as ``R`` rows of ``L`` lanes
    (:func:`repro.core.hflex.lane_major`; the flat slot order is the
    row-major order).  They carry a *leading group axis* ``(G, MB, NW, R,
    L)`` when ``G`` bucket-mates have been stacked into one dispatch
    (:func:`stack_hflex`); ``q``/``nse`` gain the same leading axis.  All
    geometry/shape statics are shared by the group members.
    """

    vals: jax.Array  # ([G,] MB, NW, R, L) f32
    cols: jax.Array  # ([G,] MB, NW, R, L) i32
    rows: jax.Array  # ([G,] MB, NW, R, L) i32
    q: jax.Array     # ([G,] MB, NW) i32, chunk-ceiled counts (kernel trips)
    nse: jax.Array   # ([G,] MB, NW) i32, true counts (autodiff padding mask)
    m: int = dataclasses.field(metadata=dict(static=True))
    k: int = dataclasses.field(metadata=dict(static=True))
    tm: int = dataclasses.field(metadata=dict(static=True))
    k0: int = dataclasses.field(metadata=dict(static=True))
    chunk: int = dataclasses.field(metadata=dict(static=True))
    interleaved: bool = dataclasses.field(metadata=dict(static=True))
    nnz: int = dataclasses.field(metadata=dict(static=True))

    @property
    def batch(self) -> Optional[int]:
        """Group size G for stacked payloads, None for a single matrix."""
        return self.vals.shape[0] if self.vals.ndim == 5 else None

    @property
    def mb(self) -> int:
        return self.vals.shape[-4]

    @property
    def nw(self) -> int:
        return self.vals.shape[-3]

    @property
    def lw(self) -> int:
        return self.vals.shape[-2] * self.vals.shape[-1]

    def flat_slabs(self, x):
        """A slab array viewed as ``([G,] MB, NW, LW)`` (flat slot axis)."""
        return x.reshape(*x.shape[:-2], self.lw)

    def valid_slots(self) -> jax.Array:
        """Mask of the real (non-padding) slots, shaped like ``vals``: slot
        ``r * L + l`` of slab ``(b, w)`` is real below ``nse[b, w]``."""
        shape, nd = self.vals.shape, self.vals.ndim
        pos = (jax.lax.broadcasted_iota(jnp.int32, shape, nd - 2) * shape[-1]
               + jax.lax.broadcasted_iota(jnp.int32, shape, nd - 1))
        return pos < jnp.asarray(self.nse)[..., None, None]

    @property
    def geometry(self) -> Tuple[int, int, int]:
        return (self.mb, self.nw, self.lw)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BsrWeight:
    """Block-sparse (K, F) weight: nonzero (TK, TF) tiles, CSC over F tiles.

    Arrays are ``(NB, TK, TF)`` / ``(NB,)`` / ``(NF+1,)`` for a single
    weight, or carry a *leading group axis* ``(G, NB, TK, TF)`` /
    ``(G, NB)`` / ``(G, NF+1)`` when ``G`` same-geometry weights have been
    stacked into one dispatch (:func:`stack_bsr`).  NB is then the padded
    block-count bucket shared by the group; member ``g`` truly stores
    ``indptr[g, -1] <= NB`` blocks and its padded slots hold zero blocks
    (the pointer walk never reaches them — they exist only so the group
    shares one executable, like HFLEX's LW bucket).
    """

    blocks: jax.Array   # ([G,] NB, TK, TF)
    brow: jax.Array     # ([G,] NB) i32
    indptr: jax.Array   # ([G,] NF+1) i32
    k: int = dataclasses.field(metadata=dict(static=True))
    f: int = dataclasses.field(metadata=dict(static=True))
    tk: int = dataclasses.field(metadata=dict(static=True))
    tf: int = dataclasses.field(metadata=dict(static=True))

    @property
    def batch(self) -> Optional[int]:
        """Group size G for stacked payloads, None for a single weight."""
        return self.blocks.shape[0] if self.blocks.ndim == 4 else None

    @property
    def nb(self) -> int:
        """Stored block count (the padded bucket for stacked payloads)."""
        return self.blocks.shape[-3]

    @property
    def density(self) -> float:
        nbk, nbf = self.k // self.tk, self.f // self.tf
        return self.nb / float(max(nbk * nbf, 1))


# ---------------------------------------------------------------------------
# Host-side packing
# ---------------------------------------------------------------------------


def pack_hflex(
    a: SparseMatrix,
    tm: int = 128,
    k0: int = 4096,
    chunk: int = 8,
    interleave: bool = True,
    bucket: bool = False,
    device: bool = True,
) -> PackedSpMM:
    """Host preprocessing -> packed slab arrays. ``bucket=True`` rounds LW up
    to a power of two so matrices of similar density share one compiled
    kernel (HFlex compile-cache).

    ``device=False`` returns **host-resident** (numpy) slab leaves instead
    of committing the payload to the default device: worker threads can
    pack without touching the device, and a payload larger than device
    memory never OOMs at pack time — the plan tier
    (:class:`repro.sparse_api.SpmmPlan` / ``StreamingPlan``) owns the
    single ``device_put`` at dispatch.  The packed *values* are identical
    either way, so downstream results are bit-identical.
    """
    slabs = pack_block_slabs(a, tm=tm, k0=k0, chunk=chunk,
                             interleave=interleave, bucket=bucket)
    nse = slabs.nse if slabs.nse is not None else np.minimum(
        (slabs.vals != 0).sum(-1), slabs.q)
    conv = jnp.asarray if device else np.asarray
    return PackedSpMM(
        vals=conv(lane_major(slabs.vals)),
        cols=conv(lane_major(slabs.cols)),
        rows=conv(lane_major(slabs.rows)),
        q=conv(slabs.q),
        nse=conv(np.asarray(nse, np.int32)),
        m=slabs.m, k=slabs.k, tm=tm, k0=k0, chunk=chunk,
        interleaved=bool(getattr(slabs, "interleaved", interleave and slabs.mb > 1)),
        nnz=slabs.nnz,
    )


def pack_bsr_weight(
    w: np.ndarray, tk: int = 128, tf: int = 128, threshold: float = 0.0,
    device: bool = True,
) -> BsrWeight:
    """Pack a dense (K, F) weight into BSR, dropping all-(|w|<=threshold)
    blocks. Blocks sorted by block-col then block-row (CSC-ish over output
    tiles, matching the kernel's pointer walk).  ``device=False`` keeps the
    tile payload host-resident (numpy leaves) — the BSR twin of
    ``pack_hflex(device=False)``."""
    w = np.asarray(w)
    k, f = w.shape
    if k % tk or f % tf:
        raise ValueError("weight dims must be multiples of the block tile")
    nbk, nbf = k // tk, f // tf
    wb = w.reshape(nbk, tk, nbf, tf).transpose(0, 2, 1, 3)  # (nbk, nbf, tk, tf)
    keep = np.abs(wb).max(axis=(2, 3)) > threshold          # (nbk, nbf)
    br, bc = np.nonzero(keep)
    order = np.lexsort((br, bc))
    br, bc = br[order], bc[order]
    blocks = wb[br, bc]                                     # (NB, tk, tf)
    indptr = np.zeros(nbf + 1, np.int32)
    np.cumsum(np.bincount(bc, minlength=nbf), out=indptr[1:])
    conv = jnp.asarray if device else np.asarray
    return BsrWeight(
        blocks=conv(np.ascontiguousarray(blocks, np.float32)),
        brow=conv(br.astype(np.int32)),
        indptr=conv(indptr),
        k=k, f=f, tk=tk, tf=tf,
    )


# ---------------------------------------------------------------------------
# SparseTensor
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SparseTensor:
    """Format-agnostic device sparse matrix ``A`` of shape ``(M, K)``.

    Execute ``C = alpha * A @ B + beta * C`` via :func:`repro.sparse_api.spmm`
    or simply ``A @ B``.  The op is differentiable (cotangents flow to ``B``,
    ``C`` and the packed non-zero values), and ``alpha``/``beta`` are traced
    scalars — one compiled executable serves any epilogue.
    """

    data: Any   # PackedSpMM (HFLEX) | BsrWeight storing A^T (BSR)
    format: Format = dataclasses.field(metadata=dict(static=True))
    shape: Tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    # stored elements inside the logical (M, K) bounds; None -> derive from
    # the payload (BSR payloads may carry tile-padding cells outside bounds)
    nse: Optional[int] = dataclasses.field(default=None,
                                           metadata=dict(static=True))

    # -- structure ----------------------------------------------------------

    @property
    def m(self) -> int:
        return self.shape[0]

    @property
    def k(self) -> int:
        return self.shape[1]

    @property
    def batch(self) -> Optional[int]:
        """Group size G of a stacked (batched) tensor, None if unbatched.

        A batched tensor holds G same-geometry matrices behind one leading
        payload axis (:func:`stack_hflex` / :func:`stack_bsr`); ``shape``
        stays the per-member logical ``(M, K)`` and ``spmm`` takes ``b`` of
        shape ``(G, K, N)``.
        """
        return self.data.batch

    @property
    def nnz(self) -> int:
        if self.nse is not None:
            return self.nse
        if self.format is Format.HFLEX:
            return self.data.nnz
        d = self.data
        tk, tf = d.tk, d.tf
        if d.blocks.ndim == 4:
            # member g truly stores indptr[g, -1] blocks; padded slots are
            # zero filler and do not count
            return int(np.asarray(d.indptr[..., -1]).sum()) * tk * tf
        return int(d.nb * tk * tf)

    @property
    def density(self) -> float:
        m, k = self.shape
        cells = m * k * (self.batch or 1)
        return self.nnz / float(max(cells, 1))

    @property
    def geometry(self) -> Tuple[int, ...]:
        """Bucketable executable geometry (what forces a recompile)."""
        if self.format is Format.HFLEX:
            d = self.data
            return (*d.geometry, d.tm, d.k0, d.chunk, d.interleaved)
        d = self.data
        return (d.nb, d.k, d.f, d.tk, d.tf)

    @property
    def nbytes(self) -> int:
        """Total bytes of the packed device payload (every array leaf).

        This is what the out-of-core streaming threshold compares against a
        device-memory budget: a matrix whose ``nbytes`` exceeds the budget
        cannot be resident and must stream K0-window chunks instead
        (``plan(..., device_bytes=)``).
        """
        leaves = jax.tree_util.tree_leaves(self.data)
        return int(sum(x.nbytes for x in leaves))

    @property
    def on_host(self) -> bool:
        """True when every packed payload leaf is host-resident (numpy) —
        the product of ``pack_hflex(device=False)`` /
        ``stack_hflex(device=False)``.  Host-resident tensors are safe to
        build on worker threads and never pin device memory; the plan tier
        performs the single ``device_put`` at dispatch."""
        return all(isinstance(x, np.ndarray)
                   for x in jax.tree_util.tree_leaves(self.data))

    def to_device(self) -> "SparseTensor":
        """Commit a host-resident payload to the default device (one
        transfer per leaf); a no-op for already-device tensors."""
        if not self.on_host:
            return self
        data = jax.tree_util.tree_map(jnp.asarray, self.data)
        return dataclasses.replace(self, data=data)

    @property
    def values(self) -> jax.Array:
        """The differentiable non-zero payload (vals slab / BSR blocks)."""
        return self.data.vals if self.format is Format.HFLEX else self.data.blocks

    def with_values(self, v: jax.Array) -> "SparseTensor":
        """Same sparsity structure, new non-zero values (pruned-layer update)."""
        if self.format is Format.HFLEX:
            return dataclasses.replace(
                self, data=dataclasses.replace(self.data, vals=v))
        return dataclasses.replace(
            self, data=dataclasses.replace(self.data, blocks=v))

    # -- group (batch) structure -------------------------------------------

    def __getitem__(self, g: int) -> "SparseTensor":
        """Member ``g`` of a stacked (batched) tensor (host-side op)."""
        gsz = self.batch
        if gsz is None:
            raise TypeError("indexing requires a batched (stacked) tensor")
        g = int(g)
        if not -gsz <= g < gsz:
            raise IndexError(f"group index {g} out of range for batch {gsz}")
        d = self.data
        if self.format is Format.BSR:
            nb_g = int(np.asarray(d.indptr[g, -1]))
            data_g = dataclasses.replace(
                d, blocks=d.blocks[g, :nb_g], brow=d.brow[g, :nb_g],
                indptr=d.indptr[g])
            # stored cells inside the logical (M, K) bounds, recomputed the
            # way from_dense does (edge tiles are part-padding)
            brow = np.asarray(data_g.brow)
            bcol = np.searchsorted(np.asarray(data_g.indptr),
                                   np.arange(nb_g), side="right") - 1
            nse_g = int((np.clip(self.k - brow * d.tk, 0, d.tk)
                         * np.clip(self.m - bcol * d.tf, 0, d.tf)).sum())
            return SparseTensor(data=data_g, format=self.format,
                                shape=self.shape, nse=nse_g)
        nnz_g = int(np.asarray(d.nse[g]).sum())
        data_g = dataclasses.replace(
            d, vals=d.vals[g], cols=d.cols[g], rows=d.rows[g],
            q=d.q[g], nse=d.nse[g], nnz=nnz_g)
        return SparseTensor(data=data_g, format=self.format, shape=self.shape)

    def unstack(self) -> Tuple["SparseTensor", ...]:
        """Split a stacked tensor back into its G members (host-side op)."""
        gsz = self.batch
        if gsz is None:
            raise TypeError("unstack requires a batched (stacked) tensor")
        return tuple(self[g] for g in range(gsz))

    # -- K0-window structure (out-of-core streaming) -------------------------

    @property
    def num_windows(self) -> int:
        """Number of K0 windows along K (the slab NW axis)."""
        if self.format is not Format.HFLEX:
            raise TypeError("num_windows requires Format.HFLEX")
        return self.data.nw

    def windows(self, w0: int, w1: int) -> "SparseTensor":
        """The sub-matrix covering K0-windows ``[w0, w1)`` as a
        self-describing SparseTensor.

        The result holds the ``(MB, w1-w0, R, L)`` sub-payload (leading group
        axes pass through) with per-window ``q``/``nse`` sliced along, and
        logical shape ``(M, min(K, w1*K0) - w0*K0)`` — i.e. column block
        ``[w0*K0, w1*K0)`` of ``A``, re-based to column 0.  Because slab
        ``cols`` are window-local, no index arithmetic is touched: the slice
        is a view over the window axis, and
        ``A.windows(w0, w1) @ b[w0*K0 : w1*K0]`` is exactly those windows'
        contribution to ``A @ b``.  This is the paper's BRAM K-window lifted
        to the host→device boundary: the K dimension of the out-of-core
        plan's 2-D (K-window × N-tile) grid.  The N dimension needs no
        sparse-side slicing at all — per-column math is independent, so a
        ``StreamingPlan`` pairs these window slices with ``b[:, lo:hi]``
        column stripes and the results concatenate bit-exactly.

        Slices of a stacked (batched) tensor keep the group axis and the
        per-member ``nse``, so they remain ``unstack``-compatible.  Works on
        traced payloads (inside jit/grad; ``nnz`` then falls back to the
        parent's static count).
        """
        if self.format is not Format.HFLEX:
            raise TypeError("windows() requires Format.HFLEX")
        d = self.data
        nw = d.nw
        w0, w1 = int(w0), int(w1)
        if not 0 <= w0 < w1 <= nw:
            raise ValueError(f"window slice [{w0}, {w1}) out of range for "
                             f"NW={nw}")
        nse_w = d.nse[..., :, w0:w1]
        if isinstance(nse_w, jax.core.Tracer):
            nnz_w = d.nnz                      # static upper bound under trace
        else:
            nnz_w = int(np.asarray(nse_w).sum())
        k_w = min(self.k, w1 * d.k0) - w0 * d.k0
        data_w = dataclasses.replace(
            d,
            vals=d.vals[..., w0:w1, :, :],
            cols=d.cols[..., w0:w1, :, :],
            rows=d.rows[..., w0:w1, :, :],
            q=d.q[..., :, w0:w1],
            nse=nse_w,
            k=k_w,
            nnz=nnz_w,
        )
        from repro.analysis.validate import maybe_validate

        return maybe_validate(SparseTensor(data=data_w, format=self.format,
                                           shape=(self.m, k_w)))

    # -- compute ------------------------------------------------------------

    def spmm(self, b, c=None, alpha=1.0, beta=0.0, *, backend: str = "auto",
             **opts) -> jax.Array:
        from .ops import spmm as _spmm

        return _spmm(self, b, c, alpha, beta, backend=backend, **opts)

    def __matmul__(self, b) -> jax.Array:
        b = jnp.asarray(b)
        if b.ndim == 1 and self.batch is None:
            return self.spmm(b[:, None])[:, 0]
        return self.spmm(b)

    def todense(self) -> jax.Array:
        """Materialize A as a dense (M, K) f32 array — (G, M, K) for a
        stacked tensor (oracle/debug path)."""
        if self.batch is not None:
            return jnp.stack([t.todense() for t in self.unstack()])
        m, k = self.shape
        if self.format is Format.HFLEX:
            d = self.data
            mb, nw = d.mb, d.nw
            rows, cols = d.flat_slabs(d.rows), d.flat_slabs(d.cols)
            bi = jnp.arange(mb, dtype=jnp.int32)[:, None, None]
            wi = jnp.arange(nw, dtype=jnp.int32)[None, :, None]
            if d.interleaved:
                rows_g = rows * mb + bi            # undo block interleave
            else:
                rows_g = bi * d.tm + rows
            cols_g = wi * d.k0 + cols
            out = jnp.zeros((m, k), jnp.float32)
            # padded slots carry val == 0 -> 'drop' only guards OOB pad rows
            return out.at[rows_g.reshape(-1), cols_g.reshape(-1)].add(
                d.vals.reshape(-1), mode="drop")
        d = self.data  # stores A^T as a (K', M') weight
        nbf = d.f // d.tf
        bcol = jnp.searchsorted(
            d.indptr, jnp.arange(d.blocks.shape[0]), side="right") - 1
        at = jnp.zeros((d.k // d.tk, nbf, d.tk, d.tf), jnp.float32)
        at = at.at[d.brow, bcol].add(d.blocks.astype(jnp.float32))
        at = at.transpose(0, 2, 1, 3).reshape(d.k, d.f)    # A^T (K', M')
        return at.T[:m, :k]


# ---------------------------------------------------------------------------
# Constructors
# ---------------------------------------------------------------------------


def from_sparse_matrix(
    a: SparseMatrix,
    format: Format = Format.HFLEX,
    *,
    tm: int = 128,
    k0: int = 4096,
    chunk: int = 8,
    interleave: bool = True,
    bucket: bool = True,
    block: Tuple[int, int] = (128, 128),
    threshold: float = 0.0,
    device: bool = True,
) -> SparseTensor:
    """Pack a host COO :class:`SparseMatrix` into a packed SparseTensor
    (device-resident by default; ``device=False`` keeps numpy leaves —
    see :func:`pack_hflex`)."""
    if format is Format.HFLEX:
        packed = pack_hflex(a, tm=tm, k0=k0, chunk=chunk,
                            interleave=interleave, bucket=bucket,
                            device=device)
        return SparseTensor(data=packed, format=Format.HFLEX, shape=a.shape)
    from repro.core.sparse import to_dense

    return from_dense(to_dense(a), format=Format.BSR, block=block,
                      threshold=threshold, device=device)


def from_coo(
    shape: Tuple[int, int],
    row: np.ndarray,
    col: np.ndarray,
    val: np.ndarray,
    format: Format = Format.HFLEX,
    **kwargs,
) -> SparseTensor:
    """Build from raw COO triples (host arrays)."""
    sm = SparseMatrix(
        tuple(shape),
        np.asarray(row, np.int32),
        np.asarray(col, np.int32),
        np.asarray(val, np.float32),
    ).sorted_column_major()
    return from_sparse_matrix(sm, format=format, **kwargs)


def from_dense(
    a: np.ndarray,
    format: Format = Format.HFLEX,
    *,
    block: Tuple[int, int] = (128, 128),
    threshold: float = 0.0,
    device: bool = True,
    **kwargs,
) -> SparseTensor:
    """Build from a dense (M, K) array; zeros (or, for BSR, all-zero tiles)
    are dropped."""
    a = np.asarray(a)
    if a.ndim != 2:
        raise ValueError("from_dense expects a 2-D matrix")
    if format is Format.HFLEX:
        return from_sparse_matrix(_coo_from_dense(a), format=format,
                                  device=device, **kwargs)
    m, k = a.shape
    bm, bk = block
    mpad, kpad = cdiv(m, bm) * bm, cdiv(k, bk) * bk
    at = np.zeros((kpad, mpad), np.float32)
    at[:k, :m] = a.T.astype(np.float32)
    w = pack_bsr_weight(at, tk=bk, tf=bm, threshold=threshold, device=device)
    # stored cells inside the logical bounds (edge tiles are part-padding)
    brow = np.asarray(w.brow)
    bcol = np.searchsorted(np.asarray(w.indptr), np.arange(brow.shape[0]),
                           side="right") - 1
    nse = int((np.clip(k - brow * bk, 0, bk)
               * np.clip(m - bcol * bm, 0, bm)).sum())
    return SparseTensor(data=w, format=Format.BSR, shape=(m, k), nse=nse)


def stack_hflex(tensors, device: bool = True) -> SparseTensor:
    """Stack G same-geometry HFLEX tensors into one batched SparseTensor.

    The members must be *bucket-mates*: identical executable geometry
    (``SparseTensor.geometry`` — slab dims, tiling, interleave) **and**
    identical logical shape ``(M, K)``.  Ragged callers embed their members
    in a common bounding shape first (pad ``b`` rows / slice output rows —
    see the serving scheduler).  The result carries a leading group axis on
    every payload array; ``spmm`` then takes ``b`` of shape ``(G, K, N)``
    and the whole group executes as **one** dispatch (one batch-grid kernel
    launch / one vmapped XLA call).

    Round trip: ``stack_hflex(ts).unstack()`` recovers the members
    (per-member ``nnz`` is rebuilt from the true slab counts ``nse``).

    ``device=False`` keeps the stacked payload **host-resident** (numpy
    leaves): the async serving pipeline's pack stage stacks groups on
    worker threads without ever touching the device — the plan tier
    performs the single ``device_put`` at dispatch.  Stacked values are
    identical either way (host stack is a plain ``np.stack``).
    """
    ts = list(tensors)
    if not ts:
        raise ValueError("stack_hflex needs at least one tensor")
    for t in ts:
        if not isinstance(t, SparseTensor):
            raise TypeError(f"stack_hflex expects SparseTensors, got "
                            f"{type(t).__name__}")
        if t.format is not Format.HFLEX:
            raise ValueError("stack_hflex supports Format.HFLEX only")
        if t.batch is not None:
            raise ValueError("cannot stack an already-batched tensor")
    t0 = ts[0]
    for t in ts[1:]:
        if t.geometry != t0.geometry:
            raise ValueError(
                f"geometry mismatch: {t.geometry} != {t0.geometry} — only "
                f"bucket-mates (same slab geometry) can share a dispatch")
        if t.shape != t0.shape:
            raise ValueError(
                f"shape mismatch: {t.shape} != {t0.shape} — embed ragged "
                f"members in a common (M, K) bounding shape before stacking")
    d0 = t0.data

    def _stack_host(xs):
        return np.stack([np.asarray(x) for x in xs])

    if not device:
        _stack = _stack_host                   # host-resident pack stage
    elif jax.default_backend() == "cpu" or all(t.on_host for t in ts):
        # Host stack + one transfer per field: ~5x faster than jnp.stack on
        # CPU (np.asarray of a CPU jax array is near-zero-copy), bit-exact.
        # Host-resident members stack on the host too (one transfer total
        # instead of G per field).  Device-resident payloads on an
        # accelerator stack there.
        def _stack(xs):
            return jnp.asarray(_stack_host(xs))
    else:
        _stack = jnp.stack
    stacked = PackedSpMM(
        vals=_stack([t.data.vals for t in ts]),
        cols=_stack([t.data.cols for t in ts]),
        rows=_stack([t.data.rows for t in ts]),
        q=_stack([t.data.q for t in ts]),
        nse=_stack([t.data.nse for t in ts]),
        m=d0.m, k=d0.k, tm=d0.tm, k0=d0.k0, chunk=d0.chunk,
        interleaved=d0.interleaved,
        nnz=sum(t.data.nnz for t in ts),
    )
    from repro.analysis.validate import maybe_validate

    return maybe_validate(
        SparseTensor(data=stacked, format=Format.HFLEX, shape=t0.shape))


def repad_lw(t: SparseTensor, lw: int) -> SparseTensor:
    """Widen an HFLEX tensor's slab LW axis to ``lw`` with inert zero slots.

    Only ``vals``/``cols``/``rows`` grow (zero-filled); ``q``/``nse`` and
    every geometry static besides LW are untouched, so the padding is
    *inert*: the Pallas kernels walk exactly ``q`` chunk trips and never
    reach the new slots, and the flat jnp path's extra contributions are
    ``0.0 * b[0]`` terms — ``±0.0`` added into segment-sum accumulators
    that are never ``-0.0`` (they start at ``+0.0``, and an IEEE-754
    round-to-nearest sum of nonzero terms cannot produce ``-0.0``), an
    exact identity.  Results are therefore bit-identical to the original
    tensor on every backend.

    This is how the cost-model merge policy turns *near-miss* LW buckets
    into bucket-mates: re-pad the narrow members up to the widest member's
    bucket, then :func:`stack_hflex` the union into one dispatch.  Works on
    host-resident (numpy) and device payloads alike; batched (stacked)
    tensors pass through with the group axis intact.
    """
    if not isinstance(t, SparseTensor):
        raise TypeError(f"repad_lw expects a SparseTensor, got "
                        f"{type(t).__name__}")
    if t.format is not Format.HFLEX:
        raise ValueError("repad_lw supports Format.HFLEX only")
    d = t.data
    cur = d.lw
    lw = int(lw)
    if lw < cur:
        raise ValueError(f"cannot shrink LW: {cur} -> {lw}")
    if lw == cur:
        return t
    if slab_lw(lw) != lw:
        raise ValueError(f"LW={lw} has no lane layout (next valid width: "
                         f"{slab_lw(lw)})")
    pad = [(0, 0)] * (d.vals.ndim - 2) + [(0, lw - cur)]
    xp = np if t.on_host else jnp

    def widen(x):
        return lane_major(xp.pad(d.flat_slabs(x), pad))

    data = dataclasses.replace(
        d, vals=widen(d.vals), cols=widen(d.cols), rows=widen(d.rows))
    from repro.analysis.validate import maybe_validate

    return maybe_validate(SparseTensor(data=data, format=Format.HFLEX,
                                       shape=t.shape, nse=t.nse))


def bucket_block_count(nb: int, floor: int = 8) -> int:
    """Round a BSR block count up to its bucket: the next power of two
    (min ``floor``) — the BSR analogue of the HFLEX LW bucket, so
    near-miss pruned layers share one compiled executable."""
    b = floor
    while b < nb:
        b *= 2
    return b


def stack_bsr(tensors, device: bool = True) -> SparseTensor:
    """Stack G same-geometry BSR tensors into one batched SparseTensor.

    The members must share the weight statics ``(K', F', TK, TF)`` and the
    logical shape ``(M, K)``; their block *counts* may differ — every
    member is padded to the shared :func:`bucket_block_count` bucket
    NB_pad with zero blocks (``brow`` padded in-bounds with 0), and the
    true per-member count survives as ``indptr[g, -1]`` — the BSR twin of
    HFLEX's per-member ``nse``, used to mask padding cotangents in the
    backward pass.  Padded slots are inert in the forward pass: the
    kernel's pointer walk stops at ``indptr[g, -1]`` and the reference
    path scatters zero blocks.

    ``spmm`` then takes ``b`` of shape ``(G, K, N)`` and the whole group
    executes as **one** dispatch, bit-identical per member to the
    unstacked calls.  Round trip: ``stack_bsr(ts).unstack()`` recovers the
    members (padding stripped, per-member ``nse`` rebuilt).

    ``device=False`` keeps the stacked payload **host-resident** (numpy
    leaves) so the async serving pipeline's pack stage can stack groups on
    worker threads; the plan tier performs the single ``device_put`` at
    dispatch.
    """
    ts = list(tensors)
    if not ts:
        raise ValueError("stack_bsr needs at least one tensor")
    for t in ts:
        if not isinstance(t, SparseTensor):
            raise TypeError(f"stack_bsr expects SparseTensors, got "
                            f"{type(t).__name__}")
        if t.format is not Format.BSR:
            raise ValueError("stack_bsr supports Format.BSR only")
        if t.batch is not None:
            raise ValueError("cannot stack an already-batched tensor")
    t0 = ts[0]
    d0 = t0.data
    for t in ts[1:]:
        d = t.data
        if (d.k, d.f, d.tk, d.tf) != (d0.k, d0.f, d0.tk, d0.tf):
            raise ValueError(
                f"geometry mismatch: {(d.k, d.f, d.tk, d.tf)} != "
                f"{(d0.k, d0.f, d0.tk, d0.tf)} — only same-tiling weights "
                f"can share a dispatch")
        if t.shape != t0.shape:
            raise ValueError(
                f"shape mismatch: {t.shape} != {t0.shape} — members must "
                f"share the logical (M, K) shape")
    g = len(ts)
    nb_pad = bucket_block_count(max(t.data.nb for t in ts))
    nfp1 = int(np.asarray(d0.indptr).shape[-1])
    blocks = np.zeros((g, nb_pad, d0.tk, d0.tf), np.float32)
    brow = np.zeros((g, nb_pad), np.int32)
    indptr = np.zeros((g, nfp1), np.int32)
    for i, t in enumerate(ts):
        d = t.data
        nb = d.nb
        blocks[i, :nb] = np.asarray(d.blocks)
        brow[i, :nb] = np.asarray(d.brow)
        indptr[i] = np.asarray(d.indptr)
    conv = np.asarray if not device else jnp.asarray
    stacked = BsrWeight(blocks=conv(blocks), brow=conv(brow),
                        indptr=conv(indptr),
                        k=d0.k, f=d0.f, tk=d0.tk, tf=d0.tf)
    from repro.analysis.validate import maybe_validate

    return maybe_validate(
        SparseTensor(data=stacked, format=Format.BSR, shape=t0.shape,
                     nse=sum(t.nnz for t in ts)))


def from_bsr_weight(w: BsrWeight) -> SparseTensor:
    """Wrap an existing (K, F) BSR *weight* as the SparseTensor ``W^T`` of
    shape (F, K), so that ``W^T @ x^T = (x @ W)^T`` — the bridge from the
    ``x @ W`` orientation of a layer to ``spmm(A, b)``."""
    nb, tk, tf = w.blocks.shape
    return SparseTensor(data=w, format=Format.BSR, shape=(w.f, w.k),
                        nse=int(nb * tk * tf))
