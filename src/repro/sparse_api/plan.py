"""SpmmPlan — prepare an SpMM once, run it many times.

The unplanned :func:`repro.sparse_api.spmm` entry point is general (any
backend, differentiable, traced epilogue) but pays per call: backend
resolution, option-key construction, pytree hashing through the jit cache,
and — in the traced body — the derivation of gather/scatter indices.  A
*plan* hoists all of that to preparation time, the API analogue of the
paper's preprocessing stage:

    >>> import repro.sparse_api as sp
    >>> P = sp.plan(A, n=64)                  # pad/permute/resolve ONCE
    >>> y = P.run(b)                          # hot loop: compiled call only
    >>> y = P.run(b, c, alpha=2.0, beta=0.5)  # traced epilogue, no recompile

What a plan does once:

* resolves the backend (``auto`` included) and freezes the option key;
* precomputes the flat global gather/scatter index operands (HFLEX ``jnp``
  path) or the payload operand list (Pallas / BSR paths);
* AOT-lowers and compiles the executable, cached in a module-level table
  keyed by the **bucketed geometry** (plus logical shape, N, group size,
  dtypes and backend): distinct matrices packed into the same bucket share
  one executable and one trace — ``BACKEND_STATS["traces"]`` stays flat.

``run`` results are bit-identical to the unplanned ``spmm`` (they execute
the same op sequence; see ``backends._hflex_flat_exec``), and ``alpha`` /
``beta`` remain *runtime* operands (HFlex: one executable serves any
epilogue).  ``run(values=...)`` substitutes a new non-zero payload of the
same structure (pruned-weight serving: update weights without re-planning).

**Group plans** (:func:`plan_group`) extend the same machinery to a whole
group of bucket-mates: the G members are stacked behind a leading payload
axis (:func:`repro.sparse_api.stack_hflex`), ``run`` takes ``b`` of shape
``(G, K, N)``, and the entire group executes as **one** compiled-call
dispatch.  ``values=`` substitution stays per-group (shape
``(G, *A.values.shape[1:])``).

**Mesh plans** (``plan(..., mesh=)``) carry a device mesh: the executable
is AOT-compiled with the engine's multi-chip shardings (A row-blocks over
``data``, B column-tiles over ``model`` — see
``SextansEngine.shard_specs``), so the sharded multi-chip path and the
batched serving path run through one plan abstraction.  XLA cannot
partition the HFLEX kernel's call, so on ``pallas`` the plan pads the
row blocks to a multiple of the ``data`` axis, places each chip's share
of the slabs on that chip, and runs the kernel per chip under
``shard_map``: no chip ever holds another chip's slabs.

**Streaming plans** (:class:`StreamingPlan`, selected by
``plan(..., device_bytes=)`` or forced with ``stream=True``) are the
out-of-core tier: a matrix whose slab payload exceeds the device budget is
held host-side and executed over a 2-D **(K-window × N-tile)** grid — ONE
window-step executable of bucketed shape ``(MB, WCHUNK, LW)`` × dense
width ``NTILE`` accumulates ``A_w @ B_{w,t}`` into a persistent (donated)
f32 C-stripe accumulator while the next chunk's host→device transfer is
staged, and the ``alpha``/``beta`` epilogue is applied once per tile at
the end of its window walk.  When the full-N working set fits the budget
the N dimension stays untiled (``n_tiles == 1``, exactly the PR-4
pipeline); when even one full-N chunk would blow the budget, N splits into
column tiles so the budget bounds ``(WCHUNK·K0, NTILE)`` slices of ``b``
plus an ``(M, NTILE)`` C stripe.  Results are bit-identical to the
resident path either way (see ``backends.StreamOps``: per-column math is
independent, and each column's add sequence is untouched by tiling).
This is the paper's BRAM K-window and URAM C-partition lifted together to
the host→device boundary: device memory bounds the *tile*, not the
matrix.

Plans are a forward/serving construct: ``run`` calls an AOT-compiled
executable and is not differentiable — training goes through ``spmm`` (or
``spmm_streaming`` for out-of-core training steps).
"""

from __future__ import annotations

import dataclasses
import threading
import warnings
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hflex import bucket_geometry
from repro.core.partition import cdiv
from repro.kernels._compat import resolve_interpret
from repro.kernels.sextans_spmm import sextans_spmm_pallas
from repro.tracing import span

from . import backends as _bk
from .tensor import Format, PackedSpMM, SparseTensor, stack_bsr, stack_hflex

__all__ = ["SpmmPlan", "StreamingPlan", "RaggedPlan", "plan", "plan_group",
           "plan_ragged", "clear_plan_cache", "device_memory_budget",
           "PLAN_STATS"]

# Executable-cache hits/misses (the paper counts avoided place/route runs;
# we count avoided traces+compiles) and compiled-call dispatches (the
# batched scheduler's amortization target: dispatches << requests).
# ``window_dispatches`` counts the streaming tier's per-chunk dispatches
# separately (they are deliberate pipeline steps, not missed batching).
# A second process reuses compiled code through JAX's persistent
# compilation cache (repro.compile_cache), not through this table.
PLAN_STATS: Dict[str, int] = {"exec_hits": 0, "exec_misses": 0,
                              "dispatches": 0, "window_dispatches": 0}

_EXEC_CACHE: Dict[Tuple, Any] = {}

# Plans are built both by the owning thread and the async dispatch thread
# (PackExecutePipeline serializes *dispatch*, but a sync engine call can
# trace concurrently with it).  One lock makes hit/miss accounting exact
# and bounds compilation to once per key even under that race; holding it
# across the compile is deliberate — two threads racing the same key
# would otherwise both pay the trace+compile.
_EXEC_LOCK = threading.Lock()


def clear_plan_cache() -> None:
    """Drop all cached plan executables (tests / memory pressure)."""
    with _EXEC_LOCK:
        _EXEC_CACHE.clear()


def _aot_compile(key: Tuple, fn, arg_shapes, in_shardings=None,
                 out_shardings=None, donate_argnums=None):
    """Lower + compile ``fn`` for ``arg_shapes`` once per cache key (a
    miss still reads JAX's persistent compilation cache, where enabled)."""
    with _EXEC_LOCK:
        hit = _EXEC_CACHE.get(key)
        if hit is not None:
            PLAN_STATS["exec_hits"] += 1
            return hit
        PLAN_STATS["exec_misses"] += 1
        kw = {}
        if donate_argnums is not None:
            kw["donate_argnums"] = donate_argnums
        if in_shardings is None:
            jfn = jax.jit(fn, **kw)
        else:
            jfn = jax.jit(fn, in_shardings=in_shardings,
                          out_shardings=out_shardings, **kw)
        compiled = jfn.lower(*arg_shapes).compile()
        _EXEC_CACHE[key] = compiled
        return compiled


def _check_bsr_tiles(a: SparseTensor, backend: str, opts: Dict[str, Any]):
    """Refuse a BSR tiling that the compiled TPU kernel cannot run."""
    d = a.data
    if (_bk.is_kernel(backend) and (d.tk % 128 or d.tf % 128)
            and not resolve_interpret(opts.get("interpret"))):
        raise ValueError(
            f"BSR blocks of {d.tk}x{d.tf} cannot run on the TPU "
            f"kernel: its x and output tiles are lane tiles, so "
            f"both block sides must be multiples of 128 — repack "
            f"with block=(128, 128) (or a multiple), or plan with "
            f"backend='jnp'")


def device_memory_budget() -> Optional[int]:
    """Best-effort device memory budget in bytes (None if unknown).

    Uses the default device's ``memory_stats()['bytes_limit']`` where the
    backend reports it (TPU/GPU); CPU backends report nothing, so
    ``plan(..., device_bytes="auto")`` stays resident there.
    """
    try:
        dev = jax.local_devices()[0]
        stats = dev.memory_stats()
        if stats:
            limit = int(stats.get("bytes_limit", 0))
            return limit or None
    except Exception:
        pass
    return None


def _per_window_bytes(d, n: int, itemsize: int) -> int:
    """Device bytes one K0 window contributes to a streamed chunk: the
    vals/cols/rows slab columns (4 B each), its ``q`` column, the staged
    ``(K0, N)`` rows of ``b`` plus one in-step copy of them (the jnp path
    gathers them, the Pallas path pads them), and the per-slot contribution
    intermediate ``(MB*LW, N)`` f32 the scatter/one-hot accumulate
    materializes — without it the dominant step allocation would be
    invisible to both window-chunk sizing and the reported chunk/peak byte
    stats.  Single source of truth for both."""
    return (d.mb * d.lw * 12 + d.mb * 4
            + 2 * d.k0 * n * itemsize
            + d.mb * d.lw * n * 4)


def _ab_operands(cache: Dict, alpha, beta,
                 g: Optional[int] = None) -> Tuple[Any, Any]:
    """Device buffers for the epilogue scalars, cached per value so hot
    loops never re-commit host scalars (traced/non-scalar inputs convert
    directly).  Group plans (``g``) compile a ``(G,)`` per-member epilogue
    signature, so scalars are broadcast up to it here — one executable
    serves uniform and mixed-epilogue groups alike."""

    def shaped(x):
        x = jnp.asarray(x, jnp.float32)
        if g is not None and x.ndim == 0:
            x = jnp.broadcast_to(x, (g,))
        return x

    try:
        key = (float(alpha), float(beta))
        cached = cache.get(key)
        if cached is None:
            cached = (shaped(alpha), shaped(beta))
            if len(cache) < 256:
                cache[key] = cached
        return cached
    except TypeError:           # traced / non-scalar: convert directly
        return (shaped(alpha), shaped(beta))


class SpmmPlan:
    """A prepared ``C = alpha * A @ B + beta * C`` for one (A, N) pair —
    or one (stacked group, N) pair when ``A`` is batched.

    Build via :func:`plan` / :func:`plan_group`.  Attributes of note:

    * ``backend`` — the resolved backend name (never ``"auto"``).
    * ``group`` — G for a group plan, None for a single matrix.
    * ``mesh`` — the device mesh the executable was sharded for (or None).
    * ``exec_key`` — the executable-cache key (bucketed geometry + logical
      shape + N + group size + dtypes + backend/options + mesh).
    """

    #: True when a TuningDB decision steered this plan's backend/tiling
    #: (set by ``plan()``/``plan_group()``; engines count tuned dispatches).
    tuned = False
    #: seconds of the ``sextans.plan.build`` span that built this plan
    build_s = 0.0

    def __init__(self, a: SparseTensor, n: int, backend: str,
                 opts: Dict[str, Any], dtype=jnp.float32, mesh=None):
        if not isinstance(a, SparseTensor):
            raise TypeError(f"plan expects a SparseTensor, got {type(a).__name__}")
        if n <= 0:
            raise ValueError("n must be positive")
        from repro.analysis.validate import maybe_validate

        maybe_validate(a)   # SEXTANS_CHECK=1: validate at plan time
        self.a = a
        self.n = int(n)
        self.m, self.k = a.shape
        self.group = a.batch
        self.mesh = mesh
        self.backend = _bk.resolve_backend(backend, a, n=self.n)
        self.opts = _bk.with_lane(a, self.backend, self.n, opts)
        self.dtype = jnp.dtype(dtype)
        okey = tuple(sorted(self.opts.items()))
        if a.format is Format.BSR:
            _check_bsr_tiles(a, self.backend, self.opts)

        m, k, n = self.m, self.k, self.n
        g = self.group
        # The flat path host-precomputes gather/scatter ids — a win when one
        # plan serves many runs.  Group plans are typically built per flush
        # and run once, so they take the payload path instead: the ids are
        # derived in-trace (backends._hflex_jnp) and fused by XLA, and plan
        # construction is a tree-flatten.  Results are bit-identical either
        # way (same op sequence on the same index values).
        flat = (a.format is Format.HFLEX and self.backend == "jnp"
                and mesh is None and g is None)
        self._flat = flat
        row_split = (mesh is not None and a.format is Format.HFLEX
                     and _bk.is_kernel(self.backend))
        if a.format is Format.HFLEX:
            d = a.data
            bucket = bucket_geometry(d.mb, d.nw, d.lw, n)
        else:
            d = a.data
            bucket = (d.nb, d.k, d.f, d.tk, d.tf)
        # Group plans compile a (G,) per-member epilogue signature (see
        # _ab_operands) — the "abvec" marker keeps their keys apart from
        # scalar-signature executables.
        self.exec_key = ("flat" if flat else "payload", self.backend, okey,
                         a.format, a.geometry, bucket, (m, k, n), g,
                         str(self.dtype), mesh) + (
                             ("abvec",) if g is not None else ())

        if flat:
            # Host-precomputed flat gather/scatter indices (same layout
            # helper as the unplanned backend, evaluated in numpy): the
            # traced body is exactly backends._hflex_flat_exec — one gather,
            # one segment_sum, fused epilogue.  No pad, no permute, no iota.
            # Group plans carry the leading G axis straight through (the
            # body vmaps over it — still one compiled-call dispatch).
            rows_g, cols_g = _bk._hflex_global_ids(d, xp=np)
            lead = d.vals.shape[:-4]
            self._operands = (
                jnp.asarray(d.vals).reshape(*lead, -1),
                jnp.asarray(cols_g),
                jnp.asarray(rows_g),
            )
            self._values_slot = 0

            def traced(vals, cols_gg, rows_gg, b, c, alpha, beta):
                _bk.bump_trace()
                return _bk._hflex_flat_exec(vals, cols_gg, rows_gg, b, c,
                                            alpha, beta, m)

            self._traced = traced
        elif not row_split:
            # Generic payload plan: pass every leaf of the packed format as
            # an operand (so bucket-mates share the executable) and rebuild
            # the tensor inside the trace.  Host-resident leaves (numpy,
            # from ``pack_hflex(device=False)`` / ``stack_hflex(device=
            # False)``) are committed to the device HERE, exactly once — the
            # plan owns the pack→device boundary, so worker-thread packing
            # never touches the device and the hot loop never re-transfers.
            leaves, treedef = jax.tree_util.tree_flatten(a)
            vals_leaf = a.values
            self._values_slot = next(
                i for i, leaf in enumerate(leaves) if leaf is vals_leaf)
            self._operands = tuple(
                x if isinstance(x, jax.Array) else jnp.asarray(x)
                for x in leaves)
            self._treedef = treedef
            backend_fn = _bk.get_backend(self.backend).fn
            opts_d = self.opts

            def traced(*args):
                *lvs, b, c, alpha, beta = args
                a_t = jax.tree_util.tree_unflatten(treedef, lvs)
                return backend_fn(a_t, b, c, alpha, beta, **opts_d)

            self._traced = traced

        self._place_values = None
        if row_split:
            in_sh, out_sh = self._init_row_split(mesh)
        elif mesh is not None:
            in_sh, out_sh = self._mesh_shardings(mesh)
        else:
            in_sh = out_sh = None

        self._bshape = (k, n) if g is None else (g, k, n)
        self._cshape = (m, n) if g is None else (g, m, n)
        b_s = jax.ShapeDtypeStruct(self._bshape, self.dtype)
        c_s = jax.ShapeDtypeStruct(self._cshape, self.dtype)
        s_s = jax.ShapeDtypeStruct(() if g is None else (g,), jnp.float32)
        arg_shapes = tuple(
            jax.ShapeDtypeStruct(x.shape, x.dtype) for x in self._operands
        ) + (b_s, c_s, s_s, s_s)
        self._compiled = _aot_compile(self.exec_key, self._traced, arg_shapes,
                                      in_shardings=in_sh,
                                      out_shardings=out_sh)
        self._zero_c: Optional[jax.Array] = None
        # Epilogue scalars are runtime operands; cache their device buffers
        # per value so the hot loop never re-commits host scalars.
        self._ab_cache: Dict[Tuple[float, float], Tuple[Any, Any]] = {}

    def _init_row_split(self, mesh):
        """Row-split mesh plan of the HFLEX kernel (see
        :func:`row_split_spmm`): places each chip's row blocks of the
        payload on that chip and returns the operand and result
        shardings."""
        from jax.sharding import NamedSharding

        d = self.a.data
        traced, mbp, slab_spec, q_spec = row_split_spmm(
            d, mesh, self.m, self.k, self.n, self.group, self.opts)

        def place(x, spec, axis):
            pad = [(0, 0)] * x.ndim
            pad[axis] = (0, mbp - x.shape[axis])
            xp = np if isinstance(x, np.ndarray) else jnp
            return jax.device_put(xp.pad(x, pad), NamedSharding(mesh, spec))

        self._place_values = lambda v: place(v, slab_spec, v.ndim - 4)
        self._operands = tuple(place(x, slab_spec, x.ndim - 4)
                               for x in (d.vals, d.cols, d.rows)) + (
            place(np.asarray(d.q), q_spec, d.q.ndim - 2),)
        self._values_slot = 0
        self._traced = traced
        rep = NamedSharding(mesh, jax.sharding.PartitionSpec())
        return tuple(x.sharding for x in self._operands) + (rep,) * 4, rep

    def _mesh_shardings(self, mesh):
        """Operand/result NamedShardings for a mesh plan: the engine's
        multi-chip layout (A row-blocks + C rows over ``data``, B/C columns
        over ``model``), lifted over the group axis when batched (groups
        replicate over the mesh; each chip runs its row shard of every
        member)."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.core.engine import SextansEngine

        if self.a.format is not Format.HFLEX:
            raise ValueError("mesh plans support Format.HFLEX only")
        specs = SextansEngine.shard_specs()
        batched = self.group is not None

        def lift(s: P) -> P:
            return P(None, *s) if batched else s

        d = self.a.data
        pk_spec = PackedSpMM(
            vals=lift(specs["vals"]), cols=lift(specs["cols"]),
            rows=lift(specs["rows"]), q=lift(specs["q"]),
            nse=lift(specs["nse"]),
            m=d.m, k=d.k, tm=d.tm, k0=d.k0, chunk=d.chunk,
            interleaved=d.interleaved, nnz=d.nnz,
        )
        t_spec = SparseTensor(data=pk_spec, format=self.a.format,
                              shape=self.a.shape, nse=self.a.nse)
        leaf_specs = jax.tree_util.tree_flatten(
            t_spec, is_leaf=lambda x: isinstance(x, P))[0]
        nd = lambda s: NamedSharding(mesh, s)
        in_sh = tuple(nd(s) for s in leaf_specs) + (
            nd(lift(specs["b"])), nd(lift(specs["c"])), nd(P()), nd(P()))
        return in_sh, nd(lift(specs["c"]))

    @property
    def payload_bytes(self) -> int:
        """Bytes of the packed operand payload this plan keeps device-
        resident between runs (the quantity a ``device_bytes`` streaming
        threshold compares against)."""
        return int(sum(x.nbytes for x in self._operands))

    # -- execution ----------------------------------------------------------

    def run(self, b, c=None, alpha=1.0, beta=0.0, *, values=None) -> jax.Array:
        """Execute the planned SpMM: one compiled-call dispatch.

        ``b`` must be ``(K, N)`` — ``(G, K, N)`` for a group plan — of the
        planned dtype; ``c`` defaults to a cached zeros block.
        ``alpha``/``beta`` are runtime operands (no recompile); a group
        plan also accepts ``(G,)`` per-member vectors (scalars broadcast),
        each member's epilogue bit-identical to its scalar run.  ``values``
        substitutes a new non-zero payload with the packed structure of
        ``A`` (same shape as ``A.values`` — per-group for a group plan).
        """
        with span("sextans.plan.run"):
            b = jnp.asarray(b)
            if b.shape != self._bshape or b.dtype != self.dtype:
                raise ValueError(
                    f"plan expects b of shape {self._bshape} dtype "
                    f"{self.dtype}, got {b.shape} {b.dtype}")
            if c is None:
                if self._zero_c is None:
                    self._zero_c = jnp.zeros(self._cshape, self.dtype)
                c = self._zero_c
            else:
                # cast to the planned dtype: the executable was compiled for
                # it, and the batched scheduler casts mismatched c the same way
                c = jnp.asarray(c, self.dtype)
            alpha, beta = _ab_operands(self._ab_cache, alpha, beta,
                                       g=self.group)
            ops = self._operands
            if values is not None:
                values = jnp.asarray(values)
                if self._flat:                     # flat path stores vals flat
                    lead = values.shape[:-4] if values.ndim >= 4 else ()
                    values = values.reshape(*lead, -1)
                elif self._place_values is not None:   # row-split mesh plan
                    values = self._place_values(values)
                ops = (ops[:self._values_slot] + (values,)
                       + ops[self._values_slot + 1:])
            PLAN_STATS["dispatches"] += 1
            return self._compiled(*ops, b, c, alpha, beta)

    def __call__(self, b, c=None, alpha=1.0, beta=0.0, **kw) -> jax.Array:
        return self.run(b, c, alpha, beta, **kw)

    def __repr__(self) -> str:
        gtag = f"x{self.group}" if self.group else ""
        mtag = ", mesh" if self.mesh is not None else ""
        return (f"SpmmPlan(shape=({self.m}, {self.k}){gtag}@{self.n}, "
                f"backend={self.backend!r}, format={self.a.format.value}"
                f"{mtag})")


class RaggedPlan:
    """A prepared ragged grouped product over a stacked BSR tensor: the
    dropless mixture-of-experts lane.

    ``A`` holds E experts' weights (``A.batch == E``, each of logical shape
    ``(M, K)``).  :meth:`run` takes ``rows`` token rows ``x`` of shape
    ``(rows, K)``, laid out by expert in tiles of
    :data:`~repro.sparse_api.backends.RAGGED_TILE` rows, the expert of each
    row tile ``te`` and the count of tiles in use ``used`` (both int32, on
    the device), and returns ``(rows, M)`` with row ``r`` equal to
    ``x[r] @ A[te[r // TILE]]^T``.  Built via :func:`plan_ragged`; the
    executable is cached by (backend, geometry, E, logical shape, rows,
    dtype), and ``run(values=...)`` substitutes the live stacked payload,
    as :meth:`SpmmPlan.run` does.  Inference only.
    """

    #: seconds of the ``sextans.plan.build`` span that built this plan
    build_s = 0.0

    def __init__(self, a: SparseTensor, rows: int, backend: str,
                 opts: Dict[str, Any], dtype=jnp.float32):
        if a.format is not Format.BSR or a.batch is None:
            raise ValueError("a ragged plan takes a stacked BSR tensor "
                             "(stack_bsr)")
        tile = _bk.RAGGED_TILE
        if rows <= 0 or rows % tile:
            raise ValueError(f"rows must be a positive multiple of {tile}, "
                             f"got {rows}")
        self.a = a
        self.rows = int(rows)
        self.m, self.k = a.shape
        self.group = a.batch
        self.backend = _bk.resolve_backend(backend, a, n=self.rows)
        self.opts = dict(opts)
        self.dtype = jnp.dtype(dtype)
        _check_bsr_tiles(a, self.backend, self.opts)
        d = a.data
        self._operands = tuple(jnp.asarray(x)
                               for x in (d.blocks, d.brow, d.indptr))
        self.exec_key = ("ragged", self.backend,
                         tuple(sorted(self.opts.items())), a.geometry,
                         self.group, (self.m, self.k), self.rows,
                         str(self.dtype))
        treedef = jax.tree_util.tree_structure(a)
        backend_name, opts_d = self.backend, self.opts

        def traced(blocks, brow, indptr, x, te, used):
            a_t = jax.tree_util.tree_unflatten(treedef,
                                               [blocks, brow, indptr])
            return _bk.bsr_ragged(backend_name, a_t, x, te, used, **opts_d)

        sd = jax.ShapeDtypeStruct
        arg_shapes = tuple(sd(x.shape, x.dtype) for x in self._operands) + (
            sd((self.rows, self.k), self.dtype),
            sd((self.rows // tile,), jnp.int32), sd((1,), jnp.int32))
        self._compiled = _aot_compile(self.exec_key, traced, arg_shapes)

    def run(self, x, te, used, *, values=None) -> jax.Array:
        """One compiled-call dispatch; ``values`` replaces the stacked
        ``(E, NB, TK, TF)`` payload."""
        with span("sextans.plan.run"):
            ops = self._operands
            if values is not None:
                ops = (values,) + ops[1:]
            PLAN_STATS["dispatches"] += 1
            return self._compiled(*ops, x, te, used)

    def __repr__(self) -> str:
        return (f"RaggedPlan(shape=({self.m}, {self.k})x{self.group}"
                f"@{self.rows} rows, backend={self.backend!r})")


def row_split_spmm(d: PackedSpMM, mesh, m: int, k: int, n: int,
                   group: Optional[int], opts: Dict[str, Any]):
    """The traced row-split SpMM of the HFLEX kernel on ``mesh``.

    Each chip runs the kernel on its own row blocks (``shard_map`` over
    ``data``) against the whole, replicated ``b``; the ``b``/``c``
    padding and the row permutation stay outside, in the global program.
    MB is padded to ``mbp``, a multiple of the ``data`` axis, with empty
    row blocks (``q = 0``: skipped).  ``d`` supplies statics only, so
    shape structs work.  Returns ``(traced, mbp, slab_spec, q_spec)``;
    ``traced(vals, cols, rows, q, b, c, alpha, beta)`` takes slabs padded
    to ``mbp`` and returns the replicated ``([G,] M, N)`` result."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    mb, nw = d.vals.shape[-4], d.vals.shape[-3]
    tm, k0, interleaved = d.tm, d.k0, d.interleaved
    n_data, n_model = mesh.shape["data"], mesh.shape["model"]
    mbp = cdiv(mb, n_data) * n_data
    lead = (None,) if group is not None else ()
    slab_spec = P(*lead, "data", None, None, None)
    q_spec = P(*lead, "data", None)
    tn = opts.get("tn") or _bk.hflex_lane(n)
    npad = cdiv(n, tn * n_model) * tn * n_model
    interpret = opts.get("interpret")
    lp = ((0, 0),) if group is not None else ()
    rep = NamedSharding(mesh, P())

    def local(vals, cols, rows, q, b, c, alpha, beta):
        return sextans_spmm_pallas(vals, cols, rows, q, b, c, alpha, beta,
                                   tm=tm, k0=k0, tn=tn,
                                   interpret=interpret)

    split = jax.shard_map(
        local, mesh=mesh,
        in_specs=(slab_spec,) * 3 + (q_spec, P(*lead, None, "model"),
                                     P(*lead, "data", "model"), P(), P()),
        out_specs=P(*lead, "data", "model"), check_vma=False)

    def traced(vals, cols, rows, q, b, c, alpha, beta):
        _bk.bump_trace()
        bp = jnp.pad(b, (*lp, (0, nw * k0 - k), (0, npad - n)))
        cp = jnp.pad(c, (*lp, (0, mb * tm - m), (0, npad - n)))
        if interleaved:
            cp = _bk._permute_rows_fwd(cp, mb, tm)
        cp = jnp.pad(cp, (*lp, (0, (mbp - mb) * tm), (0, 0)))
        # gather the row shards before un-permuting: the permutation
        # interleaves rows of every chip's blocks
        out = jax.sharding.reshard(
            split(vals, cols, rows, q, bp, cp, alpha, beta), rep)
        out = out[..., :mb * tm, :]
        if interleaved:
            out = _bk._permute_rows_inv(out, mb, tm)
        return out[..., :m, :n]

    return traced, mbp, slab_spec, q_spec


class StreamingPlan:
    """Out-of-core SpMM: K0-window chunks stream through a persistent C
    accumulator — for matrices whose slab payload exceeds device memory.

    Built via ``plan(..., device_bytes=)`` / ``plan(..., stream=True)``.
    The full HFLEX payload is staged **host-side** and executed over a 2-D
    (K-window × N-tile) grid, column tiles outer, window chunks inner:
    each of the ``steps = ceil(NW / window_chunk)`` dispatches of a tile
    receives only a ``(MB, WCHUNK, LW)`` slab chunk plus the matching
    ``(WCHUNK*K0, NTILE)`` block of ``b``, accumulated into a donated f32
    C-stripe by ONE AOT-compiled window-step executable shared by every
    tile (the chunk after the one in flight is staged while the device
    computes — across tile boundaries too — so JAX async dispatch gives
    the transfer/compute overlap as long as ``run`` never blocks).
    ``beta*c`` is folded in exactly once per tile by its epilogue
    dispatch, so results are bit-identical to the resident
    :class:`SpmmPlan` / unplanned ``spmm`` (see ``backends.StreamOps`` for
    why the raw-accumulator decomposition is the only bit-exact one; the
    tail tile is column-padded inertly, like tail windows are padded with
    inert slabs).

    The budget sizes both dimensions: the largest ``n_tile`` (N, then
    descending powers of two) whose working set
    ``2·WCHUNK·per_window(NTILE) + acc(NTILE) + 2·M·NTILE·itemsize``
    admits at least one window per dispatch wins, so N stays untiled
    (``n_tiles == 1`` — device-array results, exactly the PR-4 pipeline)
    whenever it can.  With ``n_tiles > 1`` the assembled ``(M, N)`` result
    is a **host (numpy) array** — the full C may not fit on device; only
    one stripe plus one pending writeback is ever device-resident.

    Attributes of note: ``window_chunk`` (K0 windows per dispatch, bucketed
    to a power of two so bucket-mates share the step executable),
    ``n_tile`` / ``n_tiles`` (column-tile width and count),
    ``steps`` (window dispatches per tile), ``window_dispatches``
    (``steps * n_tiles`` per run), ``payload_bytes`` (full host payload),
    ``chunk_payload_bytes`` and ``peak_payload_bytes`` (device working
    set: two staged chunks + the accumulator + epilogue operands, at
    ``n_tile`` width).
    """

    group = None
    mesh = None
    #: True when a TuningDB decision steered this plan's tiling (see
    #: :class:`SpmmPlan.tuned`).
    tuned = False
    #: seconds of the ``sextans.plan.build`` span that built this plan
    build_s = 0.0

    def __init__(self, a: SparseTensor, n: int, backend: str,
                 opts: Dict[str, Any], dtype=jnp.float32,
                 device_bytes: Optional[int] = None,
                 window_chunk: Optional[int] = None,
                 n_tile: Optional[int] = None):
        if not isinstance(a, SparseTensor):
            raise TypeError(
                f"plan expects a SparseTensor, got {type(a).__name__}")
        if a.format is not Format.HFLEX:
            raise ValueError("streaming plans support Format.HFLEX only")
        if a.batch is not None:
            raise ValueError(
                "streaming plans take one matrix at a time (the serving "
                "scheduler routes oversized requests around group stacking)")
        if n <= 0:
            raise ValueError("n must be positive")
        from repro.analysis.validate import maybe_validate

        maybe_validate(a)   # SEXTANS_CHECK=1: validate at plan time
        self.a = a
        self.n = int(n)
        self.m, self.k = a.shape
        self.backend = _bk.resolve_backend(backend, a, n=self.n)
        stream = _bk.get_backend(self.backend).stream
        if stream is None:
            raise ValueError(
                f"backend {self.backend!r} has no streaming hooks "
                f"(StreamOps); register it with stream= to use it out of "
                f"core")
        self._stream = stream
        self.opts = dict(opts)
        self.dtype = jnp.dtype(dtype)
        self.device_bytes = device_bytes

        d = a.data
        # Host staging: the out-of-core contract — the full payload lives in
        # host memory (zero-copy for host-resident packs, near-zero-copy
        # from CPU jax arrays), and only chunk-sized buffers are ever
        # device_put.  The plan then drops every reference to the caller's
        # device arrays (self.a is rebuilt over the host copies), so it
        # pins no device payload of its own.  True out-of-core on a real
        # accelerator packs with ``pack_hflex(device=False)``: the payload
        # is numpy end to end and never touches the device at all.
        self._vals_h = np.asarray(d.vals)
        self._cols_h = np.asarray(d.cols)
        self._rows_h = np.asarray(d.rows)
        self._q_h = np.asarray(d.q)
        d = dataclasses.replace(d, vals=self._vals_h, cols=self._cols_h,
                                rows=self._rows_h, q=self._q_h,
                                nse=np.asarray(d.nse))
        self.a = a = SparseTensor(data=d, format=a.format, shape=a.shape,
                                  nse=a.nse)
        self._d = d

        if window_chunk is not None:
            window_chunk = int(window_chunk)
            if not 1 <= window_chunk <= d.nw:
                raise ValueError(
                    f"window_chunk must be in [1, NW={d.nw}], got "
                    f"{window_chunk}")
        if n_tile is not None:
            n_tile = int(n_tile)
            if not 1 <= n_tile <= self.n:
                raise ValueError(
                    f"n_tile must be in [1, N={self.n}], got {n_tile}")
        ntile, wc = self._choose_tiling(device_bytes, n_tile, window_chunk)
        self.opts = _bk.with_lane(a, self.backend, ntile, self.opts)
        okey = tuple(sorted(self.opts.items()))
        self.n_tile = ntile
        self.n_tiles = cdiv(self.n, ntile)
        self.window_chunk = wc
        self.steps = cdiv(d.nw, wc)
        acc_shape = self._acc_shape_for(ntile)
        self._acc_shape = acc_shape
        acc_bytes = int(np.prod(acc_shape)) * 4
        out_bytes = 2 * self.m * ntile * self.dtype.itemsize  # c + out stripe
        self.chunk_payload_bytes = wc * _per_window_bytes(
            d, ntile, self.dtype.itemsize)
        # double-buffered: chunk i computing + chunk i+1 staged
        self.peak_payload_bytes = (2 * self.chunk_payload_bytes
                                   + acc_bytes + out_bytes)
        if (device_bytes is not None
                and self.peak_payload_bytes > device_bytes):
            # No (window_chunk, n_tile) point on the 2-D grid fits: the
            # accumulator + epilogue stripe + one double-buffered window
            # are irreducible even at the finest tiling, so the plan keeps
            # the requested width rather than paying tiling overhead for a
            # budget it cannot meet anyway.  On a real device this overrun
            # is the OOM the budget was meant to prevent — surface it
            # instead of failing silently later.
            warnings.warn(
                f"streaming working set ({self.peak_payload_bytes} B: "
                f"2x{self.chunk_payload_bytes} B chunks + {acc_bytes} B "
                f"accumulator + {out_bytes} B epilogue operands) exceeds "
                f"device_bytes={device_bytes}; window_chunk="
                f"{self.window_chunk} is already the floor for this "
                f"(M, N) even with N-tiling — raise the budget or shrink "
                f"M",
                stacklevel=3)

        # ONE window-step executable: bucketed (MB, WCHUNK, R, L) chunk shape
        # shared by every bucket-mate (the HFlex property, kept under
        # streaming) AND by every column tile — the step is tile-position-
        # independent (the tail tile arrives column-padded), so the 2-D
        # grid needs no extra executables.  k of the chunk is the constant
        # WCHUNK*K0; the parent's ragged K only affects host-side slicing.
        m, k0 = self.m, d.k0
        kc = wc * k0
        interleaved, tm, chunk_sz = d.interleaved, d.tm, d.chunk
        opts_d = self.opts

        def traced_step(vals, cols, rows, q, b_chunk, acc):
            dd = PackedSpMM(vals=vals, cols=cols, rows=rows, q=q, nse=q,
                            m=m, k=kc, tm=tm, k0=k0, chunk=chunk_sz,
                            interleaved=interleaved, nnz=0)
            a_c = SparseTensor(data=dd, format=Format.HFLEX, shape=(m, kc))
            return stream.step(a_c, b_chunk, acc, **opts_d)

        a_struct = self.a      # statics only inside collect (no leaves read)

        out_dtype = self.dtype

        def traced_finish(acc, c, alpha, beta):
            raw = stream.collect(a_struct, acc, ntile, **opts_d)
            return _bk.stream_finish(raw, c, alpha, beta, out_dtype)

        geom = (d.mb, wc, d.lw, tm, k0, chunk_sz, interleaved)
        # the N slot is the *tile* width: plans that tile a huge N down to
        # the same stripe share executables with plans of that natural N
        self.exec_key = ("stream-step", self.backend, okey, geom, m, ntile,
                         str(self.dtype))
        sd = jax.ShapeDtypeStruct
        slab = (d.mb, wc) + d.vals.shape[-2:]
        chunk_shapes = (
            sd(slab, jnp.float32),                  # vals
            sd(slab, jnp.int32),                    # cols
            sd(slab, jnp.int32),                    # rows
            sd((d.mb, wc), jnp.int32),              # q
            sd((kc, ntile), self.dtype),            # b tile chunk
            sd(acc_shape, jnp.float32),             # carried accumulator
        )
        # The accumulator is donated: the persistent C stripe is updated in
        # place across window dispatches (on backends that honor donation).
        self._step_exec = _aot_compile(self.exec_key, traced_step,
                                       chunk_shapes, donate_argnums=(5,))
        fin_key = ("stream-finish", self.backend, okey, geom, m, ntile,
                   str(self.dtype))
        fin_shapes = (sd(acc_shape, jnp.float32),
                      sd((m, ntile), self.dtype),
                      sd((), jnp.float32), sd((), jnp.float32))
        self._finish_exec = _aot_compile(fin_key, traced_finish, fin_shapes)
        self._zero_c: Optional[jax.Array] = None
        self._ab_cache: Dict[Tuple[float, float], Tuple[Any, Any]] = {}

    # -- sizing --------------------------------------------------------------

    def _acc_shape_for(self, width: int) -> Tuple[int, ...]:
        """Accumulator shape the backend's stream.init materializes for a
        dense width (backends may pad it up, e.g. the Pallas kernel layout
        rounds columns to TN) — sizing must charge the real allocation."""
        stream, a, opts = self._stream, self.a, self.opts
        return tuple(jax.eval_shape(
            lambda: stream.init(a, width, **opts)).shape)

    def _choose_tiling(self, device_bytes, n_tile, window_chunk):
        """Pick the (n_tile, window_chunk) execution grid for the budget.

        Largest tile first: the full N, then descending powers of two —
        the first width whose double-buffered working set
        ``2*WCHUNK*per_window(NTILE) + acc(NTILE) + 2*M*NTILE*itemsize``
        admits at least one window per dispatch wins, and its window chunk
        is the largest power of two that fits (>= 1).  So N stays untiled
        whenever it can (n_tiles == 1 is exactly the 1-D PR-4 pipeline)
        and tiles only when one full-N chunk alone would blow the budget.
        Explicit ``n_tile``/``window_chunk`` pin their dimension; no
        budget means the finest (MB, 1, LW) granularity at full width.
        If nothing fits, fall back to the requested width at the minimum
        chunk (the caller warns about the overrun).
        """
        d = self._d
        itemsize = self.dtype.itemsize
        if device_bytes is None:
            return (n_tile or self.n), (window_chunk or 1)
        budget = int(device_bytes)
        if n_tile is not None:
            candidates = [n_tile]
        else:
            candidates = [self.n]
            t = 1
            while t < self.n:
                t <<= 1
            t >>= 1                                  # largest pow2 < N
            while t >= 1:
                candidates.append(t)
                t >>= 1
        for ntile in candidates:
            acc_bytes = int(np.prod(self._acc_shape_for(ntile))) * 4
            out_bytes = 2 * self.m * ntile * itemsize
            per_w = _per_window_bytes(d, ntile, itemsize)
            if window_chunk is not None:
                if (2 * window_chunk * per_w + acc_bytes + out_bytes
                        <= budget):
                    return ntile, window_chunk
                continue
            avail = max(budget - acc_bytes - out_bytes, 0) // 2
            wc = avail // per_w
            if wc >= 1:
                wc = 1 << (int(wc).bit_length() - 1)  # pow2 bucket
                return ntile, min(wc, d.nw)
        return (n_tile or self.n), (window_chunk or 1)

    @property
    def payload_bytes(self) -> int:
        """Full packed payload bytes (held host-side; what a resident plan
        would pin on device)."""
        return self.a.nbytes

    @property
    def window_dispatches(self) -> int:
        """Window-chunk dispatches per run — ``steps`` per column tile —
        (excludes the per-tile epilogues)."""
        return self.steps * self.n_tiles

    # -- execution -----------------------------------------------------------

    def _stage_chunk(self, i: int, b_h: np.ndarray, vals_h: np.ndarray,
                     n0: int = 0):
        """Slice + pad chunk ``i`` of column tile ``[n0, n0+n_tile)`` on
        the host and start its transfer."""
        d = self._d
        wc, k0, nw = self.window_chunk, d.k0, d.nw
        w0 = i * wc
        w1 = min(nw, w0 + wc)
        pad = wc - (w1 - w0)
        vals_c = vals_h[:, w0:w1]
        cols_c = self._cols_h[:, w0:w1]
        rows_c = self._rows_h[:, w0:w1]
        q_c = self._q_h[:, w0:w1]
        if pad:
            # Tail chunk: pad with inert windows — q=0 skips them in the
            # kernel, and rows=MB*TM maps their slots out of [0, M) in BOTH
            # row layouts (interleaved: r*MB + bi >= MB*TM >= M;
            # block-major: bi*TM + r >= MB*TM >= M), so the jnp scatter
            # drops them.  Bit-identity is unconditional (the padded
            # windows contribute no adds at all).
            wpad = ((0, 0), (0, pad), (0, 0), (0, 0))
            vals_c = np.pad(vals_c, wpad)
            cols_c = np.pad(cols_c, wpad)
            rows_c = np.pad(rows_c, wpad, constant_values=d.mb * d.tm)
            q_c = np.pad(q_c, ((0, 0), (0, pad)))
        kb0 = w0 * k0
        kb1 = min(self.k, kb0 + wc * k0)
        n1 = min(self.n, n0 + self.n_tile)
        b_c = b_h[kb0:kb1, n0:n1]
        rpad = wc * k0 - b_c.shape[0]
        # Tail tile: pad with inert zero columns — per-column math is
        # independent, so real columns are bit-untouched and the padded
        # ones are sliced off at writeback.
        cpad = self.n_tile - (n1 - n0)
        if rpad or cpad:
            b_c = np.pad(b_c, ((0, rpad), (0, cpad)))
        return tuple(jax.device_put(x)
                     for x in (vals_c, cols_c, rows_c, q_c, b_c))

    def _c_tile(self, c_h: Optional[np.ndarray], j: int):
        """Device (M, n_tile) slice of the epilogue operand for tile ``j``
        (cached zeros when there is no ``c``; tail tile column-padded)."""
        if c_h is None:
            if self._zero_c is None:
                self._zero_c = jnp.zeros((self.m, self.n_tile), self.dtype)
            return self._zero_c
        n0 = j * self.n_tile
        n1 = min(self.n, n0 + self.n_tile)
        ct = c_h[:, n0:n1]
        if n1 - n0 < self.n_tile:
            ct = np.pad(ct, ((0, 0), (0, self.n_tile - (n1 - n0))))
        return jax.device_put(ct)

    def run(self, b, c=None, alpha=1.0, beta=0.0, *, values=None):
        """Stream the SpMM over the (N-tile × K-chunk) grid: per tile,
        ``steps`` window dispatches + one epilogue.

        ``b`` is ``(K, N)`` of the planned dtype — a host (numpy) array by
        preference: only tile-chunk-sized slices are transferred.
        ``values`` substitutes a new non-zero payload of the packed
        structure (sliced host-side per chunk, chunk-ahead like ``b`` —
        streamed pruned-weight serving double-buffers too).  The loop
        never blocks on device results, so chunk i+1's transfer overlaps
        chunk i's compute, across tile boundaries included.

        With ``n_tiles == 1`` the result is a device array (the PR-4
        path); with ``n_tiles > 1`` the stripes are assembled into a host
        (numpy) ``(M, N)`` array — the full C is exactly what the budget
        said does not fit on device.
        """
        b_h = np.asarray(b)
        if b_h.shape != (self.k, self.n) or b_h.dtype != self.dtype:
            raise ValueError(
                f"plan expects b of shape {(self.k, self.n)} dtype "
                f"{self.dtype}, got {b_h.shape} {b_h.dtype}")
        vals_h = self._vals_h
        if values is not None:
            vals_h = np.asarray(values)
            if vals_h.shape != self._vals_h.shape:
                raise ValueError(
                    f"values must have the packed shape "
                    f"{self._vals_h.shape}, got {vals_h.shape}")
        if self.n_tiles == 1:
            if c is None:
                c = self._c_tile(None, 0)
            else:
                # cast to the planned dtype (the AOT executable's
                # signature) — the same treatment the batched scheduler
                # gives mismatched c
                c = jnp.asarray(c, self.dtype)
                if c.shape != (self.m, self.n):
                    raise ValueError(
                        f"c must have shape {(self.m, self.n)}, "
                        f"got {c.shape}")
            alpha, beta = _ab_operands(self._ab_cache, alpha, beta)
            acc = jnp.zeros(self._acc_shape, jnp.float32)
            nxt = self._stage_chunk(0, b_h, vals_h)
            for i in range(self.steps):
                ops = nxt
                acc = self._step_exec(*ops, acc)   # async dispatch
                if i + 1 < self.steps:             # stage while it computes
                    nxt = self._stage_chunk(i + 1, b_h, vals_h)
            PLAN_STATS["dispatches"] += self.steps + 1
            PLAN_STATS["window_dispatches"] += self.steps
            return self._finish_exec(acc, c, alpha, beta)

        c_h = None
        if c is not None:
            c_h = np.asarray(c, self.dtype)
            if c_h.shape != (self.m, self.n):
                raise ValueError(f"c must have shape {(self.m, self.n)}, "
                                 f"got {c_h.shape}")
        alpha, beta = _ab_operands(self._ab_cache, alpha, beta)
        out = np.empty((self.m, self.n), self.dtype)
        pending = None          # one finished stripe awaiting writeback
        nxt = self._stage_chunk(0, b_h, vals_h, 0)
        for j in range(self.n_tiles):
            n0 = j * self.n_tile
            n1 = min(self.n, n0 + self.n_tile)
            # fresh accumulator per tile: the step executable donates its
            # acc argument, so each tile must start from its own buffer
            acc = jnp.zeros(self._acc_shape, jnp.float32)
            for i in range(self.steps):
                ops = nxt
                acc = self._step_exec(*ops, acc)   # async dispatch
                if i + 1 < self.steps:             # stage while it computes
                    nxt = self._stage_chunk(i + 1, b_h, vals_h, n0)
                elif j + 1 < self.n_tiles:         # ...across tiles too
                    nxt = self._stage_chunk(0, b_h, vals_h,
                                            (j + 1) * self.n_tile)
            stripe = self._finish_exec(acc, self._c_tile(c_h, j),
                                       alpha, beta)
            # Deferred-by-one writeback: materialize tile j-1's stripe
            # while tile j's dispatches queue — at most two stripes are
            # ever device-resident and the pipeline never drains.
            if pending is not None:
                s, p0, p1 = pending
                out[:, p0:p1] = np.asarray(s)[:, :p1 - p0]
            pending = (stripe, n0, n1)
        s, p0, p1 = pending
        out[:, p0:p1] = np.asarray(s)[:, :p1 - p0]
        PLAN_STATS["dispatches"] += self.n_tiles * (self.steps + 1)
        PLAN_STATS["window_dispatches"] += self.steps * self.n_tiles
        return out

    def __call__(self, b, c=None, alpha=1.0, beta=0.0, **kw):
        return self.run(b, c, alpha, beta, **kw)

    def __repr__(self) -> str:
        return (f"StreamingPlan(shape=({self.m}, {self.k})@{self.n}, "
                f"backend={self.backend!r}, window_chunk="
                f"{self.window_chunk}, steps={self.steps}, "
                f"n_tile={self.n_tile}, n_tiles={self.n_tiles})")


def plan(
    a: SparseTensor,
    n: int,
    *,
    backend: str = "auto",
    dtype=jnp.float32,
    mesh=None,
    device_bytes: Union[int, str, None] = None,
    stream: Optional[bool] = None,
    window_chunk: Optional[int] = None,
    n_tile: Optional[int] = None,
    autotune: Optional[str] = None,
    **opts,
) -> Union[SpmmPlan, "StreamingPlan"]:
    """Prepare ``alpha * A @ b + beta * c`` for dense operands of width ``n``.

    Performs padding/permutation precompute, backend resolution and
    executable compilation **once**; :meth:`SpmmPlan.run` then only invokes
    the cached executable.  Executables are shared across matrices whose
    bucketed geometry, logical shape, group size and dtypes coincide.

    ``mesh`` AOT-compiles the executable with the engine's multi-chip
    shardings (see :meth:`SpmmPlan._mesh_shardings`); a *group* plan can
    carry a mesh too, unifying the sharded and batched serving paths.
    ``a`` may be batched (``a.batch == G``) — or use :func:`plan_group`.

    ``device_bytes`` (an int budget, or ``"auto"`` to read the backend's
    reported memory limit) selects the out-of-core tier: when the resident
    working set — packed payload + ``b`` + ``c`` + output — exceeds the
    budget, a :class:`StreamingPlan` is returned, which streams a 2-D
    (K-window × N-tile) grid through a persistent C-stripe accumulator
    instead of pinning the slabs on device.  ``stream=True``/``False``
    forces the choice; ``window_chunk`` pins the windows-per-dispatch and
    ``n_tile`` the column-tile width (either otherwise sized from the
    budget — N stays untiled unless one full-N chunk alone would blow
    it).  Streaming requires an unbatched HFLEX matrix without a mesh —
    oversized batched/mesh plans raise rather than silently pinning more
    memory than the device has.

    ``autotune`` consults the persistent
    :class:`repro.sparse_api.autotune.TuningDB` at backend/tiling
    resolution time: ``"cached"`` applies a stored measured decision when
    one exists, ``"measure"`` additionally tunes on a miss (enumerate →
    perfmodel-prune → measure best-of-N, bit-identity guarded) and stores
    the result; ``None`` defers to ``$SEXTANS_AUTOTUNE`` (default
    ``"off"``).  Only knobs the caller left open are ever overridden —
    ``backend`` when ``"auto"``, ``window_chunk``/``n_tile`` when unset
    on a streaming plan — and the returned plan's ``tuned`` flag records
    whether a DB decision applied.  Mesh plans are never tuned.
    """
    with span("sextans.plan.build") as sp:
        mode = "off"
        if mesh is None:
            from .autotune import resolve_mode, resolve_plan_knobs

            mode = resolve_mode(autotune)
        budget: Optional[int] = None
        if device_bytes is not None:
            budget = (device_memory_budget() if device_bytes == "auto"
                      else int(device_bytes))
        if stream is None:
            stream = False
            if budget is not None:
                itemsize = jnp.dtype(dtype).itemsize
                m, k = a.shape
                working = a.nbytes + (k * n + 2 * m * n) * itemsize
                stream = working > budget
        tuned = False
        if mode != "off":
            backend, opts, window_chunk, n_tile, tuned = resolve_plan_knobs(
                a, n, dtype=jnp.dtype(dtype), mode=mode, backend=backend,
                stream=bool(stream), device_bytes=budget,
                window_chunk=window_chunk, n_tile=n_tile, opts=opts)
        if stream:
            if mesh is not None:
                raise ValueError(
                    "streaming plans cannot carry a mesh; shard rows across "
                    "chips first, then stream each shard (device_bytes applies "
                    "per chip)")
            pl = StreamingPlan(a, n, backend, opts, dtype=dtype,
                               device_bytes=budget, window_chunk=window_chunk,
                               n_tile=n_tile)
        else:
            if n_tile is not None:
                raise ValueError("n_tile applies to streaming plans only (pass "
                                 "stream=True or a device_bytes budget)")
            pl = SpmmPlan(a, n, backend, opts, dtype=dtype, mesh=mesh)
        pl.tuned = tuned
    pl.build_s = sp.wall_s
    return pl


def plan_group(
    tensors: Union[SparseTensor, Sequence[SparseTensor]],
    n: int,
    *,
    backend: str = "auto",
    dtype=jnp.float32,
    mesh=None,
    autotune: Optional[str] = None,
    **opts,
) -> SpmmPlan:
    """Prepare ONE executable for a whole group of bucket-mates.

    ``tensors`` is either a sequence of same-geometry SparseTensors —
    HFLEX stacked via :func:`repro.sparse_api.stack_hflex`, BSR via
    :func:`repro.sparse_api.stack_bsr` (the format is dispatched on) — or
    an already-stacked batched tensor.  The returned plan's
    :meth:`SpmmPlan.run` takes ``b`` of shape ``(G, K, N)`` (ragged-N
    callers pad their columns up to the planned ``n``) and executes the
    whole group as a single compiled-call dispatch; results are
    bit-identical to running each member through its own plan.
    ``run(values=...)`` substitutes a stacked non-zero payload of the same
    structure — N requests against the same pruned skeleton share one
    executable.

    ``autotune`` behaves as in :func:`plan` (group plans tune the backend
    choice only — they are always resident; the tuning key carries the
    group size, so a G=16 pool and a singleton tune independently).
    """
    with span("sextans.plan.build") as sp:
        if isinstance(tensors, SparseTensor):
            a = tensors
            if a.batch is None:
                a = (stack_bsr([a]) if a.format is Format.BSR
                     else stack_hflex([a]))
        else:
            ts = list(tensors)
            if ts and ts[0].format is Format.BSR:
                a = stack_bsr(ts)
            else:
                a = stack_hflex(ts)
        tuned = False
        if mesh is None:
            from .autotune import resolve_mode, resolve_plan_knobs

            mode = resolve_mode(autotune)
            if mode != "off":
                backend, opts, _, _, tuned = resolve_plan_knobs(
                    a, n, dtype=jnp.dtype(dtype), mode=mode, backend=backend,
                    stream=False, device_bytes=None, window_chunk=None,
                    n_tile=None, opts=opts, group=a.batch)
        pl = SpmmPlan(a, n, backend, opts, dtype=dtype, mesh=mesh)
        pl.tuned = tuned
    pl.build_s = sp.wall_s
    return pl


def plan_ragged(a: SparseTensor, rows: int, *, backend: str = "auto",
                dtype=jnp.float32, **opts) -> RaggedPlan:
    """Prepare the ragged grouped product of the stacked BSR tensor ``a``
    for ``rows`` expert-sorted token rows (:class:`RaggedPlan`)."""
    with span("sextans.plan.build") as sp:
        pl = RaggedPlan(a, rows, backend, opts, dtype=dtype)
    pl.build_s = sp.wall_s
    return pl
