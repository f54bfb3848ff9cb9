"""SextansEngine: the general-purpose SpMM engine (paper's HFlex, in JAX).

The paper's headline property is that *one synthesized accelerator serves
any SpMM* — no re-running synthesis/place/route per problem. The JAX
analogue of synthesis is XLA compilation: naive jit retraces per shape.
The engine restores the HFlex property by

1. packing every matrix into bucketed slab geometry (power-of-two LW /
   padded N), so distinct matrices hit the *same* compiled executable;
2. tracking executable-cache hits/misses (``stats``) the way the paper
   counts avoided place/route runs;
3. driving all data-dependent work (per-slab non-zero counts) through the
   scalar-prefetched pointer matrix ``q`` — contents change per problem,
   the compiled program does not;
4. treating ``alpha``/``beta`` as *traced* scalars (the kernel reads them
   from SMEM): an epilogue sweep is **zero** additional executables — they
   are no longer part of :meth:`signature`;
5. executing through :class:`repro.sparse_api.SpmmPlan` (``use_plans=True``,
   the default): per (matrix, N) pair the padding/permutation precompute,
   backend resolution and executable lookup happen **once**; the serving
   hot loop is a bare compiled call (results bit-identical to the unplanned
   path).  Set ``use_plans=False`` to route through the differentiable
   ``spmm`` entry point instead.

The engine is a thin stats-and-sharding wrapper over the unified front-end
:mod:`repro.sparse_api` (SparseTensor + backend registry); ``impl`` is a
registered backend name: "auto" (the default, platform-aware), "pallas"
(the format's Pallas kernel) or "jnp" (the XLA reference).  ``tn`` pins
the kernel's column tile; left None, the backend takes it from N
(``repro.sparse_api.hflex_lane``).

:meth:`SextansEngine.spmm_async` is the futures-based entry point: the
pack runs host-resident (``pack(device=False)``) on a worker thread, the
dispatch thread issues the compiled call (the plan owns the single
``device_put``), and the returned :class:`SpmmFuture` resolves to the
result — host packing overlaps device compute, the serving analogue of
the paper's off-chip-stream/PE overlap.  Engine state is lock-guarded so
the async pipeline's threads and the owning thread can share one engine.

Also provides the multi-chip execution plan: A row-blocks sharded across
the ``data`` axis (the paper's `row mod P` lifted to chips — C shards are
disjoint, the inner loop needs **zero** cross-chip collectives), B
column-tiles sharded across ``model``.  On the Pallas backends each chip
runs the kernel on its own row blocks under ``shard_map`` (XLA cannot
partition a kernel call itself).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.async_pipeline import PackExecutePipeline, SpmmFuture
from repro.core.partition import cdiv
from repro.core.sparse import SparseMatrix
from repro.tracing import span

# NOTE: repro.sparse_api is imported lazily inside methods — importing it
# here would cycle (sparse_api -> core.hflex -> core.__init__ -> engine).

__all__ = ["SextansEngine", "EngineStats"]


@dataclasses.dataclass
class EngineStats:
    packs: int = 0
    calls: int = 0            # logical SpMM problems served (group members count)
    dispatches: int = 0       # compiled-call dispatches issued (group: 1 for
                              # G members; streaming: window steps + epilogue)
    group_calls: int = 0      # batched group dispatches among the above
    abvec_group_calls: int = 0  # group dispatches carrying a per-member
                              # (alpha, beta) vector — epilogues folded into
                              # a shared group by the serving policy
    streamed: int = 0         # problems served through the out-of-core tier
    window_dispatches: int = 0  # K0-window-chunk dispatches (streaming,
                              # summed over column tiles)
    n_tiles: int = 0          # max column tiles any streamed call needed
    skinny_dispatches: int = 0  # SpMV-shaped dispatches (sparse_api.is_skinny)
    peak_payload_bytes: int = 0  # max device working set of a streamed call
    cache_hits: int = 0
    cache_misses: int = 0
    padded_slots: int = 0
    real_nnz: int = 0
    # -- plan-cache counters (plan_for's bounded dict; uniform visibility
    #    for warm-start claims — previously only exec misses were
    #    observable) --
    plan_cache_hits: int = 0
    plan_cache_misses: int = 0
    plan_cache_evictions: int = 0
    # -- autotuning (see repro.sparse_api.autotune) --
    tuned_dispatches: int = 0   # dispatches run through a DB-tuned plan
    tune_db_hits: int = 0       # TuningDB lookups resolved during plan builds
    tune_db_misses: int = 0
    # plan-build wall time (the ``sextans.plan.build`` span's seconds,
    # ``SpmmPlan.build_s``), split by whether the build compiled something
    # (cold: PLAN_STATS exec_misses grew — trace+compile and, in measure
    # mode, tuning measurement) or reused an executable of the plan cache
    # (warm)
    plan_builds_cold: int = 0
    plan_builds_warm: int = 0
    plan_build_cold_s: float = 0.0
    plan_build_warm_s: float = 0.0

    @property
    def hit_rate(self) -> float:
        total = self.cache_hits + self.cache_misses
        return self.cache_hits / total if total else 0.0

    @property
    def plan_cache_hit_rate(self) -> float:
        total = self.plan_cache_hits + self.plan_cache_misses
        return self.plan_cache_hits / total if total else 0.0

    @property
    def dispatches_per_call(self) -> float:
        """< 1.0 once batched group execution starts amortizing dispatch."""
        return self.dispatches / self.calls if self.calls else 0.0


class SextansEngine:
    """General-purpose SpMM executor with an HFlex executable cache."""

    #: State shared with the async pack pool / dispatch thread: every
    #: access outside ``__init__`` must hold ``self._lock`` (enforced by
    #: the ``lock-discipline`` rule of ``repro.analysis``).
    _lock_guarded = ("stats", "_seen_signatures", "_plans", "_pipe",
                     "last_streaming_plan")

    def __init__(
        self,
        tm: int = 128,
        k0: int = 4096,
        chunk: int = 8,
        tn: Optional[int] = None,
        impl: str = "auto",
        interleave: bool = True,
        bucket: bool = True,
        interpret: Optional[bool] = None,
        use_plans: bool = True,
        autotune: Optional[str] = None,
    ):
        self.tm, self.k0, self.chunk, self.tn = tm, k0, chunk, tn
        self.impl = impl
        self.interleave = interleave
        self.bucket = bucket
        self.interpret = interpret
        self.use_plans = use_plans
        #: autotune mode threaded into every plan build: "off" | "cached" |
        #: "measure" (None defers to $SEXTANS_AUTOTUNE; see
        #: repro.sparse_api.autotune).  Mutable config, not guarded state.
        self.autotune = autotune
        self.stats = EngineStats()
        #: the StreamingPlan the most recent spmm_streaming call ran
        #: through — per-call stats (steps, peak_payload_bytes) for callers
        #: like the serving scheduler, without re-deriving the cache key.
        self.last_streaming_plan = None
        self._seen_signatures: set = set()
        # (id(packed), n, dtype) -> (packed, SpmmPlan); the entry holds the
        # caller's object so its id stays live (and unique) while cached.
        # Bounded at PLAN_CACHE_CAP (see plan_for).
        self._plans: Dict[Tuple, Tuple] = {}
        # Engine state (stats counters, plan cache, signature set) is
        # mutated from worker/dispatch threads by the async serving
        # pipeline as well as by the owning thread — one reentrant lock
        # guards those mutations (counting, not dispatch, is serialized).
        self._lock = threading.RLock()
        self._pipe: Optional[PackExecutePipeline] = None

    # -- preprocessing ------------------------------------------------------

    def pack(self, a: SparseMatrix, device: bool = True) -> "SparseTensor":
        """Pack a host COO matrix into the engine's slab geometry.

        ``device=False`` keeps the payload **host-resident** (numpy
        leaves): safe to call from pack worker threads, never commits
        device memory at pack time (the plan tier device_puts once at
        dispatch) — so an over-budget payload can go straight to the
        streaming lane without ever existing on device.
        """
        from repro.sparse_api import Format, from_sparse_matrix

        with span("sextans.pack"):
            t = from_sparse_matrix(
                a, format=Format.HFLEX, tm=self.tm, k0=self.k0,
                chunk=self.chunk, interleave=self.interleave,
                bucket=self.bucket, device=device,
            )
        with self._lock:
            self.stats.packs += 1
            self.stats.real_nnz += t.nnz
            self.stats.padded_slots += int(np.prod(t.data.vals.shape)) - t.nnz
        return t

    def _as_tensor(self, packed) -> "SparseTensor":
        from repro.sparse_api import Format, SparseTensor
        from repro.sparse_api.tensor import PackedSpMM

        if isinstance(packed, SparseTensor):
            return packed
        if isinstance(packed, PackedSpMM):   # legacy callers
            return SparseTensor(data=packed, format=Format.HFLEX,
                                shape=(packed.m, packed.k))
        raise TypeError(f"expected SparseTensor/PackedSpMM, got {type(packed)}")

    # -- execution ----------------------------------------------------------

    def signature(self, packed, n: int, b=None) -> Tuple:
        """Executable identity: geometry + padded N + backend (everything
        that forces a recompile). Matrix *contents* are excluded — HFlex —
        and so are alpha/beta, which the kernel reads at run time.

        ``b`` is forwarded to backend resolution so custom ``auto`` policies
        that inspect the operand see the same value dispatch will; ``n`` is
        forwarded too, so such a policy sees the width even when only the
        width is known."""
        from repro.sparse_api import resolve_backend

        t = self._as_tensor(packed)
        backend = resolve_backend(self.impl, t, b, n=n)
        return (*t.geometry, self._npad(n), backend)

    def _npad(self, n: int) -> int:
        """N padded to the kernel's column tile (``tn``, or the lane
        ``hflex_lane`` picks from N)."""
        from repro.sparse_api import hflex_lane

        lane = self.tn or hflex_lane(n)
        return cdiv(n, lane) * lane

    #: plan_for keeps at most this many plans; oldest evicted first.
    PLAN_CACHE_CAP = 256

    def plan_for(self, packed, n: int, dtype=None, *, stream: bool = False,
                 device_bytes: Optional[int] = None,
                 window_chunk: Optional[int] = None,
                 n_tile: Optional[int] = None):
        """The engine's plan for (matrix, N) — built on first use, then a
        dictionary lookup.  Executables are shared across bucket-mates
        through the module-level plan cache.  ``stream=True`` builds/caches
        the out-of-core :class:`repro.sparse_api.StreamingPlan` instead
        (same cache, extended key).

        Keyed by ``id(packed)`` — the *caller-held* object, so legacy
        ``PackedSpMM`` inputs (which get wrapped in a fresh SparseTensor per
        call) still hit the cache.  The cached entry holds a reference to
        ``packed``, keeping the id stable while the entry lives; the cache
        is bounded (oldest-first eviction) so long-running serving loops do
        not pin unbounded device memory."""
        import jax.numpy as jnp

        from repro.sparse_api import plan as _plan

        if not stream and (device_bytes is not None
                           or window_chunk is not None
                           or n_tile is not None):
            # the cache key would not record them, so a streaming plan
            # could silently shadow the resident entry — refuse instead
            raise ValueError(
                "device_bytes/window_chunk/n_tile require stream=True "
                "(plan_for's non-stream path always builds resident plans)")
        dtype = jnp.dtype(dtype or jnp.float32)
        key = (id(packed), int(n), str(dtype))
        if stream:
            key += ("stream", device_bytes, window_chunk, n_tile)
        with self._lock:
            hit = self._plans.get(key)
            if hit is not None:
                self.stats.plan_cache_hits += 1
        if hit is not None:
            return hit[1]
        from repro.sparse_api import PLAN_STATS, TUNE_STATS

        # Snapshot the module counters around the build so this engine's
        # stats attribute the deltas to itself: a build that grew
        # exec_misses compiled something (cold); one that did not reused a
        # cached executable (warm).
        db_hits0 = TUNE_STATS["db_hits"]
        db_misses0 = TUNE_STATS["db_misses"]
        exec_misses0 = PLAN_STATS["exec_misses"]
        t = self._as_tensor(packed)
        if stream:
            pl = _plan(t, n, backend=self.impl, dtype=dtype, stream=True,
                       device_bytes=device_bytes, window_chunk=window_chunk,
                       n_tile=n_tile, tn=self.tn, interpret=self.interpret,
                       autotune=self.autotune)
        else:
            pl = _plan(t, n, backend=self.impl, dtype=dtype,
                       tn=self.tn, interpret=self.interpret,
                       autotune=self.autotune)
        build_s = pl.build_s
        cold = PLAN_STATS["exec_misses"] > exec_misses0
        with self._lock:
            self.stats.plan_cache_misses += 1
            self.stats.tune_db_hits += TUNE_STATS["db_hits"] - db_hits0
            self.stats.tune_db_misses += TUNE_STATS["db_misses"] - db_misses0
            if cold:
                self.stats.plan_builds_cold += 1
                self.stats.plan_build_cold_s += build_s
            else:
                self.stats.plan_builds_warm += 1
                self.stats.plan_build_warm_s += build_s
            while len(self._plans) >= self.PLAN_CACHE_CAP:
                self._plans.pop(next(iter(self._plans)))
                self.stats.plan_cache_evictions += 1
            self._plans[key] = (packed, pl)
        return pl

    def spmm(
        self,
        packed,
        b: jax.Array,
        c: Optional[jax.Array] = None,
        alpha: float = 1.0,
        beta: float = 0.0,
    ) -> jax.Array:
        from repro.sparse_api import is_skinny, spmm

        with span("sextans.engine.spmm"):
            t = self._as_tensor(packed)
            sig = self.signature(t, b.shape[1], b)
            with self._lock:
                if sig in self._seen_signatures:
                    self.stats.cache_hits += 1
                else:
                    self.stats.cache_misses += 1
                    self._seen_signatures.add(sig)
                self.stats.calls += 1
                self.stats.dispatches += 1
                if is_skinny(t, sig[-1], b.shape[1]):
                    self.stats.skinny_dispatches += 1
            if self.use_plans:
                # Pass the *caller's* object: the plan cache keys on its
                # id, so legacy PackedSpMM inputs hit the cache across
                # calls.
                pl = self.plan_for(packed, b.shape[1], b.dtype)
                if pl.tuned:
                    with self._lock:
                        self.stats.tuned_dispatches += 1
                return pl.run(b, c, alpha, beta)
            return spmm(t, b, c, alpha, beta, backend=self.impl,
                        tn=self.tn, interpret=self.interpret)

    def spmm_streaming(
        self,
        packed,
        b,
        c: Optional[jax.Array] = None,
        alpha: float = 1.0,
        beta: float = 0.0,
        *,
        device_bytes: Optional[int] = None,
        window_chunk: Optional[int] = None,
        n_tile: Optional[int] = None,
    ) -> jax.Array:
        """Execute one SpMM through the out-of-core streaming tier.

        The matrix's slab payload stays host-side; the 2-D (K-window ×
        N-tile) grid of ``repro.sparse_api.StreamingPlan`` streams chunks
        through a persistent C-stripe accumulator, so problems whose
        payload exceeds ``device_bytes`` still run — including ones whose
        *dense operand* is itself too wide for a single device-resident
        stripe.  ``b`` may be a host (numpy) array: only chunk-sized
        slices are ever transferred.  Results are bit-identical to
        :meth:`spmm` (tiled runs return host numpy).

        Counts as one served problem and ``window_dispatches + n_tiles``
        dispatches (one epilogue per column tile);
        ``stats.window_dispatches`` tracks the window steps,
        ``stats.n_tiles`` the column-tile high-water and
        ``stats.peak_payload_bytes`` the device working-set high-water.
        """
        t = self._as_tensor(packed)
        n = int(np.shape(b)[-1])               # shape only — never copy b
        dtype = jnp.dtype(getattr(b, "dtype", jnp.float32))
        pl = self.plan_for(packed, n, dtype, stream=True,
                           device_bytes=device_bytes,
                           window_chunk=window_chunk, n_tile=n_tile)
        sig = (*t.geometry, self._npad(n), pl.backend, "stream",
               pl.window_chunk, pl.n_tile)
        with self._lock:
            self.last_streaming_plan = pl
            if pl.tuned:
                self.stats.tuned_dispatches += 1
            if sig in self._seen_signatures:
                self.stats.cache_hits += 1
            else:
                self.stats.cache_misses += 1
                self._seen_signatures.add(sig)
            self.stats.calls += 1
            self.stats.streamed += 1
            self.stats.dispatches += pl.window_dispatches + pl.n_tiles
            self.stats.window_dispatches += pl.window_dispatches
            self.stats.n_tiles = max(self.stats.n_tiles, pl.n_tiles)
            self.stats.peak_payload_bytes = max(self.stats.peak_payload_bytes,
                                                pl.peak_payload_bytes)
        return pl.run(b, c, alpha, beta)

    def spmm_group(
        self,
        tensors,
        b: jax.Array,
        c: Optional[jax.Array] = None,
        alpha: float = 1.0,
        beta: float = 0.0,
    ) -> jax.Array:
        """Execute a whole group of bucket-mates as ONE dispatch.

        ``tensors`` is a sequence of same-geometry SparseTensors (HFLEX
        bucket-mates, or BSR weights sharing tiling — the format is
        dispatched to ``stack_hflex`` / ``stack_bsr``) or an
        already-stacked batched tensor; ``b`` is the stacked dense
        operand ``(G, K, N)`` (``c`` likewise ``(G, M, N)`` or None).
        Returns the stacked ``(G, M, N)`` result.

        Every member counts as one served problem against the *shared*
        executable signature (G bucket-mates = 1 miss + G-1 hits — the
        HFlex story), but only one dispatch is issued.

        ``alpha``/``beta`` may each be a scalar or a ``(G,)`` vector of
        per-member epilogue coefficients (the serving policy's epilogue
        fold): member ``g`` computes ``alpha[g] * A_g @ B_g + beta[g] *
        C_g``, bit-identical to a scalar call with that member's
        coefficients.
        """
        from repro.sparse_api import Format, is_skinny
        from repro.sparse_api import plan_group as _plan_group
        from repro.sparse_api import stack_bsr, stack_hflex

        if isinstance(tensors, (list, tuple)):
            ts = [self._as_tensor(x) for x in tensors]
            if ts and ts[0].format is Format.BSR:
                t = stack_bsr(ts)
            else:
                t = stack_hflex(ts)
        else:
            t = self._as_tensor(tensors)
        g = t.batch
        if g is None:
            raise ValueError("spmm_group expects a stacked (batched) tensor "
                             "or a sequence of bucket-mates")
        b = jnp.asarray(b)
        n = b.shape[-1]
        sig = self.signature(t, n, b)
        ab_vec = jnp.ndim(alpha) > 0 or jnp.ndim(beta) > 0
        with self._lock:
            for _ in range(g):
                if sig in self._seen_signatures:
                    self.stats.cache_hits += 1
                else:
                    self.stats.cache_misses += 1
                    self._seen_signatures.add(sig)
            self.stats.calls += g
            self.stats.dispatches += 1
            self.stats.group_calls += 1
            if ab_vec:
                self.stats.abvec_group_calls += 1
            if is_skinny(t, sig[-1], n):
                self.stats.skinny_dispatches += 1
        from repro.sparse_api import TUNE_STATS

        # group plans bypass plan_for's cache — attribute their TuningDB
        # traffic here so engine stats stay uniform across paths
        db_hits0 = TUNE_STATS["db_hits"]
        db_misses0 = TUNE_STATS["db_misses"]
        pl = _plan_group(t, n, backend=self.impl, dtype=b.dtype,
                         tn=self.tn, interpret=self.interpret,
                         autotune=self.autotune)
        with self._lock:
            self.stats.tune_db_hits += TUNE_STATS["db_hits"] - db_hits0
            self.stats.tune_db_misses += TUNE_STATS["db_misses"] - db_misses0
            if pl.tuned:
                self.stats.tuned_dispatches += 1
        return pl.run(b, c, alpha, beta)

    def stats_snapshot(self) -> EngineStats:
        """A consistent copy of the counters, safe to diff around a
        dispatch while the async pipeline's threads keep mutating them."""
        with self._lock:
            return dataclasses.replace(self.stats)

    # -- async pipeline -----------------------------------------------------

    def pipeline(self, pack_threads: Optional[int] = None) -> PackExecutePipeline:
        """The engine's lazily created pack/execute pipeline (pack worker
        pool + one dispatch thread; see :mod:`repro.core.async_pipeline`).
        Shared by every :meth:`spmm_async` call; ``close()`` joins it."""
        with self._lock:
            if self._pipe is None:
                self._pipe = PackExecutePipeline(pack_threads)
            return self._pipe

    def spmm_async(
        self,
        a: SparseMatrix,
        b,
        c=None,
        alpha: float = 1.0,
        beta: float = 0.0,
    ) -> SpmmFuture:
        """Non-blocking ``pack + spmm``: returns a :class:`SpmmFuture`
        immediately.

        The pack runs **host-resident** (``pack(device=False)``) on a pack
        worker thread; the dispatch thread then issues the compiled call —
        the plan performs the single ``device_put`` there — and resolves
        the future with the *device* result (itself an async value under
        JAX dispatch; ``np.asarray(fut.result())`` materializes it).
        Several in-flight calls pack concurrently while the dispatch
        thread pipelines their launches in submit order, so host packing
        overlaps device compute.  Results are bit-identical to
        ``spmm(pack(a), ...)``; pack/dispatch exceptions resolve the
        future instead of being swallowed.
        """
        pipe = self.pipeline()
        fut = SpmmFuture()
        bn = np.asarray(b)
        cn = None if c is None else np.asarray(c)
        pf = pipe.submit_pack(self.pack, a, False)

        def dispatch():
            try:
                t = pf.result()
                out = self.spmm(t, jnp.asarray(bn),
                                None if cn is None else jnp.asarray(cn),
                                alpha, beta)
                fut._set_result(out)
            except Exception as exc:      # noqa: BLE001 — owned by the future
                fut._set_exception(exc)

        pipe.submit_dispatch(dispatch)
        return fut

    def close(self) -> None:
        """Join the async pipeline threads, if any were started."""
        with self._lock:
            pipe, self._pipe = self._pipe, None
        if pipe is not None:
            pipe.shutdown()

    def __call__(self, a: SparseMatrix, b, c=None, alpha: float = 1.0, beta: float = 0.0):
        return self.spmm(self.pack(a), jnp.asarray(b),
                         None if c is None else jnp.asarray(c), alpha, beta)

    # -- distribution plan --------------------------------------------------

    @staticmethod
    def shard_specs(data_axis: str = "data", model_axis: str = "model") -> Dict[str, P]:
        """PartitionSpecs for the sharded SpMM:

        * slabs (MB, NW, R, L): MB over data — each chip owns disjoint row
          blocks => disjoint C rows => no collective in the compute loop
          (the paper's disjoint-PE property, Eq. 4, at chip scale);
        * B (K, N): N over model — the N0 column-tile loop of Eq. 2 at chip
          scale; replicated over data (one broadcast per window, amortized);
        * C (M, N): M over data, N over model — fully disjoint shards.
        """
        return {
            "vals": P(data_axis, None, None, None),
            "cols": P(data_axis, None, None, None),
            "rows": P(data_axis, None, None, None),
            "q": P(data_axis, None),
            "nse": P(data_axis, None),
            "b": P(None, model_axis),
            "c": P(data_axis, model_axis),
        }

    def sharded_spmm_fn(self, mesh: Mesh, packed, n: int,
                        alpha: float = 1.0, beta: float = 0.0):
        """Build a sharded SpMM callable for execution on a mesh.

        Routed through :class:`repro.sparse_api.SpmmPlan` with
        ``plan(..., mesh=mesh)``: the executable is AOT-compiled ONCE with
        the multi-chip shardings of :meth:`shard_specs` and shared through
        the module-level plan cache (bucket-mates on the same mesh reuse
        it) — the multi-chip path and the batched serving path now run on
        one plan abstraction, and a *group* plan can carry a mesh the same
        way (``plan_group(..., mesh=)``).

        The returned ``fn(a, b, c)`` keeps the legacy signature; ``a`` must
        share the planned sparsity *structure* (its ``values`` payload is
        substituted per call — pass the planned matrix itself, or a
        same-structure weight update).  A structurally different ``a`` is
        rejected (checked once per distinct object, by identity first and
        content only on the first sighting), never silently mis-executed
        against the planned indices.
        """
        from repro.sparse_api import plan as _plan

        t = self._as_tensor(packed)
        pl = _plan(t, n, backend=self.impl, mesh=mesh,
                   tn=self.tn, interpret=self.interpret)
        d_plan = t.data
        verified: Dict[int, object] = {}   # id(cols leaf) -> leaf (kept live)

        def fn(a=None, b=None, c=None):
            values = None
            if a is not None:
                ta = self._as_tensor(a)
                d = ta.data
                if d.cols is not d_plan.cols and id(d.cols) not in verified:
                    same = (np.array_equal(d.cols, d_plan.cols)
                            and np.array_equal(d.rows, d_plan.rows)
                            and np.array_equal(d.q, d_plan.q))
                    if not same:
                        raise ValueError(
                            "sharded_spmm_fn: `a` has a different sparsity "
                            "structure than the planned matrix; only the "
                            "values payload is substituted per call — "
                            "build a new sharded fn for a new structure")
                    verified[id(d.cols)] = d.cols
                values = ta.values
            return pl.run(b, c, alpha, beta, values=values)

        fn.plan = pl
        return fn
