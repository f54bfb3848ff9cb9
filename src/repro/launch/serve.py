"""Serving drivers.

Two serving paths, matching the paper's two deployment stories:

1. **SpMM serving** (the paper's own workload): C = αAB + βC requests of
   arbitrary matrix sizes through one SextansEngine — one compiled
   executable set (HFlex), no re-synthesis per problem.  The serving loop
   is a *geometry-bucketing scheduler* (:class:`SpmmScheduler`):
   ``submit()`` accumulates requests, ``flush()`` groups them by bucketed
   slab geometry × padded-N × dtype × epilogue, stacks every group into
   one ``(G, ...)`` payload (``repro.sparse_api.stack_hflex``) and
   executes it as ONE compiled-call dispatch (one batch-grid kernel launch
   on the Pallas path, one vmapped XLA call on the ``jnp`` path), then
   scatters results back in request order — dispatch overhead amortizes
   G-fold, the analogue of keeping every HBM channel busy with independent
   problems.  Results are bit-identical to per-request execution.

   With ``async_pipeline=True`` the scheduler becomes a **pipelined
   producer/consumer** (the paper's off-chip-stream/PE overlap lifted to
   the serving tier): ``submit()`` returns a :class:`SpmmFuture`
   immediately and starts the request's *host-resident* pack
   (``pack_hflex(device=False)`` — numpy leaves, no device touch) on a
   pack worker thread; ``flush()`` is non-blocking and hands the batch to
   a dispatch thread that forms the same groups as the synchronous path
   (request packs ran concurrently; grouping waits for them all so it
   stays deterministic), stacks each group host-side on the workers, and
   launches each group's compiled call **as soon as its group pack
   completes** — so flush N+1 packs while flush N computes, and within a
   flush, group g+1 packs/stacks while group g runs on device.  Futures
   resolve in submit order, results stay bit-identical to the synchronous
   path, and worker exceptions propagate to the owning future (the failed
   request is restored to the queue for retry, as the synchronous path
   restores its queue on failure).  The hidden host time is reported as
   ``overlap_s`` / ``pack_hidden_fraction``.

   ``serve_spmm_requests`` wraps the scheduler for one-shot pools and
   reports the compile-cache hit rate plus grouping stats
   (``groups``, ``batched_fraction``, ``dispatches_per_request``) and
   ``compute_gflops`` (wall − non-hidden preprocessing, matching how the
   paper separates preprocessing from execution).  With a ``device_bytes``
   budget, requests whose packed payload exceeds it take the *out-of-core
   streaming lane* (``SextansEngine.spmm_streaming``): K0-window chunks
   stream through a persistent C accumulator — multiple dispatches per
   request, tracked in ``streamed`` / ``window_dispatches`` /
   ``peak_payload_bytes``.  Because packing is host-resident, an
   over-budget payload now reaches the streaming lane without ever having
   existed on device (the pack-time OOM the resident pack mode had).

2. **LM serving**: prefill + token-by-token decode with a KV/state cache
   (examples/serve_lm.py drives this at CPU scale; the decode dry-run cells
   prove the production sharding).
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.async_pipeline import PackExecutePipeline, SpmmFuture
from repro.core.engine import SextansEngine
from repro.core.sparse import SparseMatrix
from repro.launch.policy import GroupSketch, MergePolicy
from repro.sparse_api import (Format, SparseTensor, bucket_block_count,
                              is_skinny, repad_lw, resolve_backend,
                              stack_bsr, stack_hflex)

__all__ = ["SpmmRequest", "SpmmFuture", "SpmmScheduler", "MergePolicy",
           "serve_spmm_requests", "lm_generate"]


@dataclasses.dataclass
class SpmmRequest:
    """One ``C = alpha * A @ B + beta * C`` serving request.

    ``a`` is either a host COO :class:`SparseMatrix` (packed HFLEX by the
    scheduler's pack stage) or an already-packed :class:`SparseTensor` —
    the pruned-model serving form: a BSR weight skeleton packed once and
    submitted many times rides the pack stage as a passthrough, and
    same-geometry BSR requests group into one batched dispatch exactly
    like HFLEX bucket-mates.

    ``deadline_s`` is the request's latency budget in seconds *relative
    to submit time* (None = no deadline): the background flusher
    (``SpmmScheduler(background_flush=True)``) admits the request's group
    no later than ``deadline_margin_s`` before it expires.  ``priority``
    orders admitted groups within a flush (higher first; ties by ticket).
    Both are validated at ``submit()`` — negative or NaN values are
    rejected with a ``ValueError``, never silently queued.
    """

    a: Union[SparseMatrix, SparseTensor]
    b: np.ndarray
    c: Optional[np.ndarray] = None
    alpha: float = 1.0
    beta: float = 0.0
    deadline_s: Optional[float] = None
    priority: float = 0.0


def _embed(t, m_cap: int, k_cap: int):
    """View an HFLEX SparseTensor as the same matrix inside a larger
    (m_cap, k_cap) zero matrix.  Pure metadata: slab payloads are
    untouched, only the static logical bounds grow — the scheduler uses
    this to stack bucket-mates whose logical shapes are ragged (the extra
    rows/cols are zero, results are sliced back, bit-identically)."""
    from repro.sparse_api import SparseTensor

    d = dataclasses.replace(t.data, m=m_cap, k=k_cap)
    return SparseTensor(data=d, format=t.format, shape=(m_cap, k_cap))


def _request_flops(r: SpmmRequest) -> float:
    """Problem-size FLOPs of one request; packed (SparseTensor) requests
    use the stored-cell count the way SparseMatrix.problem_size_flop does."""
    n = r.b.shape[1]
    if isinstance(r.a, SparseTensor):
        return 2 * r.a.nnz * n + 3 * r.a.shape[0] * n
    return r.a.problem_size_flop(n)


@dataclasses.dataclass
class _Entry:
    """One queued request: its ticket, and — in async mode — the owning
    future plus the in-flight pack (``pack``) / packed tensor state.
    ``submit_ts`` (``time.monotonic()``) anchors the request's latency
    sample and its ``deadline_s`` expiry."""

    ticket: int
    request: SpmmRequest
    future: Optional[SpmmFuture] = None
    pack: Any = None          # concurrent.futures.Future of _pack_host
    tensor: Any = None        # host-resident SparseTensor once packed
    submit_ts: float = 0.0


@dataclasses.dataclass
class _FlushCounters:
    """Per-flush dispatch accounting, shared by the sync and async paths."""

    groups: int = 0
    dispatches: int = 0
    batched: int = 0
    streamed: int = 0
    window_disp: int = 0
    n_tiles: int = 0          # column-tile high-water among streamed requests
    skinny: int = 0           # dispatches that resolved to the SpMV lane
    peak: int = 0
    # cost-model policy accounting: near-miss bucket merges applied this
    # flush, dispatches they saved (members - 1 per merge cluster), and
    # requests whose (alpha, beta) rode a folded per-member vector
    merged_groups: int = 0
    merge_saved: int = 0
    folded: int = 0
    # engine-stat deltas attributed to this flush (autotuning + plan cache;
    # see EngineStats): dispatches that ran a DB-tuned plan, TuningDB
    # lookups resolved while building this flush's plans, and the cold
    # (compiled) vs warm (plan-cache) plan-build wall split.
    tuned: int = 0
    db_hits: int = 0
    db_misses: int = 0
    build_cold_s: float = 0.0
    build_warm_s: float = 0.0


class SpmmScheduler:
    """Geometry-bucketing SpMM serving scheduler (submit / flush).

    ``submit(request)`` queues a request; ``flush()`` executes everything
    queued.  Inside a flush, requests whose packed tensors share a
    bucketed slab geometry (HFlex bucket-mates), padded dense width, dtype
    and epilogue scalars are stacked into one batched dispatch
    (``SextansEngine.spmm_group``); ragged logical shapes within a bucket
    are embedded in the group's bounding (M, K) and ragged N is padded up
    to the bucket — both bit-exactly (zero columns/rows never contribute,
    and segment-sum prefixes are exact).  Everything else executes as
    singleton plan calls.  Packing is **host-resident** end to end
    (``pack_hflex(device=False)``): slab payloads stay numpy until the
    plan tier performs the single ``device_put`` at dispatch.

    **Synchronous mode** (default): ``submit`` returns an int ticket,
    ``flush()`` blocks and returns results in submit order.  On failure
    the queue is restored (ahead of anything submitted since), so one
    malformed request cannot silently drop the rest.

    **Async pipeline mode** (``async_pipeline=True``): ``submit`` returns
    a :class:`SpmmFuture` immediately and starts the pack on a worker
    thread; ``flush()`` is non-blocking — it hands the batch to the
    dispatch thread and returns the batch's futures.  The dispatch stage
    launches each group as soon as its (host) pack completes, so packing
    overlaps device execution across *and* within flushes; futures resolve
    in submit order with results bit-identical to synchronous ``flush()``.
    A pack/dispatch exception resolves the owning future with that
    exception and restores the failed request to the queue (retry on the
    next flush — remove it with :meth:`cancel` to drop it instead);
    unaffected requests still execute.

    ``device_bytes`` adds the *out-of-core streaming lane*: a request whose
    packed payload exceeds the budget bypasses group stacking and executes
    through :meth:`SextansEngine.spmm_streaming` — a 2-D (K-window ×
    N-tile) grid of chunks through a persistent C-stripe accumulator,
    multiple dispatches per request, still bit-identical (``n_tile``
    overrides the plan's column-tile width).  Oversized traffic therefore
    no longer fails or pins more device memory than exists; it just rides
    the streaming tier.

    **Cost-model policy mode** (``policy=`` a
    :class:`repro.launch.policy.MergePolicy`): two exact-key restrictions
    relax, both provably bit-identical per member:

    * *epilogue folding* — ``(alpha, beta)`` leave the group key for
      backends whose batched path applies them as a per-member ``(G,)``
      vector (``policy.fold_epilogue``; the general case of the gate —
      same FMA per member as the scalar epilogue), so mixed-epilogue
      bucket-mates share one dispatch;
    * *near-miss merging* — after grouping, a merge pass re-prices
      adjacent LW / padded-N / BSR-block-count buckets with
      ``repro.core.perfmodel.packed_event_cycles`` and merges them into
      one padded group exactly when the merged dispatch is modeled
      cheaper than the split dispatches (padding waste vs per-dispatch
      overhead; narrow members are widened with the inert
      ``repad_lw`` zero slots).  ``stats["merged_groups"]`` /
      ``["merge_saved_dispatches"]`` / ``["folded_requests"]`` account
      for both.

    **Continuous batching** (``background_flush=True``, requires
    ``async_pipeline=True``; implies a default policy): a daemon flusher
    thread replaces caller-driven ``flush()`` as the admission mechanism —
    it admits a forming group when the cost model calls it *full enough*
    (``policy.full_enough`` — modeled work amortizes the per-dispatch
    overhead) or when its most urgent member is within
    ``deadline_margin_s`` of its ``deadline_s`` expiry; admitted groups
    dispatch in priority order.  ``flush()`` still works (final drain);
    :meth:`shutdown` stops the flusher, drains whatever is queued — a
    half-formed merged group included — and joins the pipeline, so no
    future is ever stranded.  Per-request latency (submit → future
    resolution) is recorded; ``latency_p50`` / ``latency_p99`` report the
    distribution (0.0 while empty).

    ``stats`` accumulates across flushes:

    * ``requests`` / ``groups`` / ``dispatches`` — problems served vs
      compiled calls issued.  ``dispatches`` counts *every* compiled call
      consistently at request granularity: a group contributes 1 for its G
      members together, a singleton 1, and a streamed request its
      ``window_dispatches + n_tiles`` (one epilogue per column tile; so
      ``dispatches_per_request`` < 1 measures batching amortization and
      > 1 measures streaming depth);
    * ``batched_requests`` → ``batched_fraction`` — how much traffic rode
      a group dispatch;
    * ``streamed`` / ``window_dispatches`` / ``n_tiles`` /
      ``peak_payload_bytes`` — the streaming lane: requests routed,
      window-chunk dispatches issued (summed over column tiles), the
      column-tile high-water, and the device working-set high-water of any
      streamed request;
    * ``skinny_dispatches`` — SpMV-shaped dispatches (singleton or
      group), as ``repro.sparse_api.is_skinny`` decides;
    * ``preprocess_s`` vs ``wall_s`` — pack() time separated from
      execution, the paper's preprocessing/execution split;
    * ``overlap_s`` / ``pack_stall_s`` — async mode: pack time hidden
      behind the pipeline (workers packed while the dispatch stage was
      busy) vs pack time the dispatch stage actually had to wait for;
      ``pack_hidden_fraction = overlap_s / preprocess_s``;
    * ``failed`` — requests whose future resolved with an exception (and
      were restored to the queue);
    * ``last_flush`` — the same counters scoped to the most recent flush
      (per-flush reporting: multi-dispatch streaming requests made the
      cumulative numbers alone ambiguous).
    """

    #: State shared between submitters, flush, the async dispatch thread
    #: and the background flusher: every access outside ``__init__`` must
    #: hold ``self._lock`` (enforced by the ``lock-discipline`` rule of
    #: ``repro.analysis``).
    _lock_guarded = ("_pending", "_next_ticket", "stats", "_latencies")

    #: bounded latency-sample window (most recent kept)
    LATENCY_CAP = 65536

    def __init__(self, engine: Optional[SextansEngine] = None,
                 max_group: int = 64,
                 device_bytes: Optional[int] = None,
                 window_chunk: Optional[int] = None,
                 n_tile: Optional[int] = None,
                 async_pipeline: bool = False,
                 pack_threads: Optional[int] = None,
                 autotune: Optional[str] = None,
                 policy: Optional[MergePolicy] = None,
                 background_flush: bool = False,
                 flush_poll_s: float = 0.002,
                 deadline_margin_s: float = 0.005):
        self.engine = engine or SextansEngine(tm=128, k0=512, chunk=8,
                                              impl="jnp")
        if autotune is not None:
            # thread the tuning mode into every plan the engine builds for
            # this scheduler ("off" | "cached" | "measure"); omit to keep
            # whatever mode the caller's engine already carries
            self.engine.autotune = autotune
        if max_group < 1:
            raise ValueError("max_group must be >= 1")
        if background_flush and not async_pipeline:
            raise ValueError(
                "background_flush requires async_pipeline=True — the "
                "flusher hands admitted batches to the dispatch thread")
        self.max_group = max_group
        self.device_bytes = device_bytes
        self.window_chunk = window_chunk
        self.n_tile = n_tile
        self.async_pipeline = bool(async_pipeline)
        #: cost-model grouping policy; continuous batching defaults one in
        #: so admission has a "full enough" signal.  None = exact-key
        #: grouping with scalar epilogues (the legacy behaviour).
        self.policy = policy if policy is not None else (
            MergePolicy() if background_flush else None)
        self.flush_poll_s = float(flush_poll_s)
        self.deadline_margin_s = float(deadline_margin_s)
        self._pipe = (PackExecutePipeline(pack_threads)
                      if self.async_pipeline else None)
        self._lock = threading.Lock()
        self._pending: List[_Entry] = []
        self._next_ticket = 0
        self._latencies: List[float] = []
        self.stats: Dict[str, Any] = {
            "requests": 0,
            "groups": 0,
            "dispatches": 0,
            "batched_requests": 0,
            "streamed": 0,
            "window_dispatches": 0,
            "n_tiles": 0,
            "skinny_dispatches": 0,
            "peak_payload_bytes": 0,
            "merged_groups": 0,
            "merge_saved_dispatches": 0,
            "folded_requests": 0,
            "tuned_dispatches": 0,
            "tune_db_hits": 0,
            "tune_db_misses": 0,
            "plan_build_cold_s": 0.0,
            "plan_build_warm_s": 0.0,
            "failed": 0,
            "flushes": 0,
            "flusher_flushes": 0,
            "flusher_errors": 0,
            "wall_s": 0.0,
            "preprocess_s": 0.0,
            "overlap_s": 0.0,
            "pack_stall_s": 0.0,
            "flops": 0.0,
            "last_flush": {},
        }
        self._stop_flusher = threading.Event()
        self._flusher: Optional[threading.Thread] = None
        if background_flush:
            self._flusher = threading.Thread(
                target=self._flusher_loop, name="spmm-flusher", daemon=True)
            self._flusher.start()

    # -- queueing -----------------------------------------------------------

    def submit(self, request: SpmmRequest) -> Union[int, SpmmFuture]:
        """Queue a request.  Synchronous mode returns its int ticket
        (flush-order position); async mode returns a :class:`SpmmFuture`
        immediately and starts the host pack on a worker thread.

        Operands are normalized to ndarrays here (array-likes accepted);
        SLO fields are validated here too — a negative or NaN
        ``deadline_s`` / ``priority`` raises immediately rather than
        poisoning the background flusher's admission arithmetic later."""
        b = np.asarray(request.b)
        if b.ndim != 2:
            raise ValueError("SpmmRequest.b must be 2-D (K, N)")
        c = None if request.c is None else np.asarray(request.c)
        if c is not None and c.shape != (request.a.shape[0], b.shape[1]):
            raise ValueError(
                f"SpmmRequest.c must be (M, N) = "
                f"{(request.a.shape[0], b.shape[1])}, got {c.shape}")
        if request.deadline_s is not None:
            d = float(request.deadline_s)
            if not np.isfinite(d) or d < 0:
                raise ValueError(
                    f"SpmmRequest.deadline_s must be a finite, "
                    f"non-negative number of seconds, got "
                    f"{request.deadline_s!r}")
        p = float(request.priority)
        if not np.isfinite(p):
            raise ValueError(f"SpmmRequest.priority must be a finite "
                             f"number, got {request.priority!r}")
        if b is not request.b or c is not request.c:
            request = dataclasses.replace(request, b=b, c=c)
        now = time.monotonic()
        # Ticket allocation and enqueue are one critical section: the
        # flush resolves futures by iterating _pending and assumes it is
        # ticket-ordered, so concurrent submitters must not interleave
        # between taking a ticket and appending.
        if not self.async_pipeline:
            with self._lock:
                ticket = self._next_ticket
                self._next_ticket += 1
                self._pending.append(_Entry(ticket, request, submit_ts=now))
            return ticket
        pack = self._pipe.submit_pack(self._pack_host, request)
        with self._lock:
            ticket = self._next_ticket
            self._next_ticket += 1
            e = _Entry(ticket, request, future=SpmmFuture(ticket),
                       submit_ts=now)
            e.pack = pack
            self._pending.append(e)
        return e.future

    def cancel(self, ticket: int) -> bool:
        """Remove a pending (not yet flushed) request by ticket — e.g. a
        request whose future failed and was restored for retry.  Its
        unresolved future (if any) is resolved with ``CancelledError``.
        Returns True if an entry was removed."""
        with self._lock:
            for i, e in enumerate(self._pending):
                if e.ticket == ticket:
                    del self._pending[i]
                    break
            else:
                return False
        if e.future is not None and not e.future.done():
            e.future._set_exception(concurrent.futures.CancelledError())
        return True

    @property
    def pending(self) -> int:
        with self._lock:
            return len(self._pending)

    def shutdown(self, wait: bool = True) -> None:
        """Stop the background flusher (if any), drain the queue, and
        join the async pipeline threads (no-op in synchronous mode).

        With ``wait=True`` everything still pending — including a
        half-formed merged group the flusher had not yet admitted — is
        flushed before the pipeline joins, so every outstanding future
        resolves and the queue cannot strand work."""
        if self._flusher is not None:
            self._stop_flusher.set()
            self._flusher.join()
            self._flusher = None
        if wait and self.async_pipeline and self.pending:
            self.flush()                     # final drain
        if self._pipe is not None:
            self._pipe.shutdown(wait=wait)

    def __enter__(self) -> "SpmmScheduler":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()

    # -- pack stage (host-resident, worker-thread safe) ----------------------

    def _pack_host(self, r: SpmmRequest):
        """Pack one request's matrix host-resident; returns (tensor, s).

        Already-packed requests (``r.a`` a :class:`SparseTensor` — the
        pruned-weight serving form) pass straight through: the skeleton
        was packed once up front, so per-request pack cost is zero."""
        if isinstance(r.a, SparseTensor):
            return r.a, 0.0
        t0 = time.perf_counter()
        t = self.engine.pack(r.a, device=False)
        return t, time.perf_counter() - t0

    def _group_key(self, t, r: SpmmRequest):
        from repro.core.hflex import bucket_geometry

        d = t.data
        # Epilogue fold gate (policy mode): when the resolved backend's
        # batched path applies (alpha, beta) as a per-member (G,) vector
        # bit-identically, the scalars leave the key — (None, None) marks
        # a folded group and _prep_group rebuilds the member vector.
        # Backends outside the gate keep the exact-epilogue key.
        a_k: Any = float(r.alpha)
        b_k: Any = float(r.beta)
        if self.policy is not None and self.policy.fold_epilogue(
                resolve_backend(self.engine.impl, t, r.b)):
            a_k = b_k = None
        if t.format is Format.BSR:
            # BSR bucket-mates: same weight tiling (K', F', TK, TF) and a
            # shared padded block-count bucket (stack_bsr pads every member
            # up to it), same logical shape, padded dense width, dtype and
            # epilogue.  Block *counts* may differ within the bucket.
            nb_b = bucket_block_count(d.nb)
            n_b = bucket_geometry(1, 1, 1, r.b.shape[1])[3]
            # ``t.shape`` is deliberate, not a compile hazard: stack_bsr
            # only accepts members with identical logical (M, K), and the
            # executable cache keys on the *padded* bucket geometry —
            # distinct weight shapes could never share a dispatch anyway.
            return (t.format, (nb_b, d.k, d.f, d.tk, d.tf), t.shape, n_b,  # repro: ignore[trace-hazard] -- grouping key, not a jit key; stack_bsr needs exact (M, K)
                    np.dtype(np.asarray(r.b).dtype).str, a_k, b_k)
        n_b = bucket_geometry(d.mb, d.nw, d.lw, r.b.shape[1])[3]
        return (t.format, t.geometry, None, n_b,
                np.dtype(np.asarray(r.b).dtype).str, a_k, b_k)

    def _route(self, e: _Entry, groups: Dict, stream_lane: List) -> None:
        """Send a packed entry to its bucket group or the streaming lane."""
        if (self.device_bytes is not None
                and e.tensor.nbytes > self.device_bytes):
            # Oversized: route around group stacking — stacking would
            # multiply the resident payload by G, the opposite of what
            # an over-budget matrix needs.
            stream_lane.append(e)
        else:
            key = self._group_key(e.tensor, e.request)
            groups.setdefault(key, []).append(e)

    def _prep_group(self, key, chunk: List[_Entry]):
        """Host-side group pack stage: embed the bucket-mates in the
        geometry-constant bounds, stack them (host-resident — no device
        touch; this runs on pack workers in async mode), and assemble the
        batched dense operands.  Returns ((stacked, bg, cg, alpha, beta),
        seconds)."""
        t0 = time.perf_counter()
        fmt, n_b = key[0], key[3]
        alpha, beta = key[5], key[6]
        if alpha is None:
            # folded epilogue: the group key carries (None, None) and each
            # member's coefficients dispatch as a (G,) vector — the batched
            # epilogue applies alpha[g] * acc + beta[g] * c, the same FMA
            # per member as its scalar call (bit-identical by construction)
            alpha = np.asarray([float(e.request.alpha) for e in chunk],
                               np.float32)
            beta = np.asarray([float(e.request.beta) for e in chunk],
                              np.float32)
        g = len(chunk)
        # Policy mode pads the group axis to a power-of-two bucket (dummy
        # replicated members, zero dense operands, outputs discarded): the
        # group executable keys on G, and continuous batching produces a
        # different member count every flush — without G-bucketing each
        # admission would recompile.  Same flush-invariance argument as
        # the (MB*TM, NW*K0) embed below, applied to the batch axis.
        g_pad = g
        if self.policy is not None and g > 1:
            g_pad = 1 << (g - 1).bit_length()
            if self.max_group:
                g_pad = max(g, min(g_pad, self.max_group))
        pad_members = [chunk[0].tensor] * (g_pad - g)
        np_dtype = np.dtype(key[4])
        if fmt is Format.BSR:
            # BSR members share the exact logical (M, K) (part of the group
            # key) and the weight tiling; stack_bsr pads block counts up to
            # the shared bucket.  No ragged embed needed.
            stacked = stack_bsr([e.tensor for e in chunk] + pad_members,
                                device=False)
            m_cap, k_cap = chunk[0].tensor.shape
        else:
            # Embed to the geometry-constant bounds (MB*TM, NW*K0), NOT the
            # flush's max member shape: the plan's exec key includes (m, k),
            # so a flush-dependent bound would recompile whenever ragged
            # traffic changes the group's largest member.  The slab bounds
            # are shared by every bucket-mate, making the group executable
            # flush-invariant (waste is < one row tile + one K window, and
            # the padding rows/cols are exact zeros — results stay
            # bit-identical).
            d0 = chunk[0].tensor.data
            m_cap = d0.mb * d0.tm
            k_cap = d0.nw * d0.k0
            stacked = stack_hflex(
                [_embed(e.tensor, m_cap, k_cap) for e in chunk]
                + [_embed(t, m_cap, k_cap) for t in pad_members],
                device=False)
        if g_pad > g and np.ndim(alpha) > 0:
            # dummy members: (0, 0) epilogue — their (discarded) outputs
            # stay exact zeros regardless of the replicated values
            alpha = np.concatenate([alpha, np.zeros(g_pad - g, np.float32)])
            beta = np.concatenate([beta, np.zeros(g_pad - g, np.float32)])
        bg = np.zeros((g_pad, k_cap, n_b), np_dtype)
        any_c = any(e.request.c is not None for e in chunk)
        cg = np.zeros((g_pad, m_cap, n_b), np_dtype) if any_c else None
        for i, e in enumerate(chunk):
            r = e.request
            bk, bn = r.b.shape
            bg[i, :bk, :bn] = r.b
            if r.c is not None:
                cm, cn = r.c.shape
                cg[i, :cm, :cn] = r.c
        return (stacked, bg, cg, alpha, beta), time.perf_counter() - t0

    # -- cost-model merge pass (policy mode) ---------------------------------

    def _sketch(self, key, members: List[_Entry]) -> GroupSketch:
        """Summarize one formed group for the cost model: stacked member
        pointer matrices (BSR: true block counts as pseudo-``q`` — the
        pointer walk IS the block walk, priced against the block-count
        bucket with TK as the window analogue), the group's padded
        buckets, and whether the resolved backend walks padded slots."""
        fmt, geo, n_b = key[0], key[1], key[3]
        backend = resolve_backend(self.engine.impl, members[0].tensor,
                                  members[0].request.b)
        if fmt is Format.BSR:
            q = np.asarray(
                [[[int(np.asarray(e.tensor.data.indptr)[-1])]]
                 for e in members], np.int64)
            lw, k0 = geo[0], geo[3]
        else:
            q = np.stack([np.asarray(e.tensor.data.q) for e in members])
            lw, k0 = geo[2], geo[4]
        return GroupSketch(key=key, q=q, n=n_b, k0=k0, lw=lw,
                           flat=backend == "jnp")

    def _merge_groups(self, groups: Dict, ctr: _FlushCounters) -> Dict:
        """Near-miss merge pass: let the policy re-price this flush's
        groups (``plan_merges``) and apply every cost-positive cluster —
        narrow HFLEX members are widened to the target LW bucket with
        :func:`repro.sparse_api.repad_lw` (inert zero slots; ``q``/``nse``
        untouched), BSR members re-bucket inside ``stack_bsr``, and ragged
        N rides the existing zero-padded ``bg`` assembly — so the merged
        dispatch is bit-identical per member to the split dispatches."""
        if self.policy is None or len(groups) < 2:
            return groups
        sketches = [self._sketch(key, members)
                    for key, members in groups.items()]
        clusters = self.policy.plan_merges(sketches,
                                           max_group=self.max_group)
        for idx, cl in enumerate(clusters):
            members = sorted((e for key in cl.keys for e in groups.pop(key)),
                             key=lambda e: e.ticket)
            key0 = cl.keys[0]
            fmt, geo = key0[0], key0[1]
            if fmt is Format.BSR:
                geo_t = (cl.lw,) + tuple(geo[1:])
            else:
                geo_t = tuple(geo[:2]) + (cl.lw,) + tuple(geo[3:])
                for e in members:
                    if e.tensor.data.lw < cl.lw:
                        e.tensor = repad_lw(e.tensor, cl.lw)
            # the ("merged", idx) suffix keeps the target distinct from
            # any surviving exact-key group the planner chose NOT to fold
            # into this cluster (prep only reads fixed key positions)
            target = ((fmt, geo_t, key0[2], cl.n) + tuple(key0[4:])
                      + (("merged", idx),))
            groups[target] = members
            ctr.merged_groups += 1
            ctr.merge_saved += len(cl.keys) - 1
        return groups

    # -- dispatch stage ------------------------------------------------------

    def _fold_engine_deltas(self, ctr: _FlushCounters, before) -> None:
        """Attribute the engine-stat growth since ``before`` (an
        ``engine.stats_snapshot()`` taken when this flush's dispatch stage
        started) to the flush's counters — tuned dispatches, TuningDB
        traffic and the cold/warm plan-build wall split."""
        after = self.engine.stats_snapshot()
        ctr.tuned = after.tuned_dispatches - before.tuned_dispatches
        ctr.db_hits = after.tune_db_hits - before.tune_db_hits
        ctr.db_misses = after.tune_db_misses - before.tune_db_misses
        ctr.build_cold_s = after.plan_build_cold_s - before.plan_build_cold_s
        ctr.build_warm_s = after.plan_build_warm_s - before.plan_build_warm_s

    def _count_skinny(self, tensor, b, ctr: _FlushCounters) -> None:
        """Bump ``ctr.skinny`` when this dispatch is SpMV-shaped — the same
        resolution (operand included) and test the engine performs."""
        if is_skinny(tensor, resolve_backend(self.engine.impl, tensor, b),
                     np.shape(b)[-1]):
            ctr.skinny += 1

    def _dispatch_single(self, e: _Entry, results: Dict,
                         ctr: _FlushCounters) -> None:
        r = e.request
        self._count_skinny(e.tensor, r.b, ctr)
        out = self.engine.spmm(
            e.tensor, jnp.asarray(r.b),
            None if r.c is None else jnp.asarray(r.c), r.alpha, r.beta)
        results[e.ticket] = (out, r.a.shape[0], r.b.shape[1])

    def _dispatch_group(self, chunk: List[_Entry], prep, results: Dict,
                        ctr: _FlushCounters) -> None:
        stacked, bg, cg, alpha, beta = prep
        self._count_skinny(stacked, bg, ctr)
        if np.ndim(alpha) > 0:
            ctr.folded += len(chunk)
        out = self.engine.spmm_group(
            stacked, jnp.asarray(bg),
            None if cg is None else jnp.asarray(cg), alpha, beta)
        for i, e in enumerate(chunk):
            results[e.ticket] = (out[i], e.request.a.shape[0],
                                 e.request.b.shape[1])

    def _dispatch_stream(self, e: _Entry, results: Dict,
                         ctr: _FlushCounters) -> None:
        r = e.request
        out = self.engine.spmm_streaming(
            e.tensor, r.b, None if r.c is None else jnp.asarray(r.c),
            r.alpha, r.beta, device_bytes=self.device_bytes,
            window_chunk=self.window_chunk, n_tile=self.n_tile)
        # per-call stats from the plan this exact call ran through —
        # not the engine's lifetime aggregates
        pl = self.engine.last_streaming_plan
        # window steps (summed over column tiles) + one epilogue per tile
        ctr.dispatches += pl.window_dispatches + pl.n_tiles
        ctr.window_disp += pl.window_dispatches
        ctr.n_tiles = max(ctr.n_tiles, pl.n_tiles)
        ctr.peak = max(ctr.peak, pl.peak_payload_bytes)
        ctr.streamed += 1
        results[e.ticket] = (out, r.a.shape[0], r.b.shape[1])

    # -- execution: synchronous ----------------------------------------------

    def flush(self) -> Union[List[np.ndarray], List[SpmmFuture]]:
        """Execute all queued requests.

        Synchronous mode blocks and returns results in submit order; on
        failure the queue is restored (ahead of anything submitted since),
        so one malformed request cannot silently drop the rest — the
        caller can remove it and retry.

        Async mode is non-blocking: the batch is handed to the dispatch
        thread and the batch's futures are returned immediately (the same
        objects ``submit`` returned; restored-after-failure requests get
        fresh futures here).  Futures resolve in submit order."""
        with self._lock:
            pending, self._pending = self._pending, []
        if not pending:
            return []
        if self.async_pipeline:
            for e in pending:
                if e.pack is None:      # restored after a failure: re-pack
                    e.pack = self._pipe.submit_pack(self._pack_host,
                                                    e.request)
            self._pipe.submit_dispatch(self._flush_async, pending)
            return [e.future for e in pending]
        try:
            return self._flush(pending)
        except Exception:
            with self._lock:
                self._pending = pending + self._pending
            raise

    def _flush(self, pending: List[_Entry]) -> List[np.ndarray]:
        eng = self.engine
        t0 = time.perf_counter()
        pack_s = 0.0
        groups: Dict[Any, List[_Entry]] = {}
        stream_lane: List[_Entry] = []
        for e in pending:
            e.tensor, dt = self._pack_host(e.request)
            pack_s += dt
            self._route(e, groups, stream_lane)

        results: Dict[int, Tuple[jax.Array, int, int]] = {}
        ctr = _FlushCounters()
        groups = self._merge_groups(groups, ctr)
        es0 = eng.stats_snapshot()
        for key, members in groups.items():
            for lo in range(0, len(members), self.max_group):
                chunk = members[lo:lo + self.max_group]
                ctr.groups += 1
                ctr.dispatches += 1
                if len(chunk) == 1:
                    self._dispatch_single(chunk[0], results, ctr)
                else:
                    prep, dt = self._prep_group(key, chunk)
                    pack_s += dt
                    self._dispatch_group(chunk, prep, results, ctr)
                    ctr.batched += len(chunk)
        for e in stream_lane:
            self._dispatch_stream(e, results, ctr)
        for out, _, _ in results.values():
            jax.block_until_ready(out)
        self._fold_engine_deltas(ctr, es0)
        wall = time.perf_counter() - t0
        done_ts = time.monotonic()
        # synchronous mode: packing is fully serialized with execution, so
        # ALL pack time is stall, none hidden (overlap_s stays 0)
        self._note_flush(len(pending), ctr, wall, pack_s,
                         stall_s=pack_s, failed=0,
                         flops=sum(_request_flops(e.request)
                                   for e in pending),
                         latencies=[done_ts - e.submit_ts for e in pending])
        return [
            np.asarray(results[e.ticket][0])[:results[e.ticket][1],
                                             :results[e.ticket][2]]
            for e in pending
        ]

    # -- execution: async pipeline -------------------------------------------

    def _flush_async(self, entries: List[_Entry]) -> None:
        """Coordinator for one async flush; runs ON the dispatch thread.

        A failure of the coordinator itself (as opposed to a per-request
        pack/dispatch error, which `_flush_async_inner` owns) must never
        strand the batch: every still-unresolved future gets the
        exception and its request is restored to the queue — the async
        analogue of the synchronous flush's restore-and-raise."""
        try:
            self._flush_async_inner(entries)
        except BaseException as exc:    # noqa: BLE001 — owed to the futures
            restored = []
            for e in entries:
                if not e.future.done():
                    e.future._set_exception(exc)
                    restored.append(_Entry(e.ticket, e.request,
                                           future=SpmmFuture(e.ticket)))
            if restored:
                with self._lock:
                    self.stats["failed"] += len(restored)
                    self._pending = restored + self._pending

    def _flush_async_inner(self, entries: List[_Entry]) -> None:
        """One async flush: wait for the batch's host packs (started at
        submit time; they ran concurrently, so this stalls only on the
        slowest tail — the wait is required because bucket groups are
        formed from ALL of the flush's packed geometries, keeping the
        grouping deterministic and identical to the synchronous path),
        then dispatch every unit as soon as its *group-level* pack lands:
        singletons first (no host prep, the device fills while stacks
        build), multi-member groups in stack-completion order, then the
        streaming lane.  Futures resolve strictly in ticket order at the
        end; failed requests resolve with their exception and are
        restored to the queue."""
        t0 = time.perf_counter()
        pack_s = 0.0
        stall_s = 0.0
        failed: Dict[int, BaseException] = {}
        groups: Dict[Any, List[_Entry]] = {}
        stream_lane: List[_Entry] = []
        for e in entries:               # ticket order — same groups as sync
            ts = time.perf_counter()
            try:
                e.tensor, dt = e.pack.result()
            except Exception as exc:    # noqa: BLE001 — owned by the future
                failed[e.ticket] = exc
                continue
            finally:
                stall_s += time.perf_counter() - ts
            pack_s += dt
            self._route(e, groups, stream_lane)

        ctr = _FlushCounters()
        groups = self._merge_groups(groups, ctr)
        singles: List[List[_Entry]] = []
        stacked_units: List[Tuple[Any, List[_Entry]]] = []
        for key, members in groups.items():
            for lo in range(0, len(members), self.max_group):
                chunk = members[lo:lo + self.max_group]
                if len(chunk) == 1:
                    singles.append(chunk)
                else:
                    stacked_units.append((key, chunk))
        # group pack stage: stacks build on the workers while the device
        # runs whatever has already been dispatched
        prep_futs = {
            self._pipe.submit_pack(self._prep_group, key, chunk): chunk
            for key, chunk in stacked_units
        }

        results: Dict[int, Tuple[jax.Array, int, int]] = {}
        es0 = self.engine.stats_snapshot()
        for chunk in singles:           # no host prep — dispatch first
            e = chunk[0]
            try:
                self._dispatch_single(e, results, ctr)
                ctr.groups += 1
                ctr.dispatches += 1
            except Exception as exc:    # noqa: BLE001
                failed[e.ticket] = exc
        remaining = set(prep_futs)
        while remaining:                # dispatch groups as packs complete
            ts = time.perf_counter()
            done, remaining = concurrent.futures.wait(
                remaining, return_when=concurrent.futures.FIRST_COMPLETED)
            stall_s += time.perf_counter() - ts
            for f in done:
                chunk = prep_futs[f]
                try:
                    prep, dt = f.result()
                    pack_s += dt
                    self._dispatch_group(chunk, prep, results, ctr)
                    ctr.groups += 1
                    ctr.dispatches += 1
                    ctr.batched += len(chunk)
                except Exception as exc:    # noqa: BLE001
                    for e in chunk:
                        failed[e.ticket] = exc
        for e in stream_lane:
            try:
                self._dispatch_stream(e, results, ctr)
            except Exception as exc:        # noqa: BLE001
                failed[e.ticket] = exc
        self._fold_engine_deltas(ctr, es0)

        # restore failed requests and record the flush's stats BEFORE any
        # future resolves: a caller that wakes on the batch's last future
        # must observe the counters and latency samples of the flush that
        # produced its result
        restored = [_Entry(e.ticket, e.request, future=SpmmFuture(e.ticket))
                    for e in entries if e.ticket in failed]
        if restored:
            with self._lock:
                self._pending = restored + self._pending
        ok = [e for e in entries if e.ticket not in failed]
        done_ts = time.monotonic()
        wall = time.perf_counter() - t0
        self._note_flush(len(ok), ctr, wall, pack_s, stall_s,
                         failed=len(restored),
                         flops=sum(_request_flops(e.request) for e in ok),
                         latencies=[done_ts - e.submit_ts for e in ok])
        # resolve strictly in ticket order: a done future implies every
        # earlier future of the flush is done (submit-order determinism
        # even when groups completed out of order above; the flusher may
        # hand batches over in priority order, so re-sort here)
        for e in sorted(entries, key=lambda x: x.ticket):
            if e.ticket in failed:
                e.future._set_exception(failed[e.ticket])
            else:
                out, m, n = results[e.ticket]
                e.future._set_result(np.asarray(out)[:m, :n])

    # -- execution: deadline-driven background flusher ------------------------

    def _flusher_loop(self) -> None:
        """Daemon admission loop (``background_flush=True``): every
        ``flush_poll_s`` it scans the queue and hands cost-model-admitted
        batches to the dispatch thread.  A scan failure is counted and the
        loop keeps running — per-request failures are owned by the
        futures, and a policy bug must not silently kill admission."""
        while not self._stop_flusher.wait(self.flush_poll_s):
            try:
                self._flush_ready()
            except Exception:   # noqa: BLE001 — keep the daemon alive
                with self._lock:
                    self.stats["flusher_errors"] += 1

    def _flush_ready(self) -> int:
        """One admission scan: group the already-packed pending entries
        exactly as a flush would, admit every group that is either *full
        enough* (``policy.full_enough`` — modeled work amortizes the
        dispatch overhead) or *deadline-urgent* (its most urgent member
        is within ``deadline_margin_s`` of ``submit_ts + deadline_s``),
        order admitted groups by priority, and hand the batch to the
        dispatch thread.  Entries still packing stay queued for the next
        scan; failed packs and streaming-lane entries (batching buys them
        nothing) are admitted immediately.  Returns the admitted count.

        Races are resolved by re-intersecting with ``_pending`` under the
        lock at extraction time: an entry ``cancel()``-ed (or drained by a
        caller ``flush()``) after the scan snapshot simply is not there
        any more and is left alone."""
        now = time.monotonic()
        with self._lock:
            snapshot = list(self._pending)
        if not snapshot:
            return 0
        groups: Dict[Any, List[_Entry]] = {}
        stream_lane: List[_Entry] = []
        admit: set = set()                     # tickets
        for e in snapshot:
            if e.pack is None or not e.pack.done():
                continue                       # still packing — next scan
            try:
                e.tensor, _ = e.pack.result()  # done: returns immediately
            except Exception:   # noqa: BLE001 — owned by the future
                # failed pack: admit now so _flush_async resolves the
                # future with the exception instead of queueing it forever
                admit.add(e.ticket)
                continue
            self._route(e, groups, stream_lane)
        admit.update(e.ticket for e in stream_lane)
        ordered: List[Tuple[float, List[_Entry]]] = []
        for key, members in groups.items():
            urgent = any(
                e.request.deadline_s is not None
                and now + self.deadline_margin_s
                    >= e.submit_ts + e.request.deadline_s
                for e in members)
            full = (len(members) >= self.max_group
                    or self.policy.full_enough(self._sketch(key, members),
                                               max_group=self.max_group))
            if urgent or full:
                ordered.append(
                    (max(e.request.priority for e in members), members))
        ordered.sort(key=lambda pm: -pm[0])
        rank = {e.ticket: i for i, (_, ms) in enumerate(ordered)
                for e in ms}
        admit.update(rank)
        if not admit:
            return 0
        with self._lock:
            batch = [e for e in self._pending if e.ticket in admit]
            self._pending = [e for e in self._pending
                             if e.ticket not in admit]
        if not batch:
            return 0
        # priority order: higher-priority groups' preps start earlier on
        # the dispatch thread (futures still resolve in ticket order)
        batch.sort(key=lambda e: (rank.get(e.ticket, len(ordered)),
                                  e.ticket))
        self._pipe.submit_dispatch(self._flush_async, batch)
        with self._lock:
            self.stats["flusher_flushes"] += 1
        return len(batch)

    # -- stats ---------------------------------------------------------------

    def _note_flush(self, n_ok: int, ctr: _FlushCounters, wall: float,
                    pack_s: float, stall_s: float, failed: int,
                    flops: float,
                    latencies: Sequence[float] = ()) -> None:
        overlap = max(0.0, pack_s - stall_s)
        hidden = min(1.0, overlap / pack_s) if pack_s > 0 else 0.0
        # guarded against empty flushes: an all-failed async batch (n_ok
        # = 0, no latency samples) must not divide by zero anywhere here
        lat = np.asarray(latencies, np.float64)
        p50 = float(np.percentile(lat, 50)) if lat.size else 0.0
        p99 = float(np.percentile(lat, 99)) if lat.size else 0.0
        with self._lock:
            st = self.stats
            st["requests"] += n_ok
            st["groups"] += ctr.groups
            st["dispatches"] += ctr.dispatches
            st["batched_requests"] += ctr.batched
            st["streamed"] += ctr.streamed
            st["window_dispatches"] += ctr.window_disp
            st["n_tiles"] = max(st["n_tiles"], ctr.n_tiles)
            st["skinny_dispatches"] += ctr.skinny
            st["peak_payload_bytes"] = max(st["peak_payload_bytes"], ctr.peak)
            st["merged_groups"] += ctr.merged_groups
            st["merge_saved_dispatches"] += ctr.merge_saved
            st["folded_requests"] += ctr.folded
            self._latencies.extend(latencies)
            if len(self._latencies) > self.LATENCY_CAP:
                del self._latencies[:-self.LATENCY_CAP]
            st["tuned_dispatches"] += ctr.tuned
            st["tune_db_hits"] += ctr.db_hits
            st["tune_db_misses"] += ctr.db_misses
            st["plan_build_cold_s"] += ctr.build_cold_s
            st["plan_build_warm_s"] += ctr.build_warm_s
            st["failed"] += failed
            st["flushes"] += 1
            st["wall_s"] += wall
            st["preprocess_s"] += pack_s
            st["overlap_s"] += overlap
            st["pack_stall_s"] += stall_s
            st["flops"] += flops
            st["last_flush"] = {
                "requests": n_ok,
                "groups": ctr.groups,
                "dispatches": ctr.dispatches,
                "batched_requests": ctr.batched,
                "streamed": ctr.streamed,
                "window_dispatches": ctr.window_disp,
                "n_tiles": ctr.n_tiles,
                "skinny_dispatches": ctr.skinny,
                "merged_groups": ctr.merged_groups,
                "merge_saved_dispatches": ctr.merge_saved,
                "folded_requests": ctr.folded,
                "latency_p50_s": p50,
                "latency_p99_s": p99,
                "tuned_dispatches": ctr.tuned,
                "tune_db_hits": ctr.db_hits,
                "tune_db_misses": ctr.db_misses,
                "plan_build_cold_s": ctr.build_cold_s,
                "plan_build_warm_s": ctr.build_warm_s,
                "failed": failed,
                "wall_s": wall,
                "preprocess_s": pack_s,
                "overlap_s": overlap,
                "pack_stall_s": stall_s,
                "pack_hidden_fraction": hidden,
            }

    # -- reporting ----------------------------------------------------------

    @property
    def batched_fraction(self) -> float:
        """Fraction of served requests that rode a group dispatch."""
        with self._lock:
            n = self.stats["requests"]
            return self.stats["batched_requests"] / n if n else 0.0

    @property
    def dispatches_per_request(self) -> float:
        with self._lock:
            n = self.stats["requests"]
            return self.stats["dispatches"] / n if n else 0.0

    @property
    def pack_hidden_fraction(self) -> float:
        """Fraction of host pack time hidden behind the pipeline (async
        mode; 0.0 when packing is fully serialized with execution)."""
        with self._lock:
            p = self.stats["preprocess_s"]
            return min(1.0, self.stats["overlap_s"] / p) if p > 0 else 0.0

    def latency_percentile(self, p: float) -> float:
        """Percentile of recorded submit→resolution latency in seconds
        (bounded window of the most recent ``LATENCY_CAP`` samples);
        0.0 while no request has completed — never a division/percentile
        of an empty sample set."""
        with self._lock:
            if not self._latencies:
                return 0.0
            return float(np.percentile(np.asarray(self._latencies,
                                                  np.float64), p))

    @property
    def latency_p50(self) -> float:
        return self.latency_percentile(50.0)

    @property
    def latency_p99(self) -> float:
        return self.latency_percentile(99.0)


def _policy_stats(sched: SpmmScheduler) -> Dict[str, Any]:
    """The scheduler's cost-model policy + latency stats for reporting."""
    return {
        "merged_groups": sched.stats["merged_groups"],
        "merge_saved_dispatches": sched.stats["merge_saved_dispatches"],
        "folded_requests": sched.stats["folded_requests"],
        "flusher_flushes": sched.stats["flusher_flushes"],
        "latency_p50_s": sched.latency_p50,
        "latency_p99_s": sched.latency_p99,
    }


def serve_spmm_requests(
    requests: Sequence[SpmmRequest],
    engine: Optional[SextansEngine] = None,
    *,
    batched: bool = True,
    async_pipeline: bool = False,
    pack_threads: Optional[int] = None,
    max_group: int = 64,
    device_bytes: Optional[int] = None,
    window_chunk: Optional[int] = None,
    n_tile: Optional[int] = None,
    autotune: Optional[str] = None,
    policy: Optional[MergePolicy] = None,
    continuous: bool = False,
) -> Tuple[List[np.ndarray], Dict[str, Any]]:
    """Run a pool of SpMM requests; returns results + serving stats.

    ``batched=True`` (default) serves through :class:`SpmmScheduler`:
    bucket-mates are stacked into group dispatches, and — with
    ``device_bytes`` set — oversized requests ride the out-of-core
    streaming lane instead of pinning their full payload on device.
    ``async_pipeline=True`` serves through the scheduler's futures-based
    pack/execute pipeline (implies the batched grouping): host packing
    runs on ``pack_threads`` workers and overlaps device execution;
    results are bit-identical to the synchronous batched path and come
    back in submit order.  ``batched=False`` keeps the sequential
    one-dispatch-per-request loop (baseline).

    Stats report the HFlex executable-cache hit rate, the grouping
    behaviour (``groups``, ``batched_fraction``, ``dispatches_per_request``),
    the streaming lane (``streamed``, ``window_dispatches``, ``n_tiles``,
    ``peak_payload_bytes`` — ``n_tile`` forces/overrides the column-tile
    width of streamed requests), the skinny-N SpMV lane
    (``skinny_dispatches`` — SpMV-shaped dispatches, see
    ``repro.sparse_api.is_skinny``), the pipeline overlap (``overlap_s``,
    ``pack_hidden_fraction`` — zero outside async mode) and both
    ``gflops`` (wall clock including ``pack()`` preprocessing) and
    ``compute_gflops`` (wall − *non-hidden* preprocessing — the paper
    reports execution separately from preprocessing; hidden pack time IS
    execution-overlapped time).

    ``autotune`` threads a tuning mode ("off" | "cached" | "measure") into
    every plan the pool builds (see :mod:`repro.sparse_api.autotune`); the
    stats then report ``tuned_dispatches``, TuningDB traffic
    (``tune_db_hits`` / ``tune_db_misses``), the plan cache
    (``plan_cache_hits`` / ``plan_cache_misses`` / ``plan_cache_evictions``)
    and the cold-vs-warm plan-build wall split — a warm pool (DB and plan
    cache populated) shows ``plan_build_warm_s`` in place of the cold
    trace/compile/measure time.

    ``policy`` enables the scheduler's cost-model grouping (near-miss
    bucket merging + epilogue folding; see
    :class:`repro.launch.policy.MergePolicy`); ``continuous=True``
    additionally runs the deadline-driven background flusher (implies the
    async pipeline; requests' ``deadline_s`` / ``priority`` drive
    admission) with a caller-driven final drain for whatever the pool's
    tail leaves behind.  The stats then include ``merged_groups``,
    ``merge_saved_dispatches``, ``folded_requests`` and the per-request
    latency percentiles ``latency_p50_s`` / ``latency_p99_s``.
    """
    from repro.sparse_api import PLAN_STATS

    engine = engine or SextansEngine(tm=128, k0=512, chunk=8, impl="jnp")
    if autotune is not None:
        engine.autotune = autotune
    es0 = engine.stats_snapshot()
    exec0 = PLAN_STATS["exec_misses"]
    streamed = 0
    window_dispatches = 0
    n_tiles = 0
    skinny_dispatches = 0
    peak_payload = 0
    overlap_s = 0.0
    pack_hidden_fraction = 0.0

    sched_extra: Dict[str, Any] = {}
    if async_pipeline or continuous:
        sched = SpmmScheduler(engine, max_group=max_group,
                              device_bytes=device_bytes,
                              window_chunk=window_chunk, n_tile=n_tile,
                              async_pipeline=True,
                              pack_threads=pack_threads,
                              policy=policy,
                              background_flush=continuous)
        try:
            t0 = time.perf_counter()
            futs = [sched.submit(r) for r in requests]
            # one-shot pool: drain whatever the background flusher (if
            # any) has not admitted yet — the flusher's value shows under
            # paced arrivals (benchmarks/run.py --only slo), while the
            # wrapper guarantees completion for deadline-less pools
            sched.flush()
            outs = [f.result() for f in futs]
            wall = time.perf_counter() - t0
        finally:
            sched.shutdown()
        pack_s = sched.stats["preprocess_s"]
        flops = sched.stats["flops"]
        groups = sched.stats["groups"]
        batched_fraction = sched.batched_fraction
        dispatches_per_request = sched.dispatches_per_request
        streamed = sched.stats["streamed"]
        window_dispatches = sched.stats["window_dispatches"]
        n_tiles = sched.stats["n_tiles"]
        skinny_dispatches = sched.stats["skinny_dispatches"]
        peak_payload = sched.stats["peak_payload_bytes"]
        overlap_s = sched.stats["overlap_s"]
        pack_hidden_fraction = sched.pack_hidden_fraction
        sched_extra = _policy_stats(sched)
    elif batched:
        sched = SpmmScheduler(engine, max_group=max_group,
                              device_bytes=device_bytes,
                              window_chunk=window_chunk, n_tile=n_tile)
        for r in requests:
            sched.submit(r)
        outs = sched.flush()
        wall = sched.stats["wall_s"]
        pack_s = sched.stats["preprocess_s"]
        flops = sched.stats["flops"]
        groups = sched.stats["groups"]
        batched_fraction = sched.batched_fraction
        dispatches_per_request = sched.dispatches_per_request
        streamed = sched.stats["streamed"]
        window_dispatches = sched.stats["window_dispatches"]
        n_tiles = sched.stats["n_tiles"]
        skinny_dispatches = sched.stats["skinny_dispatches"]
        peak_payload = sched.stats["peak_payload_bytes"]
        sched_extra = _policy_stats(sched)
    else:
        outs = []
        # perf_counter (monotonic, high-resolution) + block_until_ready: JAX
        # dispatch is async, so stopping the clock before the device
        # finishes would time the *enqueue*, not the execution.
        t0 = time.perf_counter()
        pack_s = 0.0
        dispatches = 0
        skinny0 = engine.stats.skinny_dispatches
        for r in requests:
            tp = time.perf_counter()
            packed = (r.a if isinstance(r.a, SparseTensor)
                      else engine.pack(r.a))
            pack_s += time.perf_counter() - tp
            c = None if r.c is None else jnp.asarray(r.c)
            if device_bytes is not None and packed.nbytes > device_bytes:
                # the budget binds in the sequential baseline too: an
                # over-budget payload must never be pinned resident
                out = engine.spmm_streaming(
                    packed, r.b, c, r.alpha, r.beta,
                    device_bytes=device_bytes, window_chunk=window_chunk,
                    n_tile=n_tile)
                pl = engine.last_streaming_plan
                streamed += 1
                window_dispatches += pl.window_dispatches
                n_tiles = max(n_tiles, pl.n_tiles)
                peak_payload = max(peak_payload, pl.peak_payload_bytes)
                dispatches += pl.window_dispatches + pl.n_tiles
            else:
                out = engine.spmm(packed, jnp.asarray(r.b), c,
                                  r.alpha, r.beta)
                dispatches += 1
            outs.append(out)
        skinny_dispatches = engine.stats.skinny_dispatches - skinny0
        for out in outs:
            jax.block_until_ready(out)
        wall = time.perf_counter() - t0
        outs = [np.asarray(out) for out in outs]
        flops = sum(_request_flops(r) for r in requests)
        groups = len(requests)
        batched_fraction = 0.0
        dispatches_per_request = (dispatches / len(requests)
                                  if requests else 0.0)

    stats = {
        "requests": len(requests),
        "merged_groups": 0,
        "merge_saved_dispatches": 0,
        "folded_requests": 0,
        "flusher_flushes": 0,
        "latency_p50_s": 0.0,
        "latency_p99_s": 0.0,
        "wall_s": wall,
        "preprocess_s": pack_s,
        "overlap_s": overlap_s,
        "pack_hidden_fraction": pack_hidden_fraction,
        "gflops": flops / max(wall, 1e-9) / 1e9,
        "compute_gflops": flops / max(wall - (pack_s - overlap_s), 1e-9) / 1e9,
        "groups": groups,
        "batched_fraction": batched_fraction,
        "dispatches_per_request": dispatches_per_request,
        "streamed": streamed,
        "window_dispatches": window_dispatches,
        "n_tiles": n_tiles,
        "skinny_dispatches": skinny_dispatches,
        "peak_payload_bytes": peak_payload,
        "executable_cache_hit_rate": engine.stats.hit_rate,
        "cache_misses": engine.stats.cache_misses,
        "plan_executables_compiled": PLAN_STATS["exec_misses"] - exec0,
    }
    stats.update(sched_extra)
    # engine-delta reporting, uniform across the batched / async /
    # sequential paths: plan-cache visibility and the autotuning story
    es1 = engine.stats_snapshot()
    stats.update({
        "plan_cache_hits": es1.plan_cache_hits - es0.plan_cache_hits,
        "plan_cache_misses": es1.plan_cache_misses - es0.plan_cache_misses,
        "plan_cache_evictions": (es1.plan_cache_evictions
                                 - es0.plan_cache_evictions),
        "tuned_dispatches": es1.tuned_dispatches - es0.tuned_dispatches,
        "tune_db_hits": es1.tune_db_hits - es0.tune_db_hits,
        "tune_db_misses": es1.tune_db_misses - es0.tune_db_misses,
        "plan_builds_cold": es1.plan_builds_cold - es0.plan_builds_cold,
        "plan_builds_warm": es1.plan_builds_warm - es0.plan_builds_warm,
        "plan_build_cold_s": es1.plan_build_cold_s - es0.plan_build_cold_s,
        "plan_build_warm_s": es1.plan_build_warm_s - es0.plan_build_warm_s,
    })
    return outs, stats


def lm_generate(
    params: Any,
    cfg,
    prompt_tokens: jax.Array,       # (B, S0)
    steps: int,
    greedy: bool = True,
    cache_len: Optional[int] = None,
    seed: int = 0,
) -> jax.Array:
    """Prefill then decode `steps` tokens. Returns (B, steps)."""
    from repro.models import model as M

    b, s0 = prompt_tokens.shape
    smax = cache_len or (s0 + steps)
    enc_len = 0
    cache = M.init_cache(cfg, b, smax, enc_len=enc_len)

    # prefill by stepping (general across attn/ssm/hybrid caches)
    tok = prompt_tokens
    logits = None
    step_fn = jax.jit(lambda p, c, t: M.decode_step(p, cfg, c, t))
    for i in range(s0):
        logits, cache = step_fn(params, cache, tok[:, i: i + 1])

    outs = []
    key = jax.random.PRNGKey(seed)
    cur = None
    for i in range(steps):
        if cur is None:
            nxt = jnp.argmax(logits[:, -1, : cfg.vocab_size], axis=-1)
        else:
            logits, cache = step_fn(params, cache, cur)
            nxt = jnp.argmax(logits[:, -1, : cfg.vocab_size], axis=-1)
        cur = nxt[:, None].astype(jnp.int32)
        outs.append(cur)
    return jnp.concatenate(outs, axis=1)
