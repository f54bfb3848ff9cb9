"""Measurement-driven autotuning of execution geometry + persistent caches.

The paper's HFlex property makes execution geometry a *runtime* parameter
— which also makes it tunable at runtime.  This module closes the loop:

* a **candidate enumerator** over the execution-side knobs — backend
  (``jnp``, or ``pallas`` at the 128-column lane or, for N ≤ 32, the
  narrow lane), streaming ``window_chunk`` / ``n_tile``, and the skinny-N
  threshold — pruned by ranking with the :mod:`repro.core.perfmodel`
  event-cycle model and then measured best-of-N
  (``perf_counter`` + ``block_until_ready``);
* a **bit-identity guard**: every candidate's result is compared
  (``np.array_equal``) against the plan the caller would have gotten with
  autotuning off; a candidate that does not reproduce the default result
  bit-for-bit is rejected outright, so a tuned plan can never change
  numerics (Serpens/SpArch show the profitable operating point is
  workload-dependent — but Sextans' bit-exactness contract is not);
* a **TuningDB**: schema-versioned JSON under ``$SEXTANS_TUNE_DIR``
  (atomic tmp-file+rename writes, advisory ``fcntl`` file lock for
  cross-process merges, in-memory cache under the repo's ``_lock_guarded``
  discipline), keyed by (platform, dtype, bucketed geometry, padded N,
  group size) — matrix *contents* never enter the key, exactly like the
  executable cache;
* compiled executables are *not* stored here: JAX's own persistent
  compilation cache (:mod:`repro.compile_cache`) serves a second process.

Modes (``plan(..., autotune=)`` / ``$SEXTANS_AUTOTUNE``):

* ``"off"``     — default heuristics only (the default).
* ``"cached"``  — apply a stored tuning decision when one exists; never
  measure.  Safe for latency-sensitive serving.
* ``"measure"`` — on a DB miss, enumerate + measure + store, then apply.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import tempfile
import threading
import time
import warnings
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hflex import bucket_geometry
from repro.core.partition import SextansParams

from . import backends as _bk
from .tensor import Format, SparseTensor, bucket_block_count

__all__ = [
    "AUTOTUNE_MODES",
    "TUNE_SCHEMA",
    "TUNE_STATS",
    "TuningDB",
    "get_db",
    "tune_dir",
    "resolve_mode",
    "tune_key",
    "Candidate",
    "enumerate_candidates",
    "tune_plan",
    "tune_skinny_threshold",
    "apply_skinny_from_db",
]

#: Bump when the record layout (or anything that invalidates stored
#: decisions, e.g. the measurement protocol) changes — a DB written by a
#: different schema is ignored wholesale and re-tuned, never migrated.
#: 2: backends are ``pallas``/``jnp`` only, the kernel's lane is ``tn``.
TUNE_SCHEMA = 2

AUTOTUNE_MODES = ("off", "cached", "measure")

#: Module-wide tuning counters (deltas are folded into ``EngineStats`` /
#: scheduler ``last_flush`` around dispatch): ``db_hits``/``db_misses``
#: count TuningDB lookups, ``measured`` full tuning sessions,
#: ``rejected`` candidates killed by the bit-identity guard.
TUNE_STATS: Dict[str, int] = {"db_hits": 0, "db_misses": 0, "db_stores": 0,
                              "measured": 0, "rejected": 0}
_TUNE_STATS_LOCK = threading.Lock()


def _bump(name: str, k: int = 1) -> None:
    with _TUNE_STATS_LOCK:
        TUNE_STATS[name] += k


def tune_dir() -> Optional[str]:
    """The persistent cache directory (``$SEXTANS_TUNE_DIR``), or None for
    in-memory-only tuning."""
    return os.environ.get("SEXTANS_TUNE_DIR") or None


def resolve_mode(autotune: Optional[str]) -> str:
    """Resolve a ``plan(..., autotune=)`` argument: None defers to
    ``$SEXTANS_AUTOTUNE`` (default ``"off"``); anything else must be one
    of ``AUTOTUNE_MODES``."""
    if autotune is None:
        env = os.environ.get("SEXTANS_AUTOTUNE", "").strip().lower()
        return env if env in AUTOTUNE_MODES else "off"
    if autotune not in AUTOTUNE_MODES:
        raise ValueError(
            f"autotune must be one of {AUTOTUNE_MODES}, got {autotune!r}")
    return autotune


# ---------------------------------------------------------------------------
# persistence primitives
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def _file_lock(path: str):
    """Advisory cross-process lock around read-merge-write of the DB file
    (``fcntl.flock``; a no-op where the platform has no fcntl — the atomic
    rename still keeps the file itself consistent, merges just race)."""
    try:
        import fcntl
    except ImportError:
        yield
        return
    fh = open(path, "a+")
    try:
        fcntl.flock(fh.fileno(), fcntl.LOCK_EX)
        yield
    finally:
        try:
            fcntl.flock(fh.fileno(), fcntl.LOCK_UN)
        finally:
            fh.close()


def _atomic_write_json(path: str, payload: dict) -> None:
    """tmp-file + ``os.replace``: readers never observe a torn file."""
    d = os.path.dirname(path) or "."
    fd, tmp = tempfile.mkstemp(dir=d, prefix=".tune-", suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        with contextlib.suppress(OSError):
            os.remove(tmp)
        raise


class TuningDB:
    """Persistent (platform, dtype, geometry) -> tuning-record store.

    Records are plain JSON dicts under a schema-versioned envelope
    ``{"schema": TUNE_SCHEMA, "records": {key: record}}`` in
    ``<dir>/tuning.json``.  ``path=None`` is a process-local in-memory DB
    (the default when ``$SEXTANS_TUNE_DIR`` is unset).  Writes are atomic
    (tmp + rename) and merged read-modify-write under an advisory file
    lock, so concurrent processes tuning disjoint keys both land.
    """

    #: shared with serving threads through the plan tier — every access
    #: outside ``__init__`` must hold ``self._lock`` (``lock-discipline``
    #: rule of ``repro.analysis``).
    _lock_guarded = ("_mem", "stats")

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self._lock = threading.Lock()
        self._mem: Optional[Dict[str, dict]] = None   # lazy disk snapshot
        self.stats = {"hits": 0, "misses": 0, "stores": 0}

    @property
    def file(self) -> Optional[str]:
        return os.path.join(self.path, "tuning.json") if self.path else None

    def _read_disk(self) -> Dict[str, dict]:
        f = self.file
        if f is None or not os.path.exists(f):
            return {}
        try:
            with open(f) as fh:
                payload = json.load(fh)
        except (OSError, ValueError):
            return {}                       # torn/corrupt file: retune
        if not isinstance(payload, dict) or payload.get("schema") != TUNE_SCHEMA:
            return {}                       # schema mismatch: retune, never migrate
        recs = payload.get("records")
        return dict(recs) if isinstance(recs, dict) else {}

    def lookup(self, key: str) -> Optional[dict]:
        """The stored record for ``key`` (a copy), or None. Counts a
        hit/miss on both the instance and module stats."""
        with self._lock:
            if self._mem is None:
                self._mem = self._read_disk()
            rec = self._mem.get(key)
            if rec is None:
                self.stats["misses"] += 1
                _bump("db_misses")
                return None
            self.stats["hits"] += 1
            _bump("db_hits")
            return dict(rec)

    def store(self, key: str, record: dict) -> None:
        """Store (and, when backed by a directory, persist) one record."""
        with self._lock:
            if self._mem is None:
                self._mem = self._read_disk()
            self._mem[key] = dict(record)
            self.stats["stores"] += 1
            _bump("db_stores")
            if self.path is None:
                return
            os.makedirs(self.path, exist_ok=True)
            with _file_lock(os.path.join(self.path, "tuning.lock")):
                merged = self._read_disk()  # re-read: merge concurrent writers
                merged.update(self._mem)
                _atomic_write_json(self.file,
                                   {"schema": TUNE_SCHEMA, "records": merged})
                self._mem = merged

    def __len__(self) -> int:
        with self._lock:
            if self._mem is None:
                self._mem = self._read_disk()
            return len(self._mem)


_DB_LOCK = threading.Lock()
_DBS: Dict[Optional[str], "TuningDB"] = {}


def get_db(path: Optional[str] = None) -> TuningDB:
    """Process-wide :class:`TuningDB` for ``path`` (default:
    ``$SEXTANS_TUNE_DIR``; an in-memory DB when unset)."""
    if path is None:
        path = tune_dir()
    with _DB_LOCK:
        db = _DBS.get(path)
        if db is None:
            db = _DBS[path] = TuningDB(path)
        return db


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------


def tune_key(a: SparseTensor, n: int, *, dtype=jnp.float32,
             group: Optional[int] = None, stream: bool = False,
             device_bytes: Optional[int] = None,
             platform: Optional[str] = None) -> str:
    """Persistent tuning-record key: (platform, format, dtype, bucketed
    geometry, padded N, group size, execution tier).

    Matrix *contents* are excluded — the HFlex contract: any matrix in the
    bucket shares the decision, exactly as bucket-mates share a compiled
    executable.  Streamed plans additionally carry a power-of-two budget
    class (the floor pow2 of ``device_bytes``), so a decision tuned for
    one budget never steers a plan that has less room.
    """
    platform = platform or jax.default_backend()
    g = group if group is not None else (a.batch or 0)
    d = a.data
    if a.format is Format.HFLEX:
        geo = bucket_geometry(d.mb, d.nw, d.lw, int(n))
        fmt = "hflex"
    else:
        geo = (bucket_block_count(d.nb), d.k, d.f, d.tk, d.tf,
               bucket_geometry(1, 1, 1, int(n))[3])
        fmt = "bsr"
    tier = "resident"
    if stream:
        if device_bytes is None:
            tier = "stream"
        else:                       # floor pow2: same class => at least as much room
            tier = f"stream-b{1 << (max(int(device_bytes), 1).bit_length() - 1)}"
    geos = "x".join(str(int(x)) for x in geo)
    return (f"v{TUNE_SCHEMA}|{platform}|{fmt}|{np.dtype(dtype).name}"
            f"|{geos}|g{int(g)}|{tier}")


def skinny_key(platform: Optional[str] = None, dtype=jnp.float32) -> str:
    """Platform-wide key for the tuned skinny-N routing threshold (not
    geometry-specific: the threshold steers the *policy*, which runs
    before any plan exists)."""
    platform = platform or jax.default_backend()
    return f"v{TUNE_SCHEMA}|{platform}|skinny|{np.dtype(dtype).name}"


# ---------------------------------------------------------------------------
# candidate enumeration + model pruning
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point in the execution-knob space the tuner can measure.
    ``tn`` pins the kernel's column tile (None: the backend's own
    choice)."""

    backend: str
    window_chunk: Optional[int] = None
    n_tile: Optional[int] = None
    tn: Optional[int] = None


# Static backend priors multiplying the event-cycle rank: off-TPU the
# Pallas-family kernels run in *interpret mode* (orders of magnitude
# slower), so the model pruning must not waste measurement slots on them.
# They stay enumerable — on TPU the factor is 1 and measurement decides.
_INTERPRET_PENALTY = 200.0

#: modeled fixed cost per streaming dispatch (host slice + transfer +
#: launch), in Sextans cycles — only the *relative* weight against the
#: per-window compute matters, measurement picks the final winner.
DISPATCH_OVERHEAD_CYCLES = 25_000.0


def _backend_factor(name: str, platform: str) -> float:
    f = 1.0
    if platform != "tpu" and _bk.is_kernel(name):
        f *= _INTERPRET_PENALTY
    return f


def _pow2_down(n: int) -> List[int]:
    """n, then descending powers of two below n (the tiling ladder
    ``_choose_tiling`` walks)."""
    out = [int(n)]
    t = 1
    while t < n:
        t <<= 1
    t >>= 1
    while t >= 1:
        out.append(t)
        t >>= 1
    return out


def enumerate_candidates(a: SparseTensor, n: int, *, dtype=jnp.float32,
                         stream: bool = False,
                         device_bytes: Optional[int] = None,
                         window_chunk: Optional[int] = None,
                         n_tile: Optional[int] = None,
                         opts: Optional[Dict[str, Any]] = None
                         ) -> List[Candidate]:
    """All legal knob settings for this plan request.

    Resident plans enumerate backends; streaming plans enumerate
    (backend, window_chunk, n_tile) grid points whose double-buffered
    working set fits ``device_bytes`` (pinned knobs are respected).  The
    caller prunes with :func:`rank_candidates` before measuring.
    """
    opts = dict(opts or {})
    if a.format is Format.BSR or opts.get("tn") is not None:
        lanes = [("jnp", None), ("pallas", None)]     # lane pinned
    else:
        lanes = [("jnp", None), ("pallas", _bk.hflex_lane(n, narrow=False))]
        if a.batch is None and int(n) <= 32:
            # the narrow lane pads N up to a multiple of 8 — cap it
            lanes.append(("pallas", _bk.hflex_lane(n, narrow=True)))
    if not stream:
        return [Candidate(b, tn=tn) for b, tn in lanes]

    from .plan import _per_window_bytes  # lazy: plan imports this module

    d = a.data
    itemsize = np.dtype(dtype).itemsize
    m = a.shape[0]
    out: List[Candidate] = []
    for name, tn in lanes:
        be = _bk.get_backend(name)
        if be.stream is None or Format.HFLEX not in be.formats:
            continue
        topts = dict(opts, tn=tn) if tn is not None else opts
        ntiles = [int(n_tile)] if n_tile is not None else _pow2_down(int(n))
        for ntile in ntiles:
            try:
                acc_shape = jax.eval_shape(
                    lambda s=be.stream, w=ntile: s.init(a, w, **topts)).shape
            except Exception:
                break                       # backend can't stream this shape
            acc_bytes = int(np.prod(acc_shape)) * 4
            out_bytes = 2 * m * ntile * itemsize
            per_w = _per_window_bytes(d, ntile, itemsize)
            wcs = ([int(window_chunk)] if window_chunk is not None
                   else [w for w in _pow2_down(d.nw) if w <= d.nw])
            for wc in sorted(set(wcs)):
                peak = 2 * wc * per_w + acc_bytes + out_bytes
                if device_bytes is not None and peak > int(device_bytes):
                    continue
                out.append(Candidate(name, wc, ntile, tn))
    return out


def rank_candidates(a: SparseTensor, n: int, cands: List[Candidate],
                    *, platform: Optional[str] = None,
                    params: Optional[SextansParams] = None
                    ) -> List[Candidate]:
    """Order candidates by the event-cycle model (cheapest first) so only
    the top few are measured — the perfmodel-as-ranking contract pinned by
    ``tests/test_engine_perfmodel.py``."""
    from repro.core.perfmodel import analytic_cycles, packed_event_cycles

    platform = platform or jax.default_backend()
    params = params or SextansParams()
    d = a.data
    if a.format is Format.HFLEX:
        q = np.asarray(d.q)

        def cost(c: Candidate) -> float:
            return packed_event_cycles(
                q, int(n), params, k0=d.k0,
                window_chunk=c.window_chunk, n_tile=c.n_tile,
                dispatch_overhead_cycles=(DISPATCH_OVERHEAD_CYCLES
                                          if c.window_chunk is not None
                                          else 0.0),
            ) * _backend_factor(c.backend, platform)
    else:
        m, k = a.shape
        nnz = d.nb * d.tk * d.tf

        def cost(c: Candidate) -> float:
            return (analytic_cycles(m, k, nnz, int(n), params)
                    * _backend_factor(c.backend, platform))

    return sorted(cands, key=cost)


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------


def _best_of(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(max(1, int(repeats))):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


@dataclasses.dataclass
class TuneResult:
    """Outcome of one tuning session (:func:`tune_plan`)."""

    key: str
    record: Dict[str, Any]
    measured: List[Dict[str, Any]]      # every guard-surviving candidate


def tune_plan(a: SparseTensor, n: int, *, dtype=jnp.float32,
              backend: str = "auto", stream: bool = False,
              device_bytes: Optional[int] = None,
              window_chunk: Optional[int] = None,
              n_tile: Optional[int] = None,
              opts: Optional[Dict[str, Any]] = None,
              repeats: int = 3, measure_top: int = 3,
              db: Optional[TuningDB] = None, rng_seed: int = 0
              ) -> TuneResult:
    """Enumerate → model-prune → measure → guard → store one decision.

    Operands are *synthetic* (seeded ``default_rng`` at the planned
    shapes) — tuning never touches caller data.  The reference result is
    the plan the caller would get with ``autotune="off"``; every candidate
    must reproduce it bit-for-bit (``np.array_equal``) before its timing
    counts, so an accepted decision is bit-identical to the default path
    *by construction*.  The winner (plus the default's own timing, always
    measured as the baseline) is stored in the :class:`TuningDB`.
    """
    from .plan import plan as _plan

    opts = dict(opts or {})
    db = db or get_db()
    platform = jax.default_backend()
    np_dtype = np.dtype(jnp.dtype(dtype).name)
    m, k = a.shape
    g = a.batch
    n = int(n)
    rng = np.random.default_rng(rng_seed)
    bshape = (k, n) if g is None else (g, k, n)
    cshape = (m, n) if g is None else (g, m, n)
    b = rng.standard_normal(bshape).astype(np_dtype)
    c = rng.standard_normal(cshape).astype(np_dtype)
    alpha, beta = 1.25, -0.5

    def _build(cand: Candidate):
        copts = dict(opts, tn=cand.tn) if cand.tn is not None else opts
        return _plan(a, n, backend=cand.backend, dtype=dtype,
                     autotune="off", stream=stream or None,
                     device_bytes=device_bytes if stream else None,
                     window_chunk=cand.window_chunk if stream else None,
                     n_tile=cand.n_tile if stream else None, **copts)

    # the reference: exactly what the caller would run untuned
    default_pl = _plan(a, n, backend=backend, dtype=dtype, autotune="off",
                       stream=stream or None,
                       device_bytes=device_bytes if stream else None,
                       window_chunk=window_chunk if stream else None,
                       n_tile=n_tile if stream else None, **opts)
    y_ref = np.asarray(jax.block_until_ready(
        default_pl.run(b, c, alpha, beta)))
    default_cand = Candidate(default_pl.backend,
                             getattr(default_pl, "window_chunk", None),
                             getattr(default_pl, "n_tile", None),
                             default_pl.opts.get("tn"))

    cands = enumerate_candidates(a, n, dtype=dtype, stream=stream,
                                 device_bytes=device_bytes,
                                 window_chunk=window_chunk, n_tile=n_tile,
                                 opts=opts)
    ranked = rank_candidates(a, n, cands, platform=platform)
    top = ranked[:max(1, int(measure_top))]
    if default_cand not in top:
        top.append(default_cand)

    measured: List[Dict[str, Any]] = []
    default_us: Optional[float] = None
    for cand in top:
        if cand == default_cand:
            # the default already ran above (y_ref): a failure there is
            # the caller's own path failing and is raised, never skipped
            pl, y = default_pl, y_ref
        else:
            try:
                pl = _build(cand)
                y = np.asarray(jax.block_until_ready(
                    pl.run(b, c, alpha, beta)))
            except Exception:
                continue                    # combo this platform refuses
        if not np.array_equal(y, y_ref):
            _bump("rejected")               # bit-identity guard: reject
            continue
        us = _best_of(lambda p=pl: p.run(b, c, alpha, beta), repeats) * 1e6
        row = {"backend": cand.backend, "tn": cand.tn,
               "window_chunk": cand.window_chunk, "n_tile": cand.n_tile,
               "us": us}
        measured.append(row)
        if cand == default_cand:
            default_us = us
    if not measured:                        # cannot happen in practice: the
        raise RuntimeError(                 # default reproduces itself
            "no tuning candidate survived the bit-identity guard")

    win = min(measured, key=lambda r: r["us"])
    key = tune_key(a, n, dtype=dtype, group=g, stream=stream,
                   device_bytes=device_bytes, platform=platform)
    record = {
        "schema": TUNE_SCHEMA,
        "platform": platform,
        "backend": win["backend"],
        "tn": win["tn"],
        "window_chunk": win["window_chunk"],
        "n_tile": win["n_tile"],
        "stream": bool(stream),
        "us": win["us"],
        "default_backend": default_cand.backend,
        "default_us": default_us,
        "candidates_measured": len(measured),
    }
    db.store(key, record)
    _bump("measured")
    return TuneResult(key=key, record=record, measured=measured)


# ---------------------------------------------------------------------------
# plan-tier entry
# ---------------------------------------------------------------------------


def resolve_plan_knobs(a: SparseTensor, n: int, *, dtype, mode: str,
                       backend: str, stream: bool,
                       device_bytes: Optional[int],
                       window_chunk: Optional[int],
                       n_tile: Optional[int],
                       opts: Optional[Dict[str, Any]] = None,
                       group: Optional[int] = None
                       ) -> Tuple[str, Dict[str, Any], Optional[int],
                                  Optional[int], bool]:
    """``plan()``'s tuning hook: returns (backend, opts, window_chunk,
    n_tile, tuned).

    Only knobs the caller left open are ever overridden: ``backend`` (and
    with it the kernel's lane, ``opts["tn"]``) when ``"auto"``,
    ``window_chunk``/``n_tile`` when None on a streaming plan.
    ``"cached"`` applies a stored decision or does nothing; ``"measure"``
    tunes + stores on a miss (failures fall back to the heuristics with a
    warning — tuning must never take serving down).
    """
    opts = dict(opts or {})
    tunable_backend = backend == "auto"
    tunable_geo = bool(stream) and (window_chunk is None or n_tile is None)
    if mode == "off" or not (tunable_backend or tunable_geo):
        return backend, opts, window_chunk, n_tile, False
    db = get_db()
    key = tune_key(a, n, dtype=dtype, group=group, stream=bool(stream),
                   device_bytes=device_bytes)
    rec = db.lookup(key)
    if rec is None and mode == "measure":
        try:
            rec = tune_plan(a, n, dtype=dtype, backend=backend,
                            stream=bool(stream), device_bytes=device_bytes,
                            window_chunk=window_chunk, n_tile=n_tile,
                            opts=opts, db=db).record
        except Exception as e:  # noqa: BLE001 — degrade, don't take serving down
            warnings.warn(f"autotune measurement failed ({e!r}); using "
                          "default heuristics", stacklevel=3)
            return backend, opts, window_chunk, n_tile, False
    if rec is None:
        return backend, opts, window_chunk, n_tile, False
    if tunable_backend and rec.get("backend"):
        backend = str(rec["backend"])
        if rec.get("tn") and opts.get("tn") is None:
            opts["tn"] = int(rec["tn"])
    if stream:
        if window_chunk is None and rec.get("window_chunk"):
            window_chunk = int(rec["window_chunk"])
        if n_tile is None and rec.get("n_tile"):
            n_tile = int(rec["n_tile"])
    return backend, opts, window_chunk, n_tile, True


# ---------------------------------------------------------------------------
# skinny-N routing threshold
# ---------------------------------------------------------------------------


def tune_skinny_threshold(a: SparseTensor, *, widths: Optional[List[int]] = None,
                          dtype=jnp.float32, repeats: int = 3,
                          db: Optional[TuningDB] = None,
                          apply: bool = True) -> int:
    """Measure the profitable skinny-lane boundary on this platform.

    For each candidate width (default: around the built-in
    ``SKINNY_N_MAX``), times the narrow lane (``pallas`` at
    ``hflex_lane(n, narrow=True)`` on TPU, ``jnp`` elsewhere) against the
    platform's tall-N default (``pallas`` at the 128-column lane on TPU,
    ``jnp`` elsewhere) on the given representative matrix; the threshold
    is the largest width whose lane run is at least as fast (within 2%
    noise) with every smaller width also winning — Serpens' observation
    that the lane's profitable region is workload-dependent, made a
    measurement.  Stored platform-wide under :func:`skinny_key`;
    ``apply=True`` pushes it into the policy via
    :func:`apply_skinny_from_db`.
    """
    from .plan import plan as _plan

    db = db or get_db()
    platform = jax.default_backend()
    backend = "pallas" if platform == "tpu" else "jnp"
    base = _bk.SKINNY_N_MAX
    widths = sorted(set(widths or (max(1, base // 2), base, 2 * base)))
    np_dtype = np.dtype(jnp.dtype(dtype).name)
    rng = np.random.default_rng(0)
    m, k = a.shape
    threshold = 0
    rows = []
    for w in widths:
        b = rng.standard_normal((k, w)).astype(np_dtype)
        try:
            pl_lane, pl_tall = (
                _plan(a, w, backend=backend, dtype=dtype, autotune="off",
                      tn=_bk.hflex_lane(w, narrow=narrow))
                for narrow in (True, False))
        except Exception:
            break
        t_lane = _best_of(lambda p=pl_lane, x=b: p.run(x), repeats)
        t_tall = _best_of(lambda p=pl_tall, x=b: p.run(x), repeats)
        rows.append({"n": w, "lane_us": t_lane * 1e6, "tall_us": t_tall * 1e6})
        if t_lane <= t_tall * 1.02:
            threshold = w
        else:
            break                           # lane stopped winning: boundary found
    db.store(skinny_key(platform, dtype), {
        "schema": TUNE_SCHEMA,
        "platform": platform,
        "skinny_n_max": int(threshold),
        "backend": backend,
        "widths": rows,
    })
    _bump("measured")
    if apply:
        apply_skinny_from_db(db)
    return int(threshold)


def apply_skinny_from_db(db: Optional[TuningDB] = None) -> Optional[int]:
    """Push the DB's platform-tuned skinny threshold into the auto policy.

    The DB is the *lowest-precedence* source: a live
    :func:`repro.sparse_api.set_skinny_n_max` override or the
    ``$SEXTANS_SKINNY_N_MAX`` env var always wins, so this is a no-op
    (returns None) when either is set or no record exists.
    """
    if (_bk._SKINNY_OVERRIDE is not None
            or os.environ.get("SEXTANS_SKINNY_N_MAX")):
        return None
    rec = (db or get_db()).lookup(skinny_key())
    if not rec or "skinny_n_max" not in rec:
        return None
    value = int(rec["skinny_n_max"])
    _bk.set_skinny_n_max(value)
    return value
