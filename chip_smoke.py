"""Bring-up check: the SpMM main path, once, on one TPU chip.

    python chip_smoke.py [--seed S]            # one chip, phases (a)-(d)
    python chip_smoke.py --chips 4             # phase (a) row-split, 4 chips
    python chip_smoke.py --cpu-rehearsal       # tiny sizes, any platform

(For a four-device CPU rehearsal of ``--chips 4``, also set
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.)

Drives the normal entry points with the engine's defaults (``impl="auto"``,
autotune off) on data made from ``--seed``:

(a) graph aggregation: a power-law graph at ogbn-arxiv's published size
    (169,343 nodes, 1,166,243 edges before duplicate edges merge), N = 128
    float32 features; four requests with mixed (alpha, beta) through
    ``SpmmScheduler``, two of them bucket-mates that run as one group
    dispatch;
(b) out-of-core lane: the same graph with ``device_bytes`` = payload / 4,
    so ``StreamingPlan`` runs the kernel's ``accumulate=True`` steps;
(c) SpMV lane: N = 1, three PageRank-style steps through ``auto`` ->
    ``pallas`` at its narrow 8-column lane;
(d) pruned FFN: qwen2-0.5b's ``wi`` (896 x 4864) at 90% 128x128 block
    sparsity, 24 layers' weights as one grouped BSR dispatch, N = 256.

Every phase is checked against the float64 host reference
(``repro.core.sparse.spmm_reference`` / a dense ``x @ W``) and must have
resolved to its Pallas kernel (``pallas``), on HFLEX at the column tile
the phase is meant to run: 128 for N = 128, 8 for N = 1.  The per-phase lines are set-up figures of
a bring-up check (compile and wall seconds include host packing and
transfers; ``peak_bytes_in_use`` is the process high-water, so it never
falls from one phase to the next), not benchmark numbers.  The last line
of standard output is one JSON object naming the device; any failure
raises and exits non-zero before it is printed.  Without a TPU (and
without ``--cpu-rehearsal``) the script exits 1 and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.sparse_api as sp  # noqa: E402
from repro import compile_cache  # noqa: E402
from repro.core.engine import SextansEngine  # noqa: E402
from repro.core.sparse import (SparseMatrix, power_law_sparse,  # noqa: E402
                               spmm_reference)
from repro.data.matrices import magnitude_pruned  # noqa: E402
from repro.launch.serve import SpmmRequest, SpmmScheduler  # noqa: E402

# graph ~ (nodes, edges), FFN ~ (d_model, d_ff, layers, tokens)
FULL = dict(graph=(169_343, 1_166_243), ffn=(896, 4864, 24, 256))
REHEARSAL = dict(graph=(3000, 21000), ffn=(256, 512, 4, 64))
N_FEAT = 128
# float32 kernels (MXU at Precision.HIGHEST) against a float64 reference:
# the error is f32 rounding of the products and of each row's sum, which
# grows with the row's term count (graph hubs hold thousands of terms);
# 2e-4 of the result's largest magnitude bounds that with room to spare.
TOL = 2e-4

_COMPILE_S = [0.0]


def _on_duration(event: str, secs: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += secs


def _require(cond, msg):
    if not cond:
        raise RuntimeError(msg)


def _peak_bytes():
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "n/a")


def _check(name, got, ref):
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    _require(got.shape == ref.shape, f"{name}: shape {got.shape} != "
             f"reference {ref.shape}")
    _require(np.all(np.isfinite(got)), f"{name}: non-finite output")
    scale = float(np.abs(ref).max()) or 1.0
    err = float(np.abs(got - ref).max()) / scale
    _require(err <= TOL, f"{name}: max error {err:.3e} x max|ref| > {TOL}")
    return err


def _phase(label, fn):
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    info = fn()
    wall = time.perf_counter() - t0
    print(f"[set-up, not a benchmark] phase {label}: "
          + " ".join(f"{k}={v}" for k, v in info.items())
          + f" peak_bytes_in_use={_peak_bytes()}"
          f" compile_s={_COMPILE_S[0] - c0:.2f} wall_s={wall:.2f}",
          flush=True)
    gc.collect()


def _graph(size, seed):
    nodes, edges = size
    return power_law_sparse(nodes, nodes, edges / nodes, seed=seed)


def _expect(engine, t, n, want, tn=None):
    """The backend a width-``n`` call of ``t`` resolves to, checked
    against ``want`` — and, given ``tn``, the HFLEX kernel's column tile
    too."""
    got = sp.resolve_backend(engine.impl, t, n=n)
    _require(got == want, f"resolved to {got!r}, expected {want!r}")
    if tn is not None:
        lane = engine.tn or sp.hflex_lane(n)
        _require(lane == tn, f"column tile {lane}, expected {tn}")
    return got


def phase_a(size, seed):
    a = _graph(size, seed)
    eng = SextansEngine()
    A = eng.pack(a, device=False)
    rng = np.random.default_rng(seed + 1)
    m, k = a.shape
    # two bucket-mates with one (alpha, beta) -> one group dispatch; two
    # singletons with other epilogues
    epi = [(1.0, 0.0, False), (1.0, 0.0, False), (0.5, 2.0, True),
           (-1.25, 0.75, True)]
    reqs = [SpmmRequest(
        a=A, b=rng.standard_normal((k, N_FEAT)).astype(np.float32),
        c=(rng.standard_normal((m, N_FEAT)).astype(np.float32)
           if has_c else None), alpha=al, beta=be)
        for al, be, has_c in epi]
    backend = _expect(eng, A, N_FEAT, "pallas", tn=128)
    sched = SpmmScheduler(eng, async_pipeline=True)
    outs = []
    # two flushes: the group's two stacked copies of the slabs (~11 GB at
    # full size) must leave the chip before the singletons' plan moves in
    for batch in (reqs[:2], reqs[2:]):
        futs = [sched.submit(r) for r in batch]
        sched.flush()
        outs += [np.asarray(f.result()) for f in futs]
        gc.collect()
    sched.shutdown()
    _require(sched.stats["groups"] == 3
             and sched.stats["batched_requests"] == 2, str(sched.stats))
    errs = []
    for r, y in zip(reqs, outs):
        c = r.c if r.c is not None else np.zeros((m, N_FEAT), np.float32)
        errs.append(_check("a", y, spmm_reference(a, r.b, c, r.alpha,
                                                  r.beta)))
    return {"backend": backend, "tn": 128, "nnz": a.nnz,
            "payload_bytes": A.nbytes,
            "requests": len(reqs), "group_dispatches": 1,
            "max_rel_err": f"{max(errs):.2e}", "tol": TOL}


def phase_b(size, seed):
    a = _graph(size, seed)
    eng = SextansEngine()
    A = eng.pack(a, device=False)
    rng = np.random.default_rng(seed + 2)
    b = rng.standard_normal((a.shape[1], N_FEAT)).astype(np.float32)
    c = rng.standard_normal((a.shape[0], N_FEAT)).astype(np.float32)
    budget = A.nbytes // 4
    sched = SpmmScheduler(eng, device_bytes=budget, async_pipeline=True)
    fut = sched.submit(SpmmRequest(a=A, b=b, c=c, alpha=0.75, beta=-0.5))
    sched.flush()
    y = fut.result()
    sched.shutdown()
    pl = eng.last_streaming_plan
    _require(isinstance(pl, sp.StreamingPlan)
             and sched.stats["streamed"] == 1, "request was not streamed")
    _require(pl.backend == "pallas", f"streamed on {pl.backend!r}")
    err = _check("b", y, spmm_reference(a, b, c, 0.75, -0.5))
    return {"backend": pl.backend, "nnz": a.nnz, "payload_bytes": A.nbytes,
            "device_bytes": budget, "window_chunk": pl.window_chunk,
            "n_tiles": pl.n_tiles,
            "window_dispatches": pl.window_dispatches,
            "max_rel_err": f"{err:.2e}", "tol": TOL}


def phase_c(size, seed):
    g = _graph(size, seed)
    # column-stochastic transition matrix of the same graph
    deg = np.bincount(g.col, minlength=g.shape[1]).astype(np.float32)
    a = SparseMatrix(g.shape, g.row, g.col,
                     (1.0 / deg[g.col]).astype(np.float32))
    eng = SextansEngine()
    A = eng.pack(a, device=False)
    n = a.shape[0]
    backend = _expect(eng, A, 1, "pallas", tn=8)
    damp = 0.85
    tele = np.full((n, 1), (1.0 - damp) / n, np.float32)
    x = np.full((n, 1), 1.0 / n, np.float32)
    err = 0.0
    for _ in range(3):
        y = np.asarray(eng.spmm(A, jnp.asarray(x), jnp.asarray(tele),
                                damp, 1.0))
        err = max(err, _check("c", y, spmm_reference(a, x, tele, damp, 1.0)))
        x = y
    return {"backend": backend, "tn": 8, "nnz": a.nnz,
            "payload_bytes": A.nbytes, "steps": 3, "max_rel_err": f"{err:.2e}", "tol": TOL}


def phase_d(size, seed):
    d_model, d_ff, layers, tokens = size
    rng = np.random.default_rng(seed + 4)
    eng = SextansEngine()
    reqs, refs = [], []
    for layer in range(layers):
        w = magnitude_pruned(d_model, d_ff, 0.9, block=(128, 128),
                             seed=seed + 100 + layer)
        x = rng.standard_normal((tokens, d_model)).astype(np.float32)
        # A = W^T (d_ff, d_model): A @ x^T = (x @ W)^T
        A = sp.from_dense(np.ascontiguousarray(w.T), format=sp.Format.BSR,
                          block=(128, 128), device=False)
        reqs.append(SpmmRequest(a=A, b=np.ascontiguousarray(x.T)))
        refs.append((x.astype(np.float64) @ w.astype(np.float64)).T)
    backend = _expect(eng, reqs[0].a, tokens, "pallas")
    sched = SpmmScheduler(eng, async_pipeline=True)
    futs = [sched.submit(r) for r in reqs]
    sched.flush()
    outs = [f.result() for f in futs]
    sched.shutdown()
    _require(sched.stats["groups"] == 1, str(sched.stats))
    err = max(_check("d", y, r) for y, r in zip(outs, refs))
    nnz = sum(r.a.nnz for r in reqs)
    return {"backend": backend, "nnz": nnz,
            "payload_bytes": sum(r.a.nbytes for r in reqs),
            "layers": layers, "group_dispatches": 1,
            "max_rel_err": f"{err:.2e}", "tol": TOL}


def phase_a_row_split(size, seed):
    """Phase (a)'s SpMM row-split over 4 chips, against one chip."""
    a = _graph(size, seed)
    eng = SextansEngine()
    A = eng.pack(a, device=False)
    rng = np.random.default_rng(seed + 1)
    b = jnp.asarray(rng.standard_normal((a.shape[1], N_FEAT)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((a.shape[0], N_FEAT)), jnp.float32)
    backend = _expect(eng, A, N_FEAT, "pallas", tn=128)
    one_chip = SextansEngine()
    one = np.asarray(one_chip.plan_for(A, N_FEAT).run(b, c, 0.5, 2.0))
    del one_chip                     # release chip 0's copy of the slabs
    gc.collect()
    mesh = jax.make_mesh((4, 1), ("data", "model"))
    fn = eng.sharded_spmm_fn(mesh, A, N_FEAT, alpha=0.5, beta=2.0)
    four = np.asarray(fn(None, b, c))
    per_chip = {s.data.shape for s in fn.plan._operands[0].addressable_shards}
    # no collective may move the slab payload: only the (M, N) result is
    # gathered
    slab_dims = "," + ",".join(map(str, A.data.vals.shape[-3:])) + "]"
    collectives = [ln for ln in fn.plan._compiled.as_text().splitlines()
                   if re.search(r"all-gather|all-to-all|all-reduce|"
                                r"collective-permute", ln)]
    _require(not any(slab_dims in ln for ln in collectives),
             "a collective moves the slab payload")
    ref = spmm_reference(a, np.asarray(b), np.asarray(c), 0.5, 2.0)
    err1 = _check("a/1chip", one, ref)
    err4 = _check("a/4chips", four, ref)
    same = _check("a/4 vs 1", four, one)
    return {"backend": backend, "nnz": a.nnz, "payload_bytes": A.nbytes,
            "slab_shard_per_chip": sorted(per_chip),
            "collective_lines": len(collectives),
            "slab_collectives": 0,
            "max_rel_err_1chip": f"{err1:.2e}",
            "max_rel_err_4chips": f"{err4:.2e}",
            "max_rel_diff_4_vs_1": f"{same:.2e}", "tol": TOL}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only phase (a), row-split over 4 chips, "
                         "against the one-chip result and the reference")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on whatever platform JAX finds (the "
                         "Pallas kernels interpret off-TPU)")
    args = ap.parse_args()

    devs = jax.devices()
    platform = devs[0].platform
    if not args.cpu_rehearsal and platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX reports platform "
              f"{platform!r}); pass --cpu-rehearsal for the tiny-size "
              f"rehearsal", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX reports {len(devs)}", file=sys.stderr)
        return 1
    cache_dir = compile_cache.enable()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    if platform != "tpu":
        # rehearsal: route as on a TPU, so the Pallas kernels (interpreted
        # here) are what runs
        from repro.sparse_api.backends import _default_auto_policy

        sp.set_auto_policy(
            lambda a, b, platform=None: _default_auto_policy(a, b, "tpu"))
    size = REHEARSAL if args.cpu_rehearsal else FULL
    graph, seed = size["graph"], args.seed
    print(f"# platform={platform} kind={devs[0].device_kind} "
          f"devices={len(devs)} jax={jax.__version__} seed={seed} "
          f"compile_cache={cache_dir}", flush=True)
    if args.chips == 4:
        _phase("(a) row-split over 4 chips",
               lambda: phase_a_row_split(graph, seed))
    else:
        _phase("(a) graph aggregation", lambda: phase_a(graph, seed))
        _phase("(b) out-of-core lane", lambda: phase_b(graph, seed))
        _phase("(c) SpMV lane", lambda: phase_c(graph, seed))
        _phase("(d) pruned FFN", lambda: phase_d(size["ffn"], seed))
    print(f"# persistent compile cache: hits={compile_cache.STATS['hits']} "
          f"writes={compile_cache.STATS['writes']}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
