"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (and, with ``--json PATH``,
writes the same rows as machine-readable JSON so the BENCH_*.json perf
trajectory can accumulate across PRs):

  table1_*   — speedup breakdown (paper Table 1): OoO / PUs / PEs
  fig7_*     — geomean speedups vs modeled GPUs (paper Fig. 7 headline)
  fig8_peak  — peak throughput (paper Fig. 8 / Table 3)
  fig9_*     — memory bandwidth utilization geomean (paper Fig. 9)
  fig10_*    — energy efficiency geomean (paper Fig. 10)
  kernel_*   — Pallas/jnp SpMM microbenchmarks (wall-clock, CPU interpret)
  plan_spmm  — SpmmPlan.run vs unplanned spmm (bit-identity asserted)
  sched_*    — scheduler preprocessing throughput + bubble fraction
               (vectorized production scheduler vs exact-greedy reference)
  serve_*    — batched (geometry-bucketing scheduler) vs sequential vs
               async-pipelined (futures + pack/execute overlap) serving
               on a mixed pool of bucket-mates (bit-identity asserted;
               requests/s, dispatches/request, pack_hidden_fraction)
  slo_*      — continuous batching under a seeded Poisson arrival
               process: deadline-driven background flusher + cost-model
               near-miss merging + epilogue folding vs exact-key
               caller-driven flush-per-arrival (bit-identity asserted;
               p50/p99 latency, dispatches/request, merged groups)
  bsr_serve_* — pruned-model serving lane: pools of same-geometry BSR
               weights (DLMC patterns, llama/qwen FFN geometries) served
               grouped (one batched dispatch per bucket) vs per-request
               (bit-identity asserted; requests/s, dispatches/request)
  stream_*   — out-of-core 2-D (K-window x N-tile) streaming vs the
               resident plan at several device_bytes caps, including a
               huge-N case whose budget forces column tiling
               (bit-identity asserted; Mnnz/s, window dispatches, column
               tiles, peak device working set)
  spmv_*     — skinny-N (N in {1, 4, 8}) SpMV fast lane vs the tall-N
               kernel at the same widths (bit-identity asserted; Mnnz/s,
               speedup ratio) plus an auto-routed serving pool reporting
               skinny_dispatches
  autotune_* — autotuned execution geometry + the persistent tuning/plan
               cache: default vs measured-best plans on a DLMC pruned
               pattern at the skinny boundary and on forced streaming
               (bit-identity asserted), cold vs warm plan-build time, and
               a fresh-process warm start over the same SEXTANS_TUNE_DIR

All wall-clock numbers use ``time.perf_counter`` (monotonic,
high-resolution); JAX results are ``block_until_ready``-fenced.

Run:  PYTHONPATH=src python -m benchmarks.run [--budget small|full]
                                              [--json PATH]
                                              [--only SUBSTR]

``--compare OLD.json NEW.json [--tolerance R]`` diffs two ``--json``
snapshots row-by-row (ratio new/old) and exits 2 on any regression beyond
the tolerance — the BENCH_*.json trajectory as a PR gate.
"""

from __future__ import annotations

import argparse
import json
import time
from typing import List, Optional

import numpy as np

# Collected rows of the current invocation:
# {"name", "us", "derived"[, "extra"]} — "extra" carries structured
# key/value metrics for machine consumers (the CI serve-smoke assert).
ROWS: List[dict] = []


def _row(name: str, us: float, derived: str,
         extra: Optional[dict] = None) -> None:
    row = {"name": name, "us": us, "derived": derived}
    if extra is not None:
        row["extra"] = extra
    ROWS.append(row)
    print(f"{name},{us:.1f},{derived}")


def bench_table1() -> None:
    from repro.core.perfmodel import table1_breakdown
    from repro.core.sparse import banded_sparse

    a = banded_sparse(3000, 3000, 12, seed=1)   # crystm03-like (scaled)
    t0 = time.perf_counter()
    t = table1_breakdown(a, n=8)
    us = (time.perf_counter() - t0) * 1e6
    _row("table1_incr_ooo", us, f"{t['incr_ooo']:.2f}x_paper_9.97x")
    _row("table1_incr_pus", us, f"{t['incr_pus']:.2f}x_paper_7.97x")
    _row("table1_incr_pes", us, f"{t['incr_pes']:.2f}x_paper_45.3x")
    _row("table1_accum", us, f"{t['accum_pes']:.0f}x_paper_3608x")


def bench_fig7(budget: str) -> None:
    from repro.core.partition import SextansParams
    from repro.core.perfmodel import (
        PLATFORMS, event_cycles, gpu_model_time, platform_time,
        throughput_gflops)
    from repro.data.matrices import paper_n_values, suite

    pp = SextansParams()
    entries = suite(budget)
    ratios_k80, ratios_v100 = [], []
    peak = {"SEXTANS": 0.0, "SEXTANS-P": 0.0}
    t0 = time.perf_counter()
    for e in entries:
        for n in paper_n_values(budget):
            cyc = event_cycles(e.matrix, n, pp)
            ts = platform_time(e.matrix, n, PLATFORMS["SEXTANS"], pp, cycles=cyc)
            # Sextans-P: same architecture, 350 MHz + V100 bandwidth
            tsp = max(cyc / PLATFORMS["SEXTANS-P"].freq_hz,
                      e.matrix.memory_traffic_bytes(n)
                      / PLATFORMS["SEXTANS-P"].bw_Bps)
            tk = gpu_model_time(e.matrix, n, PLATFORMS["K80"])
            tv = gpu_model_time(e.matrix, n, PLATFORMS["V100"])
            ratios_k80.append(tk / ts)
            ratios_v100.append(tv / tsp)
            peak["SEXTANS"] = max(peak["SEXTANS"],
                                  throughput_gflops(e.matrix, n, ts))
            peak["SEXTANS-P"] = max(peak["SEXTANS-P"],
                                    throughput_gflops(e.matrix, n, tsp))
    us = (time.perf_counter() - t0) * 1e6 / max(len(ratios_k80), 1)
    geo_k = float(np.exp(np.mean(np.log(ratios_k80))))
    geo_v = float(np.exp(np.mean(np.log(ratios_v100))))
    _row("fig7_geomean_vs_k80", us, f"{geo_k:.2f}x_paper_2.50x")
    _row("fig7_geomean_p_vs_v100", us, f"{geo_v:.2f}x_paper_1.14x")
    _row("fig8_peak_gflops", us, f"{peak['SEXTANS']:.0f}_paper_181.1")
    _row("fig8_peak_p_gflops", us, f"{peak['SEXTANS-P']:.0f}_paper_343.6")


def bench_fig9_fig10(budget: str) -> None:
    from repro.core.partition import SextansParams
    from repro.core.perfmodel import (
        PLATFORMS, bandwidth_utilization, event_cycles, gpu_model_time,
        platform_time)
    from repro.data.matrices import paper_n_values, suite

    pp = SextansParams()
    entries = suite(budget)
    utils = {"SEXTANS": [], "K80": []}
    eff = {"SEXTANS": [], "K80": []}
    t0 = time.perf_counter()
    count = 0
    for e in entries:
        for n in paper_n_values(budget):
            count += 1
            cyc = event_cycles(e.matrix, n, pp)
            ts = platform_time(e.matrix, n, PLATFORMS["SEXTANS"], pp, cycles=cyc)
            tk = gpu_model_time(e.matrix, n, PLATFORMS["K80"])
            utils["SEXTANS"].append(
                bandwidth_utilization(e.matrix, n, ts, PLATFORMS["SEXTANS"]))
            utils["K80"].append(
                bandwidth_utilization(e.matrix, n, tk, PLATFORMS["K80"]))
            p = e.matrix.problem_size_flop(n)
            eff["SEXTANS"].append(p / ts / PLATFORMS["SEXTANS"].power_W)
            eff["K80"].append(p / tk / PLATFORMS["K80"].power_W)
    us = (time.perf_counter() - t0) * 1e6 / max(count, 1)
    gu_s = float(np.exp(np.mean(np.log(utils["SEXTANS"]))))
    gu_k = float(np.exp(np.mean(np.log(utils["K80"]))))
    _row("fig9_bw_util_sextans", us, f"{gu_s:.4f}_paper_0.0385")
    _row("fig9_bw_util_k80", us, f"{gu_k:.4f}_paper_0.0147")
    ge_s = float(np.exp(np.mean(np.log(eff["SEXTANS"]))))
    ge_k = float(np.exp(np.mean(np.log(eff["K80"]))))
    _row("fig10_energy_ratio_vs_k80", us, f"{ge_s/ge_k:.2f}x_paper_6.25x")


def bench_hub_split(budget: str) -> None:
    """Beyond-paper: virtual-sub-row splitting for hub rows (the paper's
    OoO scheduler cannot fill a PE whose window is serialized by one heavy
    row). Reports the geomean-vs-K80 recovery on the power-law subset."""
    from repro.core.partition import SextansParams
    from repro.core.perfmodel import (
        PLATFORMS, event_cycles, gpu_model_time, platform_time)
    from repro.data.matrices import paper_n_values, suite

    pp = SextansParams()
    entries = [e for e in suite(budget) if e.family == "power_law"]
    base, split = [], []
    t0 = time.perf_counter()
    for e in entries:
        for n in paper_n_values(budget):
            tk = gpu_model_time(e.matrix, n, PLATFORMS["K80"])
            t_b = platform_time(e.matrix, n, PLATFORMS["SEXTANS"], pp,
                                cycles=event_cycles(e.matrix, n, pp))
            t_s = platform_time(e.matrix, n, PLATFORMS["SEXTANS"], pp,
                                cycles=event_cycles(e.matrix, n, pp,
                                                    hub_split=4 * pp.D))
            base.append(tk / t_b)
            split.append(tk / t_s)
    us = (time.perf_counter() - t0) * 1e6 / max(len(base), 1)
    gb = float(np.exp(np.mean(np.log(base))))
    gs = float(np.exp(np.mean(np.log(split))))
    _row("hubsplit_powerlaw_vs_k80", us, f"{gb:.2f}x->{gs:.2f}x_beyond_paper")


def _time_call(fn, iters: int = 5) -> float:
    """Best-of-``iters`` wall clock (timeit practice: the minimum is the
    least noise-contaminated estimate). Warms once for compile/caches."""
    fn()  # warm / compile
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def bench_kernels() -> None:
    import jax.numpy as jnp

    import repro.sparse_api as sp
    from repro.core.sparse import power_law_sparse

    rng = np.random.default_rng(0)
    a = power_law_sparse(512, 512, 6, seed=1)
    b = jnp.asarray(rng.standard_normal((512, 64)), jnp.float32)
    A = sp.from_sparse_matrix(a, tm=128, k0=128, chunk=8, bucket=False)
    for backend in ("pallas", "jnp"):
        us = _time_call(
            lambda: sp.spmm(A, b, backend=backend).block_until_ready())
        gf = a.problem_size_flop(64) / (us / 1e6) / 1e9
        _row(f"kernel_spmm_{backend}", us, f"{gf:.3f}GFLOPs_cpu_interpret")


def bench_plan() -> None:
    """SpmmPlan.run vs unplanned spmm on the jnp (CPU production) backend.

    Asserts bit-identity between the two paths before timing — the plan is
    a dispatch/precompute optimization, never a numerics change."""
    import jax.numpy as jnp

    import repro.sparse_api as sp
    from repro.core.sparse import power_law_sparse

    rng = np.random.default_rng(0)
    a = power_law_sparse(512, 512, 6, seed=1)
    b = jnp.asarray(rng.standard_normal((512, 64)), jnp.float32)
    A = sp.from_sparse_matrix(a, tm=128, k0=128, chunk=8, bucket=True)
    plan = sp.plan(A, 64, backend="jnp")
    y_plan = np.asarray(plan.run(b))
    y_unpl = np.asarray(sp.spmm(A, b, backend="jnp"))
    assert np.array_equal(y_plan, y_unpl), "plan.run diverged from spmm"
    us_u = _time_call(
        lambda: sp.spmm(A, b, backend="jnp").block_until_ready(), iters=20)
    us_p = _time_call(lambda: plan.run(b).block_until_ready(), iters=20)
    _row("plan_spmm_unplanned", us_u, "jnp_backend")
    _row("plan_spmm", us_p, f"{us_u / us_p:.2f}x_vs_unplanned_bitexact")


def bench_scheduler() -> None:
    from repro.core.hflex import pack_pe_streams
    from repro.core.partition import SextansParams
    from repro.core.sparse import power_law_sparse

    a = power_law_sparse(20_000, 20_000, 6, seed=2)
    pp = SextansParams(K0=4096, P=64, D=10)

    def one(mode: str, iters: int) -> None:
        ps = pack_pe_streams(a, pp, mode=mode)
        us = _time_call(lambda: pack_pe_streams(a, pp, mode=mode),
                        iters=iters)
        nnz_per_s = a.nnz / (us / 1e6)
        name = "sched_preprocess" if mode == "vectorized" else \
            f"sched_preprocess_{mode}"
        _row(name, us,
             f"{nnz_per_s/1e6:.2f}Mnnz/s_bubbles_{ps.bubble_fraction:.3f}")

    one("vectorized", iters=10)    # the production preprocessing path
    one("greedy", iters=2)         # exact-greedy reference (paper Fig. 5)


def bench_serve() -> None:
    """Batched vs sequential vs async-pipelined serving on a mixed pool of
    32 bucket-mates (plus a few odd-geometry singletons): the batched rows
    measure dispatch amortization (one batch-grid dispatch per bucket
    group), the ``serve_async`` row measures the futures-based
    pack/execute overlap on top of it — host packing runs on worker
    threads while the device computes, reported as
    ``pack_hidden_fraction``.  Bit-identity across all three paths is
    asserted before timing."""
    from repro.core.engine import SextansEngine
    from repro.core.sparse import power_law_sparse, random_sparse
    from repro.launch.serve import SpmmRequest, serve_spmm_requests

    rng = np.random.default_rng(0)
    reqs = []
    for i in range(32):                     # one bucket: 32 mates, ragged N
        a = power_law_sparse(512, 512, 5, seed=i)
        n = 24 if i % 2 else 32             # both pad to the N=32 bucket
        reqs.append(SpmmRequest(
            a=a, b=rng.standard_normal((512, n)).astype(np.float32)))
    for i in range(4):                      # odd geometries -> singletons
        a = random_sparse(200 + 40 * i, 300, 0.02, seed=100 + i)
        reqs.append(SpmmRequest(
            a=a, b=rng.standard_normal((300, 32)).astype(np.float32)))

    def engine():
        return SextansEngine(tm=128, k0=128, chunk=8, impl="jnp")

    # warm all paths (compiles), then assert bit-identity
    outs_b, _ = serve_spmm_requests(reqs, engine(), batched=True)
    outs_s, _ = serve_spmm_requests(reqs, engine(), batched=False)
    outs_a, _ = serve_spmm_requests(reqs, engine(), async_pipeline=True)
    for x, y in zip(outs_b, outs_s):
        assert np.array_equal(x, y), "batched serving diverged"
    for x, y in zip(outs_b, outs_a):
        assert np.array_equal(x, y), "async serving diverged from batched"

    for mode, kw in (("serve_batched", dict(batched=True)),
                     ("serve_sequential", dict(batched=False)),
                     ("serve_async", dict(async_pipeline=True))):
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            _, stats = serve_spmm_requests(reqs, engine(), **kw)
            dt = time.perf_counter() - t0
            if best is None or dt < best[0]:
                best = (dt, stats)
        dt, stats = best
        us = dt * 1e6 / len(reqs)
        rps = len(reqs) / dt
        dpr = stats["dispatches_per_request"]
        hidden = stats["pack_hidden_fraction"]
        derived = (f"{rps:.0f}req/s_{dpr:.3f}disp/req_"
                   f"bf{stats['batched_fraction']:.2f}")
        if mode == "serve_async":
            derived += f"_packhidden{hidden:.2f}_bitexact_vs_batched"
        _row(mode, us, derived,
             extra={
                 "requests_per_s": rps,
                 "dispatches_per_request": dpr,
                 "batched_fraction": stats["batched_fraction"],
                 "groups": stats["groups"],
                 "compute_gflops": stats["compute_gflops"],
                 "pack_hidden_fraction": hidden,
                 "overlap_s": stats["overlap_s"],
                 "bit_identical": True,
             })


def bench_slo() -> None:
    """Continuous batching under load: a seeded Poisson arrival process
    over a mixed near-miss pool (two adjacent LW buckets, per-request
    ``(alpha, beta)`` drawn from a small set, tight deadlines) served two
    ways.  The ``slo_caller_flush`` baseline is the exact-key scheduler
    flushed at every arrival — one dispatch per request, saturating the
    dispatch thread so queueing delay dominates the tail.  The
    ``slo_continuous`` lane is the deadline-driven background flusher
    with the cost-model policy: near-miss buckets merge into padded
    groups, epilogues fold into per-member vectors, and admission waits
    for cost-model fullness or deadline urgency.  Both lanes replay the
    SAME seeded arrival schedule; both are asserted bit-identical to the
    per-request engine reference before anything is reported."""
    from repro.core.engine import SextansEngine
    from repro.core.sparse import power_law_sparse
    from repro.launch.policy import MergePolicy
    from repro.launch.serve import SpmmRequest, SpmmScheduler

    rng = np.random.default_rng(7)
    reqs = []
    for i in range(96):                 # adjacent LW buckets: 3 vs 6 nnz/row
        a = power_law_sparse(256, 256, 3 if i % 2 == 0 else 6, seed=i)
        b = rng.standard_normal((256, 24)).astype(np.float32)
        c = rng.standard_normal((256, 24)).astype(np.float32)
        reqs.append(SpmmRequest(a=a, b=b, c=c, alpha=[1.0, 0.5, 2.0][i % 3],
                                beta=[0.0, 1.0][i % 2]))
    # one fixed Poisson schedule (mean gap 300us) replayed by both lanes
    gaps = np.random.default_rng(42).exponential(3e-4, size=len(reqs))
    deadline_s = 0.01

    def engine():
        return SextansEngine(tm=128, k0=512, chunk=8, impl="jnp")

    eng_ref = engine()
    refs = [np.asarray(eng_ref.spmm(eng_ref.pack(r.a), r.b, r.c,
                                    r.alpha, r.beta)) for r in reqs]

    def paced_submit(submit_fn):
        futs, nxt = [], time.monotonic()
        for r, gap in zip(reqs, gaps):
            nxt += gap
            wait = nxt - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            futs.append(submit_fn(r))
        return futs

    def run_caller_flush():
        sched = SpmmScheduler(engine(), async_pipeline=True)
        t0 = time.perf_counter()
        futs = paced_submit(lambda r: (sched.submit(r), sched.flush())[0])
        outs = [f.result(timeout=300) for f in futs]
        dt = time.perf_counter() - t0
        res = (outs, dict(sched.stats), sched.latency_p50,
               sched.latency_p99, dt)
        sched.shutdown()
        return res

    def run_continuous():
        sched = SpmmScheduler(
            engine(), async_pipeline=True, background_flush=True,
            policy=MergePolicy(dispatch_overhead_cycles=5e5),
            flush_poll_s=0.002)
        t0 = time.perf_counter()
        futs = paced_submit(lambda r: sched.submit(SpmmRequest(
            a=r.a, b=r.b, c=r.c, alpha=r.alpha, beta=r.beta,
            deadline_s=deadline_s)))
        outs = [f.result(timeout=300) for f in futs]
        dt = time.perf_counter() - t0
        res = (outs, dict(sched.stats), sched.latency_p50,
               sched.latency_p99, dt)
        sched.shutdown()
        return res

    rows = {}
    for name, run in (("slo_caller_flush", run_caller_flush),
                      ("slo_continuous", run_continuous)):
        best = None
        for rep in range(3):            # rep 0 warms compiles (G buckets,
            outs, st, p50, p99, dt = run()  # merged-lw geometry)
            for o, ref in zip(outs, refs):
                assert np.array_equal(o, ref), f"{name} diverged"
            if rep == 0:
                continue
            if best is None or p99 < best[2]:
                best = (st, p50, p99, dt)
        st, p50, p99, dt = best
        dpr = st["dispatches"] / st["requests"]
        rows[name] = (st, p50, p99, dpr)
        _row(name, p99 * 1e6,
             f"p50_{p50*1e3:.1f}ms_p99_{p99*1e3:.1f}ms_"
             f"{dpr:.3f}disp/req_bitexact",
             extra={
                 "latency_p50_ms": p50 * 1e3,
                 "latency_p99_ms": p99 * 1e3,
                 "dispatches_per_request": dpr,
                 "requests_per_s": st["requests"] / dt,
                 "merged_groups": st["merged_groups"],
                 "merge_saved_dispatches": st["merge_saved_dispatches"],
                 "folded_requests": st["folded_requests"],
                 "flusher_flushes": st["flusher_flushes"],
                 "deadline_s": deadline_s,
                 "bit_identical": True,
             })
    (st_b, _, p99_b, dpr_b) = rows["slo_caller_flush"]
    (st_c, _, p99_c, dpr_c) = rows["slo_continuous"]
    _row("slo_dispatch_savings", 0.0,
         f"{dpr_b/dpr_c:.1f}x_fewer_dispatches_"
         f"p99_{p99_b/p99_c:.2f}x_better",
         extra={
             "dispatch_reduction_x": dpr_b / dpr_c,
             "p99_speedup_x": p99_b / p99_c,
             "merged_groups": st_c["merged_groups"],
         })


def bench_stream() -> None:
    """Out-of-core 2-D (K-window x N-tile) streaming vs the resident plan
    at several ``device_bytes`` caps: achieved Mnnz/s, window dispatches
    per run, column tiles, and the device working set
    (peak_payload_bytes) actually pinned.  Streaming is bit-identical to
    the resident path — asserted before timing — so the rows measure pure
    pipeline overhead: what it costs to run a matrix the chip could not
    hold.  The ``huge_n`` row caps the budget below one full-N window
    chunk, so the plan must tile the dense operand's columns too
    (``n_tiles > 1``) — tiled runs return host numpy, hence the
    ``jax.block_until_ready`` fence (a no-op on numpy)."""
    import jax

    import repro.sparse_api as sp
    from repro.core.sparse import power_law_sparse

    rng = np.random.default_rng(0)
    a = power_law_sparse(1024, 8192, 6, seed=3)
    A = sp.from_sparse_matrix(a, tm=128, k0=128, chunk=8, bucket=True)
    n = 16
    b = rng.standard_normal((8192, n)).astype(np.float32)
    payload = A.nbytes

    resident = sp.plan(A, n, backend="jnp")
    y_ref = np.asarray(resident.run(b))
    us_r = _time_call(lambda: resident.run(b).block_until_ready(), iters=10)
    mnnz_r = a.nnz / (us_r / 1e6) / 1e6
    _row("stream_spmm_resident", us_r,
         f"{mnnz_r:.1f}Mnnz/s_payload{payload}B",
         extra={"payload_bytes": payload, "mnnz_per_s": mnnz_r})

    for frac in (4, 16, 64):
        cap = payload // frac
        P = sp.plan(A, n, backend="jnp", device_bytes=cap)
        assert isinstance(P, sp.StreamingPlan), "cap did not select streaming"
        y = np.asarray(P.run(b))
        bitexact = bool(np.array_equal(y, y_ref))
        assert bitexact, "streaming diverged from resident plan"
        us = _time_call(lambda: jax.block_until_ready(P.run(b)), iters=10)
        mnnz = a.nnz / (us / 1e6) / 1e6
        _row(f"stream_spmm_cap_payload/{frac}", us,
             f"{mnnz:.1f}Mnnz/s_{P.window_dispatches}disp_"
             f"wc{P.window_chunk}_nt{P.n_tiles}_bitexact",
             extra={
                 "streamed": 1,
                 "device_bytes": cap,
                 "window_dispatches": P.window_dispatches,
                 "window_chunk": P.window_chunk,
                 "n_tile": P.n_tile,
                 "n_tiles": P.n_tiles,
                 "peak_payload_bytes": P.peak_payload_bytes,
                 "payload_bytes": payload,
                 "mnnz_per_s": mnnz,
                 "bit_identical": bitexact,
             })

    # huge-N: the budget holds less than ONE full-N window chunk, so the
    # 2-D grid must tile columns as well as windows
    n_huge = 256
    b_huge = rng.standard_normal((8192, n_huge)).astype(np.float32)
    ref_huge = np.asarray(sp.plan(A, n_huge, backend="jnp").run(b_huge))
    floor = sp.plan(A, n_huge, backend="jnp", stream=True,
                    window_chunk=1).peak_payload_bytes
    cap = min(int(floor * 0.5), payload)
    P = sp.plan(A, n_huge, backend="jnp", device_bytes=cap)
    assert isinstance(P, sp.StreamingPlan), "cap did not select streaming"
    assert P.n_tiles > 1, "budget failed to force column tiling"
    y = P.run(b_huge)
    assert isinstance(y, np.ndarray)
    bitexact = bool(np.array_equal(y, ref_huge))
    assert bitexact, "2-D streaming diverged from resident plan"
    us = _time_call(lambda: jax.block_until_ready(P.run(b_huge)), iters=5)
    mnnz = a.nnz / (us / 1e6) / 1e6
    _row("stream_spmm_2d_huge_n", us,
         f"{mnnz:.1f}Mnnz/s_{P.window_dispatches}disp_wc{P.window_chunk}_"
         f"nt{P.n_tiles}_bitexact",
         extra={
             "streamed": 1,
             "device_bytes": cap,
             "window_dispatches": P.window_dispatches,
             "window_chunk": P.window_chunk,
             "n_tile": P.n_tile,
             "n_tiles": P.n_tiles,
             "peak_payload_bytes": P.peak_payload_bytes,
             "payload_bytes": payload,
             "mnnz_per_s": mnnz,
             "bit_identical": bitexact,
         })


# f32 rounding of a row's sum over its few (power-law, ~6 per row) terms,
# relative to the result's largest magnitude, with room to spare
SPMV_TOL = 1e-5


def bench_spmv() -> None:
    """Skinny-N SpMV fast lane vs the tall-N kernel at N in {1, 4, 8}:
    ``pallas`` takes its lane from N (``hflex_lane``), so left alone it
    pads N to 8 lanes instead of TN=128 (one column tile), so
    every B window streams once and >90% of the padding work disappears.
    Both kernels run compiled on a TPU and interpreted elsewhere.  The two
    widths compile to different matmuls whose f32 sums may round
    differently, so the lane is held to the tall-N result within
    ``SPMV_TOL`` of its largest magnitude (asserted); the ratio is the
    lane's speedup at that width.  The ``serve_pool`` row routes a skinny
    request pool through ``impl="auto"`` and reports the scheduler's
    ``skinny_dispatches`` accounting."""
    import jax.numpy as jnp

    import repro.sparse_api as sp
    from repro.core.engine import SextansEngine
    from repro.core.sparse import power_law_sparse
    from repro.launch.serve import SpmmRequest, serve_spmm_requests

    rng = np.random.default_rng(0)
    a = power_law_sparse(512, 1024, 6, seed=1)
    A = sp.from_sparse_matrix(a, tm=128, k0=128, chunk=8, bucket=True)
    for n in (1, 4, 8):
        b = jnp.asarray(rng.standard_normal((1024, n)), jnp.float32)
        y_tall = np.asarray(sp.spmm(A, b, backend="pallas", tn=128))
        y_skinny = np.asarray(sp.spmm(A, b, backend="pallas"))
        diff = (float(np.abs(y_skinny - y_tall).max())
                / max(float(np.abs(y_tall).max()), 1e-30))
        assert diff <= SPMV_TOL, (
            f"spmv lane diverged from tall-N kernel at N={n}: {diff:.2e}")
        us_t = _time_call(lambda: sp.spmm(
            A, b, backend="pallas", tn=128).block_until_ready())
        us_s = _time_call(lambda: sp.spmm(
            A, b, backend="pallas").block_until_ready())
        mnnz_t = a.nnz / (us_t / 1e6) / 1e6
        mnnz_s = a.nnz / (us_s / 1e6) / 1e6
        ratio = us_t / us_s
        _row(f"spmv_n{n}_tall", us_t, f"{mnnz_t:.2f}Mnnz/s_tn128",
             extra={"n": n, "mnnz_per_s": mnnz_t})
        _row(f"spmv_n{n}_skinny", us_s,
             f"{mnnz_s:.2f}Mnnz/s_{ratio:.2f}x_vs_talln",
             extra={"n": n, "mnnz_per_s": mnnz_s,
                    "speedup_vs_talln": ratio,
                    "max_rel_diff_vs_talln": diff})

    # auto-routed skinny pool: the scheduler must count the lane
    reqs = [SpmmRequest(
        a=power_law_sparse(256, 320, 5, seed=i),
        b=rng.standard_normal((320, 4)).astype(np.float32))
        for i in range(8)]
    t0 = time.perf_counter()
    _, stats = serve_spmm_requests(
        reqs, SextansEngine(tm=128, k0=128, chunk=8, impl="auto"))
    dt = time.perf_counter() - t0
    assert stats["skinny_dispatches"] > 0, "auto pool missed the SpMV lane"
    _row("spmv_serve_pool", dt * 1e6 / len(reqs),
         f"{stats['skinny_dispatches']}skinny_disp_auto_routed",
         extra={"skinny_dispatches": stats["skinny_dispatches"],
                "requests": len(reqs),
                "dispatches_per_request": stats["dispatches_per_request"]})


def bench_bsr_serve() -> None:
    """Pruned-model serving lane: pools of same-geometry BSR weights
    (DLMC-style patterns on llama/qwen FFN geometries, budget-scaled with
    the aspect ratio preserved) served grouped vs per-request.  A pool of
    G same-sparsity members shares one bucketed group key, so the grouped
    path flushes as ONE batched dispatch (dispatches/request = 1/G); the
    mixed-sparsity DLMC grid row shows bucketing still amortizing across
    kept-block buckets.  Grouped results are bit-identical to the
    sequential path (asserted before timing)."""
    from repro.configs import get_config
    from repro.core.engine import SextansEngine
    from repro.data.matrices import (
        banded_pruned, block_random_pruned, dlmc_suite, magnitude_pruned)
    from repro.launch.serve import SpmmRequest, serve_spmm_requests
    from repro.sparse_api import Format, from_dense

    BLK = 16
    rng = np.random.default_rng(0)

    def scaled_ffn(arch: str, target: int = 128):
        cfg = get_config(arch)
        d = max(BLK, (target // BLK) * BLK)
        ff = max(BLK, int(round(cfg.d_ff / cfg.d_model * d / BLK)) * BLK)
        return d, ff

    def engine():
        return SextansEngine(tm=128, k0=128, chunk=8, impl="jnp")

    patterns = (magnitude_pruned, banded_pruned, block_random_pruned)
    for arch in ("llama3.2-1b", "qwen1.5-32b"):
        d, ff = scaled_ffn(arch)
        n = 32
        # G=16 pruned up-projections at one sparsity level: the exact
        # kept-block count is sparsity-determined, so all 16 share a
        # bucket and the grouped path is a single dispatch
        reqs = []
        for i in range(16):
            w = patterns[i % 3](d, ff, 0.90, block=(BLK, BLK), seed=i)
            a = from_dense(w.T, format=Format.BSR, block=(BLK, BLK))
            reqs.append(SpmmRequest(
                a=a, b=rng.standard_normal((d, n)).astype(np.float32)))

        outs_g, _ = serve_spmm_requests(reqs, engine(), batched=True)
        outs_s, _ = serve_spmm_requests(reqs, engine(), batched=False)
        bitexact = all(np.array_equal(x, y) for x, y in zip(outs_g, outs_s))
        assert bitexact, f"grouped BSR serving diverged ({arch})"

        for mode, kw in (("grouped", dict(batched=True)),
                         ("sequential", dict(batched=False))):
            best = None
            for _ in range(3):
                t0 = time.perf_counter()
                _, stats = serve_spmm_requests(reqs, engine(), **kw)
                dt = time.perf_counter() - t0
                if best is None or dt < best[0]:
                    best = (dt, stats)
            dt, stats = best
            us = dt * 1e6 / len(reqs)
            rps = len(reqs) / dt
            dpr = stats["dispatches_per_request"]
            tag = arch.split("-")[0].replace(".", "_")
            _row(f"bsr_serve_{mode}_{tag}", us,
                 f"{rps:.0f}req/s_{dpr:.3f}disp/req_"
                 f"bf{stats['batched_fraction']:.2f}"
                 + ("_bitexact_vs_sequential" if mode == "grouped" else ""),
                 extra={
                     "arch": arch,
                     "ffn_geometry": [d, ff],
                     "requests": len(reqs),
                     "requests_per_s": rps,
                     "dispatches_per_request": dpr,
                     "batched_fraction": stats["batched_fraction"],
                     "groups": stats["groups"],
                     "bit_identical": bitexact,
                 })

    # the full DLMC grid (3 patterns x 5 sparsities) on one geometry:
    # ragged kept-block counts spread over power-of-two buckets, grouped
    # dispatch count = number of occupied buckets, not requests
    d, ff = scaled_ffn("llama3.2-1b")
    reqs = []
    for e in dlmc_suite(d, ff, block=(BLK, BLK)):
        a = from_dense(e.weight.T, format=Format.BSR, block=(BLK, BLK))
        reqs.append(SpmmRequest(
            a=a, b=rng.standard_normal((d, 32)).astype(np.float32)))
    outs_g, _ = serve_spmm_requests(reqs, engine(), batched=True)
    outs_s, _ = serve_spmm_requests(reqs, engine(), batched=False)
    bitexact = all(np.array_equal(x, y) for x, y in zip(outs_g, outs_s))
    assert bitexact, "DLMC-grid grouped serving diverged"
    t0 = time.perf_counter()
    _, stats = serve_spmm_requests(reqs, engine(), batched=True)
    dt = time.perf_counter() - t0
    dpr = stats["dispatches_per_request"]
    _row("bsr_serve_dlmc_grid", dt * 1e6 / len(reqs),
         f"{len(reqs)}req_{stats['groups']}buckets_{dpr:.3f}disp/req_bitexact",
         extra={
             "requests": len(reqs),
             "requests_per_s": len(reqs) / dt,
             "dispatches_per_request": dpr,
             "batched_fraction": stats["batched_fraction"],
             "groups": stats["groups"],
             "bit_identical": bitexact,
         })


def bench_autotune() -> None:
    """Autotuned execution geometry + the persistent tuning/plan cache
    (``repro.sparse_api.autotune``): default-heuristic vs measured-best
    execution on a DLMC-style pruned pattern at the skinny-N boundary and
    on forced streaming (where the tuner picks the window-chunk/column-tile
    geometry the no-budget heuristic cannot), plus the cold-start story —
    ``autotune_first_build`` times this process's measure-mode plan build
    (the TuningDB and JAX's persistent compilation cache make it cheap on
    a second run over the same ``SEXTANS_TUNE_DIR`` and cache directory)
    and ``autotune_warm_rebuild`` rebuilds after ``clear_plan_cache()``
    (bit-identity of every tuned result is asserted/recorded throughout).
    Without ``SEXTANS_TUNE_DIR`` the TuningDB lives in memory only."""
    import os

    import jax
    import jax.numpy as jnp

    import repro.sparse_api as sp
    from repro import compile_cache
    from repro.core.engine import SextansEngine
    from repro.core.sparse import power_law_sparse
    from repro.data.matrices import magnitude_pruned
    from repro.launch.serve import SpmmRequest, serve_spmm_requests

    tune_dir = os.environ.get("SEXTANS_TUNE_DIR")

    rng = np.random.default_rng(0)
    # DLMC-style magnitude-pruned weight at the skinny-N boundary (N=8):
    # backend choice (tall kernel vs SpMV lane vs jnp) is live here
    w = magnitude_pruned(256, 512, 0.9, block=(16, 16), seed=1)
    A = sp.from_dense(np.asarray(w.T, np.float32), tm=128, k0=128, chunk=8,
                      bucket=True)
    nnz = A.nnz
    n = 8
    b = jnp.asarray(rng.standard_normal((A.shape[1], n)), jnp.float32)

    # -- cold-start: first measure-mode build in THIS process.  With a
    # pre-populated tune dir (CI run 2) the same call is a DB hit plus
    # compilation-cache hits — no measurement, no compile.
    ts0 = dict(sp.TUNE_STATS)
    cc0 = dict(compile_cache.STATS)
    t0 = time.perf_counter()
    P_tuned = sp.plan(A, n, autotune="measure")
    build_s = time.perf_counter() - t0
    _row("autotune_first_build", build_s * 1e6,
         f"{build_s:.3f}s_db_hits{sp.TUNE_STATS['db_hits'] - ts0['db_hits']}"
         f"_misses{sp.TUNE_STATS['db_misses'] - ts0['db_misses']}",
         extra={
             "build_s": build_s,
             "tune_db_hits": sp.TUNE_STATS["db_hits"] - ts0["db_hits"],
             "tune_db_misses": sp.TUNE_STATS["db_misses"] - ts0["db_misses"],
             "measured": sp.TUNE_STATS["measured"] - ts0["measured"],
             "compile_cache_hits": (compile_cache.STATS["hits"]
                                    - cc0["hits"]),
             "tune_dir": tune_dir,
         })

    # -- default vs tuned throughput at the skinny boundary
    P_def = sp.plan(A, n)
    y_ref = np.asarray(P_def.run(b))
    y_tuned = np.asarray(P_tuned.run(b))
    bitexact = bool(np.array_equal(y_tuned, y_ref))
    assert bitexact, "tuned plan diverged from default resolution"
    us_d = _time_call(lambda: P_def.run(b).block_until_ready(), iters=10)
    us_t = _time_call(lambda: P_tuned.run(b).block_until_ready(), iters=10)
    mnnz_d = nnz / (us_d / 1e6) / 1e6
    mnnz_t = nnz / (us_t / 1e6) / 1e6
    _row("autotune_skinny_n8_default", us_d,
         f"{mnnz_d:.2f}Mnnz/s_{P_def.backend}",
         extra={"mnnz_per_s": mnnz_d, "backend": P_def.backend, "n": n})
    _row("autotune_skinny_n8_tuned", us_t,
         f"{mnnz_t:.2f}Mnnz/s_{P_tuned.backend}_"
         f"{us_d / us_t:.2f}x_vs_default_bitexact",
         extra={"mnnz_per_s": mnnz_t, "backend": P_tuned.backend, "n": n,
                "speedup_vs_default": us_d / us_t,
                "tuned": bool(P_tuned.tuned), "bit_identical": bitexact})

    # -- forced streaming: no budget -> the heuristic takes the finest
    # granularity (window_chunk=1); the tuner ranks the (wc, n_tile) grid
    # with the event-cycle model and measures the survivors
    big = power_law_sparse(1024, 8192, 6, seed=3)
    B = sp.from_sparse_matrix(big, tm=128, k0=128, chunk=8, bucket=True)
    bb = rng.standard_normal((8192, 16)).astype(np.float32)
    S_def = sp.plan(B, 16, backend="jnp", stream=True)
    S_tun = sp.plan(B, 16, backend="jnp", stream=True, autotune="measure")
    y_sd = np.asarray(S_def.run(bb))
    y_st = np.asarray(S_tun.run(bb))
    sbit = bool(np.array_equal(y_st, y_sd))
    assert sbit, "tuned streaming diverged from default streaming"
    us_sd = _time_call(lambda: jax.block_until_ready(S_def.run(bb)), iters=5)
    us_st = _time_call(lambda: jax.block_until_ready(S_tun.run(bb)), iters=5)
    mnnz_sd = big.nnz / (us_sd / 1e6) / 1e6
    mnnz_st = big.nnz / (us_st / 1e6) / 1e6
    _row("autotune_stream_default", us_sd,
         f"{mnnz_sd:.1f}Mnnz/s_wc{S_def.window_chunk}_"
         f"{S_def.window_dispatches}disp",
         extra={"mnnz_per_s": mnnz_sd, "window_chunk": S_def.window_chunk,
                "window_dispatches": S_def.window_dispatches})
    _row("autotune_stream_tuned", us_st,
         f"{mnnz_st:.1f}Mnnz/s_wc{S_tun.window_chunk}_"
         f"{S_tun.window_dispatches}disp_{us_sd / us_st:.2f}x_bitexact",
         extra={"mnnz_per_s": mnnz_st, "window_chunk": S_tun.window_chunk,
                "window_dispatches": S_tun.window_dispatches,
                "speedup_vs_default": us_sd / us_st,
                "tuned": bool(S_tun.tuned), "bit_identical": sbit})

    # -- warm rebuild: drop the in-process plan cache, rebuild in cached
    # mode — the decision comes from the DB, the executable from JAX's
    # persistent compilation cache (a re-trace, no re-compile)
    cc0 = dict(compile_cache.STATS)
    sp.clear_plan_cache()
    t0 = time.perf_counter()
    P_warm = sp.plan(A, n, autotune="cached")
    warm_s = time.perf_counter() - t0
    y_warm = np.asarray(P_warm.run(b))
    wbit = bool(np.array_equal(y_warm, y_ref))
    assert wbit, "warm-rebuilt plan diverged"
    _row("autotune_warm_rebuild", warm_s * 1e6,
         f"{warm_s:.3f}s_cache_hits"
         f"{compile_cache.STATS['hits'] - cc0['hits']}",
         extra={
             "build_s": warm_s,
             "warm_lt_cold": bool(warm_s < build_s),
             "compile_cache_hits": compile_cache.STATS["hits"] - cc0["hits"],
             "bit_identical": wbit,
         })

    # -- serving pool, default vs engine-tuned: the scheduler threads the
    # mode into every plan build; on a warm DB the tuned pool's plan
    # builds are pure lookups (tune_db_misses == 0 on the second run)
    reqs = [SpmmRequest(
        a=power_law_sparse(256 + 64 * (i % 2), 320, 5, seed=i),
        b=rng.standard_normal((320, 8)).astype(np.float32))
        for i in range(8)]

    def serve(autotune):
        eng = SextansEngine(tm=128, k0=128, chunk=8, impl="auto",
                            autotune=autotune)
        t0 = time.perf_counter()
        outs, stats = serve_spmm_requests(reqs, eng)
        return outs, stats, time.perf_counter() - t0

    outs_off, stats_off, dt_off = serve(None)
    serve("measure")                               # populate / verify DB
    outs_on, stats_on, dt_on = serve("measure")    # warm: all DB hits
    pbit = all(np.array_equal(x, y) for x, y in zip(outs_off, outs_on))
    assert pbit, "tuned serving pool diverged from default"
    _row("autotune_serve_pool_default", dt_off * 1e6 / len(reqs),
         f"{len(reqs) / dt_off:.0f}req/s",
         extra={"requests_per_s": len(reqs) / dt_off})
    _row("autotune_serve_pool_tuned", dt_on * 1e6 / len(reqs),
         f"{len(reqs) / dt_on:.0f}req/s_"
         f"{stats_on['tuned_dispatches']}tuned_"
         f"db{stats_on['tune_db_hits']}h/{stats_on['tune_db_misses']}m_"
         "bitexact",
         extra={
             "requests_per_s": len(reqs) / dt_on,
             "tuned_dispatches": stats_on["tuned_dispatches"],
             "tune_db_hits": stats_on["tune_db_hits"],
             "tune_db_misses": stats_on["tune_db_misses"],
             "plan_cache_hits": stats_on["plan_cache_hits"],
             "plan_cache_misses": stats_on["plan_cache_misses"],
             "plan_build_warm_s": stats_on["plan_build_warm_s"],
             "plan_build_cold_s": stats_on["plan_build_cold_s"],
             "bit_identical": pbit,
         })


def bench_validate() -> None:
    """Run the ``repro.analysis`` invariant validator over every packed
    artifact family the benchmarks dispatch (kernel/plan slabs, streaming
    slabs + a window slice, the serving bucket group, BSR, PE streams) and
    report the validation overhead per artifact — the cost of running with
    ``SEXTANS_CHECK=1``."""
    import repro.sparse_api as sp
    from repro.analysis.validate import validate
    from repro.core.hflex import pack_pe_streams
    from repro.core.partition import SextansParams
    from repro.core.sparse import power_law_sparse, to_dense

    kern = sp.from_sparse_matrix(power_law_sparse(512, 512, 6, seed=1),
                                 tm=128, k0=128, chunk=8, bucket=True)
    big = sp.from_sparse_matrix(power_law_sparse(1024, 8192, 6, seed=3),
                                tm=128, k0=128, chunk=8, bucket=True)
    group = sp.stack_hflex([
        sp.from_sparse_matrix(power_law_sparse(512, 512, 5, seed=i),
                              tm=128, k0=128, chunk=8, bucket=True)
        for i in range(4)])
    dense = to_dense(power_law_sparse(256, 256, 4, seed=7))
    bsr = sp.from_dense(np.asarray(dense, np.float32),
                        format=sp.Format.BSR, block=(64, 64))
    streams = pack_pe_streams(power_law_sparse(2000, 2000, 6, seed=2),
                              SextansParams(K0=512, P=16, D=10))
    artifacts = [
        ("kernel_slabs_512", kern),
        ("stream_slabs_1024x8192", big),
        ("stream_window_slice", big.windows(0, 4)),
        ("serve_bucket_group", group),
        ("bsr_weight_256", bsr),
        ("pe_streams_2000", streams),
    ]
    total_us = 0.0
    for name, art in artifacts:
        t0 = time.perf_counter()
        validate(art)
        us = (time.perf_counter() - t0) * 1e6
        total_us += us
        _row(f"validate_{name}", us, "invariants_ok")
    _row("validate_overhead_total", total_us,
         f"{len(artifacts)}artifacts_SEXTANS_CHECK_cost",
         extra={"artifacts": len(artifacts),
                "total_us": total_us,
                "per_artifact_us": total_us / len(artifacts)})


def compare_snapshots(old_path: str, new_path: str,
                      tolerance: float = 1.25) -> int:
    """Perf-regression diff between two ``--json`` snapshots.

    Joins rows by name and reports ``new_us / old_us`` per row: a ratio
    above ``tolerance`` is a REGRESSION, below ``1/tolerance`` an
    improvement, anything between is noise-tolerant ``ok``.  Rows present
    in only one snapshot are listed (dropped/added), not judged.  Returns
    the regression count (the CLI exits 2 when it is nonzero), so the
    BENCH_*.json trajectory can gate PRs instead of just accumulating.
    """
    with open(old_path) as f:
        old = json.load(f)
    with open(new_path) as f:
        new = json.load(f)
    old_rows = {r["name"]: r for r in old.get("rows", [])}
    new_rows = {r["name"]: r for r in new.get("rows", [])}
    regressions = 0
    print("name,old_us,new_us,ratio,verdict")
    for name, orow in old_rows.items():
        nrow = new_rows.get(name)
        if nrow is None:
            continue
        ou, nu = float(orow["us"]), float(nrow["us"])
        ratio = nu / ou if ou > 0 else float("inf")
        if ratio > tolerance:
            verdict = "REGRESSION"
            regressions += 1
        elif ratio < 1.0 / tolerance:
            verdict = "improved"
        else:
            verdict = "ok"
        print(f"{name},{ou:.1f},{nu:.1f},{ratio:.3f},{verdict}")
    dropped = sorted(set(old_rows) - set(new_rows))
    added = sorted(set(new_rows) - set(old_rows))
    if dropped:
        print(f"# dropped rows ({len(dropped)}): {','.join(dropped)}")
    if added:
        print(f"# added rows ({len(added)}): {','.join(added)}")
    print(f"# {regressions} regression(s) at tolerance {tolerance:.2f}x "
          f"over {len(set(old_rows) & set(new_rows))} shared rows")
    return regressions


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", choices=("small", "full"), default="small")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows as machine-readable JSON")
    ap.add_argument("--only", metavar="SUBSTR", default=None,
                    help="run only benchmark sections whose name contains "
                         "SUBSTR (e.g. --only serve)")
    ap.add_argument("--validate", action="store_true",
                    help="set SEXTANS_CHECK=1 for the whole run (every "
                         "benchmark input is invariant-checked at plan/"
                         "dispatch time) and append validate_* overhead "
                         "rows")
    ap.add_argument("--compare", nargs=2, metavar=("OLD", "NEW"),
                    default=None,
                    help="diff two --json snapshots instead of running "
                         "benchmarks; exits 2 if any shared row regressed "
                         "beyond --tolerance")
    ap.add_argument("--tolerance", type=float, default=1.25,
                    help="regression threshold for --compare (ratio "
                         "new/old; default 1.25)")
    args, _ = ap.parse_known_args()
    if args.compare:
        import sys

        regressions = compare_snapshots(args.compare[0], args.compare[1],
                                        tolerance=args.tolerance)
        sys.exit(2 if regressions else 0)
    if args.validate:
        import os

        os.environ["SEXTANS_CHECK"] = "1"
    from repro import compile_cache

    compile_cache.enable()
    sections = [
        ("table1", bench_table1),
        ("fig7", lambda: bench_fig7(args.budget)),
        ("fig9_fig10", lambda: bench_fig9_fig10(args.budget)),
        ("hub_split", lambda: bench_hub_split(args.budget)),
        ("kernels", bench_kernels),
        ("plan", bench_plan),
        ("scheduler", bench_scheduler),
        ("serve", bench_serve),
        ("slo", bench_slo),
        ("bsr_serve", bench_bsr_serve),
        ("stream", bench_stream),
        ("skinny", bench_spmv),
        ("autotune", bench_autotune),
    ]
    if args.validate:
        sections.append(("validate", bench_validate))
    print("name,us_per_call,derived")
    for name, fn in sections:
        if args.only and args.only not in name:
            continue
        fn()
    if args.json:
        payload = {
            "schema": 1,
            "budget": args.budget,
            "rows": ROWS,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {len(ROWS)} rows to {args.json}")


if __name__ == "__main__":
    main()
