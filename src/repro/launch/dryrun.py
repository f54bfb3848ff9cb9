import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The lines above MUST run before any jax import (jax locks the device
count and platform at first init); they are intentionally before the
module docstring consumers and all other imports.  The dry-run is a
CPU-only tool: it never takes an accelerator, so it can run beside a
process that holds the chip.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --jobs 2
  python -m repro.launch.dryrun --report

Each cell writes out/dryrun/<arch>__<shape>__<mesh>.json with
memory_analysis, cost_analysis, collective stats, and roofline terms.
--all orchestrates one subprocess per cell (isolation + parallelism).
"""

import argparse
import json
import pathlib
import subprocess
import sys
import time
import traceback

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "out" / "dryrun"


def run_cell(arch: str, shape: str, mesh_kind: str, extra: dict | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import SHAPES, get_config, input_specs, shape_applicable
    from repro.distributed import steps as S
    from repro.distributed.sharding import batch_specs, cache_specs, tree_named
    from repro.launch import roofline as R
    from repro.launch.mesh import make_production_mesh
    from repro.optim.adamw import AdamWConfig

    t0 = time.time()
    cfg = get_config(arch)
    # perf-lever overrides (hillclimb runs; see EXPERIMENTS.md §Perf)
    import dataclasses as _dc
    levers = {}
    if os.environ.get("REPRO_ATTN_SKIP") == "1":
        levers["attn_skip_masked_blocks"] = True
    if os.environ.get("REPRO_REMAT"):
        levers["remat_policy"] = os.environ["REPRO_REMAT"]
    if os.environ.get("REPRO_MOE_GROUP"):
        levers["moe_group_size"] = int(os.environ["REPRO_MOE_GROUP"])
    if os.environ.get("REPRO_ATTN_CK"):
        levers["attn_chunk_k"] = int(os.environ["REPRO_ATTN_CK"])
    if os.environ.get("REPRO_ATTN_CQ"):
        levers["attn_chunk_q"] = int(os.environ["REPRO_ATTN_CQ"])
    if os.environ.get("REPRO_MLSTM_CHUNK"):
        levers["mlstm_chunk"] = int(os.environ["REPRO_MLSTM_CHUNK"])
    if os.environ.get("REPRO_SP_ATTN") == "1":
        levers["sp_attention"] = True
    if os.environ.get("REPRO_PROBS_BF16") == "1":
        levers["attn_probs_bf16"] = True
    if levers:
        cfg = _dc.replace(cfg, **levers)
    embed_d_shard = os.environ.get("REPRO_EMBED_DSHARD") == "1"
    if extra is None and (levers or embed_d_shard):
        extra = {}
    if levers or embed_d_shard:
        extra["levers"] = {**levers, "embed_d_shard": embed_d_shard}
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "mesh": mesh_kind,
                "status": "skipped", "reason": why}

    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    n_chips = mesh.size
    pod_boundary = n_chips // 2 if multi else None
    seq, gbs, kind = SHAPES[shape]
    specs = input_specs(cfg, shape)

    if kind == "train":
        # production numerics at scale: bf16 params, fp32 moments, no extra
        # master copy (m/v are the fp32 reference); microbatching sized so
        # big-model activations fit HBM.
        opt = AdamWConfig(master_fp32=False)
        micro = (16 if cfg.param_count() > 1e11 else
                 8 if cfg.param_count() > 3e10 else
                 4 if cfg.param_count() > 5e9 else 1)
        # each microbatch must still cover the data axes, or the partitioner
        # replicates compute across the uncovered shards
        dsize = 1
        for ax in ("pod", "data"):
            dsize *= mesh.shape.get(ax, 1)
        micro = min(micro, max(1, gbs // dsize))
        if os.environ.get("REPRO_MICRO"):
            micro = int(os.environ["REPRO_MICRO"])
        jit_for, _, sshape = S.build_train_step(cfg, mesh, opt, donate=True,
                                                micro_steps=micro,
                                                embed_d_shard=embed_d_shard)
        fn = jit_for(specs["batch"])
        lowered = fn.lower(sshape, specs["batch"])
    elif kind == "prefill":
        jit_for, _, pshape = S.build_prefill_step(cfg, mesh,
                                                  embed_d_shard=embed_d_shard)
        fn = jit_for(specs["batch"])
        lowered = fn.lower(pshape, specs["batch"])
    else:  # decode
        jit_for, _, pshape = S.build_decode_step(cfg, mesh, donate=True,
                                                 embed_d_shard=embed_d_shard)
        fn = jit_for(specs["cache"], specs["tokens"])
        lowered = fn.lower(pshape, specs["cache"], specs["tokens"])

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    # trip-count-aware reconstruction (cost_analysis counts loop bodies once)
    from repro.launch import hloparse
    hp = hloparse.analyze(hlo, pod_boundary=pod_boundary)
    coll = hp["collectives"]
    if extra and extra.get("attribute"):
        scopes = hloparse.attribute_by_scope(hlo)
        extra = dict(extra)
        extra["scopes"] = {
            k: {"flops": v["flops"], "bytes": v["bytes"]}
            for k, v in sorted(scopes.items(),
                               key=lambda kv: -kv[1]["bytes"])}

    # MODEL_FLOPS per chip: 6·N_active·D train, 2·N_active·D decode/prefill-fwd
    n_active = cfg.active_param_count()
    tokens = gbs * (seq if kind in ("train", "prefill") else 1)
    factor = 6 if kind == "train" else 2
    model_flops_chip = factor * n_active * tokens / n_chips

    flops = float(hp["flops"])
    bytes_acc = float(hp["hbm_bytes"])
    terms = R.roofline_terms(flops, bytes_acc, coll, model_flops_chip)

    result = {
        "arch": arch, "shape": shape, "mesh": mesh_kind, "status": "ok",
        "chips": n_chips,
        "seq": seq, "global_batch": gbs, "kind": kind,
        "params_total": cfg.param_count(),
        "params_active": n_active,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "cost": {
            "flops": flops, "bytes_accessed": bytes_acc,
            "flops_body_once": float(ca.get("flops", 0.0)),
            "bytes_body_once": float(ca.get("bytes accessed", 0.0)),
            "flops_top_computations": hp["flops_top_computations"],
        },
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes_per_device": ma.argument_size_in_bytes
            + ma.output_size_in_bytes + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes,
        },
        "collectives": {
            "wire_bytes": coll.wire_bytes,
            "cross_pod_bytes": coll.cross_pod_bytes,
            "counts": coll.counts,
            "bytes_by_op": coll.bytes_by_op,
        },
        "roofline": terms,
    }
    if extra:
        result.update(extra)
    return result


def cell_path(arch: str, shape: str, mesh_kind: str) -> pathlib.Path:
    safe = arch.replace("/", "_")
    suffix = os.environ.get("REPRO_OUT_SUFFIX", "")
    return OUT_DIR / f"{safe}__{shape}__{mesh_kind}{suffix}.json"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--report", action="store_true")
    ap.add_argument("--attribute", action="store_true",
                    help="include per-source-scope flops/bytes attribution")
    args = ap.parse_args()

    OUT_DIR.mkdir(parents=True, exist_ok=True)

    if args.report:
        return report()

    if args.all:
        return orchestrate(args)

    assert args.arch and args.shape and args.mesh in ("single", "multi")
    path = cell_path(args.arch, args.shape, args.mesh)
    try:
        res = run_cell(args.arch, args.shape, args.mesh,
                       extra={"attribute": True} if args.attribute else None)
    except Exception as e:  # recorded, non-zero exit
        res = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
        path.write_text(json.dumps(res, indent=2))
        print(json.dumps({k: res[k] for k in ("arch", "shape", "mesh", "status", "error")}))
        return 1
    path.write_text(json.dumps(res, indent=2))
    brief = {k: res.get(k) for k in ("arch", "shape", "mesh", "status")}
    if res["status"] == "ok":
        brief["dominant"] = res["roofline"]["dominant"]
        brief["compile_s"] = res["compile_s"]
    print(json.dumps(brief))
    return 0


def orchestrate(args) -> int:
    from repro.configs import ARCH_NAMES, SHAPES

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = [(a, s, m) for a in ARCH_NAMES for s in SHAPES for m in meshes]
    todo = [c for c in cells
            if args.force or not cell_path(*c).exists()]
    print(f"{len(todo)}/{len(cells)} cells to run, jobs={args.jobs}", flush=True)
    procs: list = []
    failed = []
    while todo or procs:
        while todo and len(procs) < args.jobs:
            a, s, m = todo.pop(0)
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", a, "--shape", s, "--mesh", m]
            p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                 stderr=subprocess.STDOUT, text=True)
            procs.append(((a, s, m), p, time.time()))
            print(f"[start] {a} {s} {m}", flush=True)
        for item in list(procs):
            (a, s, m), p, t0 = item
            if p.poll() is None:
                continue
            procs.remove(item)
            out = (p.stdout.read() or "").strip().splitlines()
            tail = out[-1] if out else ""
            status = "ok" if p.returncode == 0 else "FAIL"
            if p.returncode != 0:
                failed.append((a, s, m))
            print(f"[{status}] {a} {s} {m} ({time.time()-t0:.0f}s) {tail[:200]}",
                  flush=True)
        time.sleep(2)
    print(f"done; {len(failed)} failures: {failed}", flush=True)
    return 1 if failed else 0


def report() -> int:
    rows = []
    for f in sorted(OUT_DIR.glob("*.json")):
        d = json.loads(f.read_text())
        rows.append(d)
    ok = [r for r in rows if r.get("status") == "ok"]
    sk = [r for r in rows if r.get("status") == "skipped"]
    er = [r for r in rows if r.get("status") == "error"]
    print(f"cells: {len(rows)} ok={len(ok)} skipped={len(sk)} error={len(er)}")
    fmt = ("{arch:24s} {shape:12s} {mesh:6s} {dom:10s} "
           "c={c:9.2e} m={m:9.2e} n={n:9.2e} useful={u:5.2f} mem={gb:6.1f}GB")
    for r in ok:
        t = r["roofline"]
        print(fmt.format(arch=r["arch"], shape=r["shape"], mesh=r["mesh"],
                         dom=t["dominant"], c=t["compute_s"], m=t["memory_s"],
                         n=t["collective_s"], u=t["useful_flops_ratio"],
                         gb=r["memory"]["peak_bytes_per_device"] / 2**30))
    for r in sk:
        print(f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:6s} SKIPPED: {r['reason']}")
    for r in er:
        print(f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:6s} ERROR: {r['error'][:160]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
