"""Helpers of the benchmark's own tests: a copy of the benchmark at tiny
sizes, and a helper that drives one run of a cell in it on the CPU."""

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (REPO, os.path.join(REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY = {
    "gap-kron": {"graph": {"scale": 11,
                           "edge_factor": 16, "a": 0.57, "b": 0.19,
                           "c": 0.19}},
    "olmo-hybrid-7b-ffn-bsr90": {"hidden_size": 256,
                                 "intermediate_size": 512,
                                 "num_hidden_layers": 2},
}
SEED = 2 ** 31 + 12345


def make_tiny_root(dst):
    """Copy ``BENCHMARK.json`` and ``bench/`` to ``dst`` and shrink every
    configuration there to a size the CPU runs in a second."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.load(open(os.path.join(dst, "BENCHMARK.json")))
    for c in spec["configs"]:
        path = os.path.join(dst, c["file"])
        cfg = json.load(open(path))
        cfg.update(TINY[c["name"]])
        if "sparsity" in cfg:
            cfg["sparsity"] = dict(cfg["sparsity"], block_sparsity=0.5)
        json.dump(cfg, open(path, "w"))
    return str(dst)


def run_cell(root, workload, *, trace=0, seconds=0.3, seed=SEED):
    """One run of ``workload`` from ``root``, past the look for a chip."""
    from bench import run as R

    args = R.parse(["--workload", workload, "--seed", str(seed),
                    "--seconds", str(seconds), "--trace", str(trace)])
    return R.run(args, root=root, check_chip=False, use_cache=False)
