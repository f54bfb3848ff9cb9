"""Helpers of the benchmark's own tests: a copy of the benchmark at tiny
sizes, a helper that drives one run of a cell in it on the CPU, and what
each cell's tests expect.

Whatever belongs to one configuration or one cell is a data file beside
this one, found by name, so that a new configuration or cell joins these
tests with new files only:

- ``tiny/<config>.json``: the keys that shrink the configuration to a size
  the CPU runs in a second (merged over its file, top-level keys);
- ``cells/<cell>.json``: ``faults``, names in ``FAULTS`` planted in the
  cell's timed path, and ``window_spans``, program spans that a traced run
  shows inside the window.  A cell without a file expects ``DEFAULT``.
"""

import json
import os
import shutil
import sys

import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for _p in (REPO, os.path.join(REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

SEED = 2 ** 31 + 12345
DEFAULT = {"faults": ["_altered"], "window_spans": []}


def _altered(out, b):
    """One answer altered where it is produced."""
    return out.at[(0,) * out.ndim].add(1.0)


def _half(out, b):
    """Half of the batch (the dense columns) left out."""
    n = out.shape[-1]
    return out.at[..., n - n // 2:].set(0.0)


def _unchanged(out, b):
    """The step returns its state unchanged: PageRank's iterate comes back
    as it went in; an FFN layer adds nothing to its residual."""
    if b.shape == out.shape:
        return b
    return jnp.zeros_like(out)


FAULTS = {f.__name__: f for f in (_altered, _half, _unchanged)}


def load_json(path):
    with open(path) as f:
        return json.load(f)


def tiny_file(config):
    return os.path.join(HERE, "tiny", config + ".json")


def expectations(cell):
    """What the tests of ``cell`` expect: ``cells/<cell>.json`` over
    ``DEFAULT``."""
    path = os.path.join(HERE, "cells", cell + ".json")
    if not os.path.exists(path):
        return dict(DEFAULT)
    return dict(DEFAULT, **load_json(path))


def make_tiny_root(dst, *cells):
    """Copy ``BENCHMARK.json`` and ``bench/`` to ``dst`` and shrink there
    the configuration of each of ``cells`` by its ``tiny/<config>.json``.
    Other configurations are left as they are: no test runs them."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = load_json(os.path.join(dst, "BENCHMARK.json"))
    files = {c["name"]: c["file"] for c in spec["configs"]}
    config_of = {w["name"]: w["config"] for w in spec["workloads"]}
    for name in sorted({config_of[c] for c in cells}):
        tiny = tiny_file(name)
        if not os.path.exists(tiny):
            raise FileNotFoundError(
                f"configuration {name!r} has no tiny size for the CPU "
                f"tests: add {os.path.relpath(tiny, REPO)}")
        path = os.path.join(dst, files[name])
        cfg = load_json(path)
        cfg.update(load_json(tiny))
        if "sparsity" in cfg:
            cfg["sparsity"] = dict(cfg["sparsity"], block_sparsity=0.5)
        with open(path, "w") as f:
            json.dump(cfg, f)
    return str(dst)


def run_cell(root, workload, *, trace=0, seconds=0.3, seed=SEED):
    """One run of ``workload`` from ``root``, past the look for a chip."""
    from bench import run as R

    args = R.parse(["--workload", workload, "--seed", str(seed),
                    "--seconds", str(seconds), "--trace", str(trace)])
    return R.run(args, root=root, check_chip=False, use_cache=False)
