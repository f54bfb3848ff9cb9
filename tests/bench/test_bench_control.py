"""The check must fail what it exists to catch: the control (one
precision step below the configuration's) and faults planted in the
timed path, each driven through the rest of a run on the CPU."""

import json
import os

import jax.numpy as jnp
import pytest

from bench_helpers import REPO, make_tiny_root, run_cell

SPEC = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]


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


FAULTS = {
    "kron-gcn-agg-n128": (_altered, _half),
    "olmo-ffn-decode-n32": (_altered, _half, _unchanged),
    "kron-pagerank-n1": (_altered, _unchanged),
}


@pytest.mark.parametrize("cell,fault", [(c, f) for c in CELLS
                                        for f in FAULTS[c]],
                         ids=lambda x: getattr(x, "__name__", x))
def test_planted_fault_is_not_correct(cell, fault, tmp_path, monkeypatch):
    from repro.sparse_api.plan import SpmmPlan

    run = SpmmPlan.run

    def broken(self, b, *a, **kw):
        return fault(run(self, b, *a, **kw), jnp.asarray(b))

    monkeypatch.setattr(SpmmPlan, "run", broken)
    res = run_cell(make_tiny_root(tmp_path), cell)
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, tmp_path):
    from bench import run as R

    args = R.parse(["--workload", cell, "--seed", "2147483711",
                    "--seconds", "0.3", "--trace", "0"])
    res = R.run(args, root=make_tiny_root(tmp_path), check_chip=False,
                use_cache=False, control=True)
    numbers = {k: v for k, v in res["check"].items()
               if k != "window_compilations"}
    assert numbers and any(v["value"] > v["limit"]
                           for v in numbers.values()), numbers
