"""The check must fail what it exists to catch: the control (one
precision step below the configuration's) and faults planted in the
timed path, each driven through the rest of a run on the CPU."""

import json
import os

import jax.numpy as jnp
import pytest

from bench_helpers import (FAULTS, REPO, expectations, make_tiny_root,
                           run_cell)

SPEC = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("cell,fault", [(c, f) for c in CELLS
                                        for f in expectations(c)["faults"]])
def test_planted_fault_is_not_correct(cell, fault, tmp_path, monkeypatch):
    from repro.sparse_api.plan import SpmmPlan

    run, plant = SpmmPlan.run, FAULTS[fault]

    def broken(self, b, *a, **kw):
        return plant(run(self, b, *a, **kw), jnp.asarray(b))

    monkeypatch.setattr(SpmmPlan, "run", broken)
    res = run_cell(make_tiny_root(tmp_path, cell), cell)
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, tmp_path):
    from bench import run as R

    args = R.parse(["--workload", cell, "--seed", "2147483711",
                    "--seconds", "0.3", "--trace", "0"])
    res = R.run(args, root=make_tiny_root(tmp_path, cell), check_chip=False,
                use_cache=False, control=True)
    numbers = {k: v for k, v in res["check"].items()
               if k != "window_compilations"}
    assert numbers and any(v["value"] > v["limit"]
                           for v in numbers.values()), numbers
