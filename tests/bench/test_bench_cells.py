"""Each cell's run at a tiny size on the CPU, and a new cell,
configuration, traffic mix and metric found from files alone."""

import json
import os

import pytest

from bench_helpers import REPO, SEED, make_tiny_root, run_cell

from bench import graph

SPEC = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]


def _e2e_of(cell):
    return {m["name"] for m in SPEC["end_to_end"]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_at_tiny_size(cell, tmp_path):
    res = run_cell(make_tiny_root(tmp_path), cell)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == _e2e_of(cell)
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["check"]["window_compilations"]["value"] == 0
    assert list(res)[-1] == "check"
    for c in res["check"].values():
        assert c["value"] <= c["limit"]


def test_traced_run_reports_per_layer_metrics(tmp_path):
    cell = "kron-gcn-agg-n128"
    res = run_cell(make_tiny_root(tmp_path), cell, trace=1)
    assert res["correct"]
    names = set(res["metrics"])
    # the CPU has no device plane: trace-read metrics stay silent, the
    # program's counters are read
    assert {"slab_fill.hflex", "compile_s"} <= names
    assert not any("roofline" in n or "idle" in n for n in names)
    assert 0 < res["metrics"]["slab_fill.hflex"]["value"] <= 100
    assert "busy_s" in res["device"] and "breakdown" in res


def test_new_cell_config_traffic_and_metric_from_files_alone(tmp_path):
    root = make_tiny_root(tmp_path)
    cfg = {"name": "tiny-web", "source": "a test graph",
           "graph": {"scale": 10,
                     "edge_factor": 8, "a": 0.45, "b": 0.15, "c": 0.15}}
    json.dump(cfg, open(os.path.join(root, "bench", "configs",
                                     "tiny-web.json"), "w"))
    json.dump({"generator": "spmm_loop", "matrix": "transition", "n": 8,
               "alpha": 0.5, "beta": 2.0, "inputs": 2,
               "limits": {"scaled_err": 1e-4}},
              open(os.path.join(root, "bench", "traffic", "spmm-n8.json"),
                   "w"))
    with open(os.path.join(root, "bench", "metrics", "nnz_seen.py"),
              "w") as f:
        f.write("def read(record):\n"
                "    return float(record['counters']['nnz'])\n")
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({"name": "tiny-web", "source": "a test graph",
                            "file": "bench/configs/tiny-web.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "web-spmm-n8", "config": "tiny-web",
                              "traffic": "spmm-n8", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("web-spmm-n8")
    spec["per_layer"].append({"name": "nnz_seen", "unit": "nnz",
                              "better": "higher",
                              "source": "program_counter", "layer": "test",
                              "moves": "useful_gflops",
                              "workloads": ["web-spmm-n8"]})
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    res = run_cell(root, "web-spmm-n8")
    assert res["correct"], res["check"]
    assert "useful_gflops" in res["metrics"]
    traced = run_cell(root, "web-spmm-n8", trace=1)
    nnz = graph.matrix(cfg, "transition", SEED)[1].shape[0]
    assert traced["metrics"]["nnz_seen"]["value"] == float(nnz)
