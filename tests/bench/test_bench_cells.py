"""Each cell's run at a tiny size on the CPU, and a new cell,
configuration, traffic mix and metric found from files alone, by the
harness and by these tests."""

import json
import os
import shutil
import subprocess
import sys

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
    res = run_cell(make_tiny_root(tmp_path, cell), cell)
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
    res = run_cell(make_tiny_root(tmp_path, cell), cell, trace=1)
    assert res["correct"]
    names = set(res["metrics"])
    # the CPU has no device plane: trace-read metrics stay silent, the
    # program's counters are read
    assert {"slab_fill.hflex", "compile_s"} <= names
    assert not any("roofline" in n or "idle" in n for n in names)
    assert 0 < res["metrics"]["slab_fill.hflex"]["value"] <= 100
    assert "busy_s" in res["device"] and "breakdown" in res


WEB = {"name": "tiny-web", "source": "a test graph",
       "graph": {"scale": 10,
                 "edge_factor": 8, "a": 0.45, "b": 0.15, "c": 0.15}}


def _add_web(root, cells):
    """Add the configuration ``tiny-web``, the traffic mix ``spmm-n8`` and
    ``cells`` (``{cell: traffic}``) on that configuration to the benchmark
    at ``root``: new files, and entries appended to ``BENCHMARK.json``."""
    with open(os.path.join(root, "bench", "configs", "tiny-web.json"),
              "w") as f:
        json.dump(WEB, f)
    with open(os.path.join(root, "bench", "traffic", "spmm-n8.json"),
              "w") as f:
        json.dump({"generator": "spmm_loop", "matrix": "transition", "n": 8,
                   "alpha": 0.5, "beta": 2.0, "inputs": 2,
                   "limits": {"scaled_err": 1e-4}}, f)
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({"name": "tiny-web", "source": "a test graph",
                            "file": "bench/configs/tiny-web.json",
                            "reduced": [], "why": "test"})
    for cell, traffic in cells.items():
        spec["workloads"].append({"name": cell, "config": "tiny-web",
                                  "traffic": traffic, "chips": 1,
                                  "why": "test"})
        for m in spec["end_to_end"]:
            if "workloads" in m:
                m["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)


def test_new_cell_config_traffic_and_metric_from_files_alone(tmp_path):
    root = make_tiny_root(tmp_path)
    _add_web(root, {"web-spmm-n8": "spmm-n8"})
    with open(os.path.join(root, "bench", "metrics", "nnz_seen.py"),
              "w") as f:
        f.write("def read(record):\n"
                "    return float(record['counters']['nnz'])\n")
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
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
    nnz = graph.matrix(WEB, "transition", SEED)[1].shape[0]
    assert traced["metrics"]["nnz_seen"]["value"] == float(nnz)


def test_new_config_and_cells_join_these_tests_with_new_files_only(
        tmp_path):
    """In a copy of the benchmark, a configuration with its tiny size and
    two cells on it, one with a ``cells/`` file and one without, added
    with new files and appended entries only: the benchmark's tests
    collect every case of the new cells, and the tiny run of the one
    without a file passes."""
    root = tmp_path / "copy"
    tests = root / "tests" / "bench"
    shutil.copytree(os.path.join(REPO, "tests", "bench"), tests,
                    ignore=shutil.ignore_patterns("__pycache__"))
    make_tiny_root(root)
    os.symlink(os.path.join(REPO, "src"), root / "src")
    (root / "pytest.ini").write_text("[pytest]\n")
    _add_web(str(root), {"web-spmm-n8": "spmm-n8",
                         "web-pagerank-n1": "pagerank-n1"})
    (tests / "tiny" / "tiny-web.json").write_text(
        json.dumps({"graph": dict(WEB["graph"], scale=9)}))
    (tests / "cells" / "web-pagerank-n1.json").write_text(
        json.dumps({"faults": ["_altered", "_unchanged"],
                    "window_spans": ["sextans.engine.spmm"]}))

    def pytest_in_copy(*args):
        return subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
             "-p", "no:randomly", *args], cwd=root, capture_output=True,
            text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))

    p = pytest_in_copy("--collect-only", "tests/bench")
    assert p.returncode == 0 and "error" not in p.stdout.splitlines()[-1], (
        p.stdout[-4000:] + p.stderr[-4000:])
    ids = set(p.stdout.split())
    new = ["test_bench_cells.py::test_cell_runs_correct_at_tiny_size[{c}]",
           "test_bench_control.py::test_control_is_not_correct[{c}]",
           "test_bench_program_spans.py::"
           "test_traced_run_reads_the_program_spans[{c}]"]
    want = [f"tests/bench/{t.format(c=c)}" for t in new
            for c in ("web-spmm-n8", "web-pagerank-n1")]
    want += [f"tests/bench/test_bench_control.py::"
             f"test_planted_fault_is_not_correct[{c}]" for c in (
                 "web-spmm-n8-_altered", "web-pagerank-n1-_altered",
                 "web-pagerank-n1-_unchanged")]
    assert not [w for w in want if w not in ids], p.stdout[-4000:]
    assert not any("web-spmm-n8-_half" in i for i in ids)

    p = pytest_in_copy("tests/bench/test_bench_cells.py::"
                       "test_cell_runs_correct_at_tiny_size[web-spmm-n8]")
    assert p.returncode == 0 and "1 passed" in p.stdout, (
        p.stdout[-4000:] + p.stderr[-4000:])
