"""Unit tests of the benchmark's yardstick: trace reduction, generators,
useful-work arithmetic, discovery by file name, and file names that git
keeps."""

import fnmatch
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench_helpers import DEFAULT, FAULTS, HERE, REPO, load_json, tiny_file

from bench import ffn, graph, reference, trace, work

# -- trace reduction ---------------------------------------------------------


def test_trace_load_and_reduce_on_a_recorded_trace(tmp_path):
    """Record a short trace with the harness's spans (on the CPU: no
    device plane) and reduce it as a traced run does."""
    import jax
    import jax.numpy as jnp

    from bench.loop import span

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with span("bench.window"):
        for _ in range(3):
            with span("bench.dispatch"):
                y = f(x)
            with span("bench.sync"):
                y.block_until_ready()
    jax.profiler.stop_trace()
    t = trace.load(trace.find_xplane(str(tmp_path)))
    json.dumps(t)                                   # storable as JSON
    names = [n for n, _, _ in t["spans"]]
    assert names.count("bench.window") == 1
    assert names.count("bench.dispatch") == 3 and names.count(
        "bench.sync") == 3
    t0, t1 = trace.window(t)
    assert all(t0 <= s and s + d <= t1 for n, s, d in t["spans"]
               if n != "bench.window")
    r = trace.reduce(t)
    assert r["window_s"] == pytest.approx((t1 - t0) / 1e9)
    if not t["devices"]:                     # the CPU has no device plane
        assert r["busy_s"] is None and r["device_ops"] == []


def test_trace_reduction_by_hand():
    ms = 1_000_000
    t = {"devices": {"/device:TPU:0": [["k", 1 * ms, 2 * ms],
                                       ["k", 2 * ms, 2 * ms],
                                       ["e", 8 * ms, 1 * ms],
                                       ["late", 20 * ms, 5 * ms]]},
         "spans": [["bench.window", 0, 10 * ms],
                   ["bench.dispatch", 0, 5 * ms],
                   ["bench.sync", 5 * ms, 5 * ms]]}
    r = trace.reduce(t)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.004)          # [1,4] + [8,9]
    assert dict(r["device_ops"]) == pytest.approx({"k": 0.004, "e": 0.001})
    # gaps [0,1] in dispatch, [4,8] mid 6 in sync, [9,10] in sync
    assert dict(r["idle_gaps"]) == pytest.approx({"dispatch": 0.001,
                                                  "sync": 0.005})


def test_trace_without_device_ops_reads_nothing():
    t = {"devices": {}, "spans": [["bench.window", 0, 1000]]}
    r = trace.reduce(t)
    assert r["busy_s"] is None and r["idle_gaps"] == []


# -- generators --------------------------------------------------------------


def _kron():
    with open(os.path.join(REPO, "bench", "configs", "gap-kron.json")) as f:
        return json.load(f)


def _olmo():
    with open(os.path.join(REPO, "bench", "configs",
                           "olmo-hybrid-7b-ffn-bsr90.json")) as f:
        return json.load(f)


def test_kron_config_keeps_the_published_generator():
    g = _kron()["graph"]
    assert (g["edge_factor"], g["a"], g["b"], g["c"]) == (16, 0.57, 0.19,
                                                          0.19)
    assert g["scale"] <= 27                    # GAP's kron is scale 27


def test_kronecker_generator_counts_law_and_repeats():
    scale, ef = 10, 16
    u, v = graph.kronecker_edges(scale, ef, 0.57, 0.19, 0.19, 2 ** 31 + 5)
    assert u.shape == v.shape == (ef << scale,)
    assert u.min() >= 0 and max(u.max(), v.max()) < 1 << scale
    u2, v2 = graph.kronecker_edges(scale, ef, 0.57, 0.19, 0.19, 2 ** 31 + 5)
    np.testing.assert_array_equal(u, u2)
    np.testing.assert_array_equal(v, v2)
    u3, _ = graph.kronecker_edges(scale, ef, 0.57, 0.19, 0.19, 2 ** 31 + 6)
    assert not np.array_equal(u, u3)
    # the top vertex of the unpermuted law draws (a + b) ** scale of the
    # sources; after the id permutation it is not vertex 0
    deg = np.bincount(u, minlength=1 << scale)
    hub = int(np.argmax(deg))
    assert hub != 0
    expect = (0.57 + 0.19) ** scale * u.size
    assert abs(deg[hub] - expect) < 0.15 * expect


def test_undirected_build_drops_loops_and_repeats():
    u = np.array([0, 1, 1, 2, 3, 3])
    v = np.array([1, 0, 1, 3, 2, 0])
    src, dst = graph.undirected(4, u, v)
    assert sorted(zip(src.tolist(), dst.tolist())) == [
        (0, 1), (0, 3), (1, 0), (2, 3), (3, 0), (3, 2)]


@pytest.mark.parametrize("kind", sorted(graph.MATRICES))
def test_graph_matrices_repeat_by_seed(kind):
    cfg = {"graph": {"scale": 11,
                     "edge_factor": 8, "a": 0.57, "b": 0.19, "c": 0.19}}
    a = graph.matrix(cfg, kind, 7)
    b = graph.matrix(cfg, kind, 7)
    c = graph.matrix(cfg, kind, 8)
    for x, y in zip(a[1:], b[1:]):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[1], c[1])
    shape, row, col, val = a
    nodes, src, dst = graph.edges(cfg, 7)
    if kind == "transition":
        assert row.shape == src.shape
        sums = np.bincount(col, weights=val, minlength=nodes)
        outdeg = np.bincount(col, minlength=nodes)
        np.testing.assert_allclose(sums[outdeg > 0], 1.0, rtol=1e-6)
    else:                                    # symmetric, self-loops kept
        keys = set(zip(row.tolist(), col.tolist()))
        assert all((j, i) in keys for i, j in keys)
        assert all((i, i) in keys for i in range(nodes))
        assert row.shape == (src.shape[0] + nodes,)


def test_ffn_counts_match_the_published_widths():
    cfg = _olmo()
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_hidden_layers"]) == (3840, 11008, 32)
    assert ffn.kept_blocks(cfg) == {"gate": 258, "up": 258, "down": 258}
    total = 32 * 3 * 258 * 128 * 128
    assert total == 405_798_912
    for name in ffn.MATS:
        a = ffn.pattern(cfg, 11, 3, name)
        b = ffn.pattern(cfg, 11, 3, name)
        c = ffn.pattern(cfg, 12, 3, name)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])
        assert not (np.array_equal(a[0], c[0]) and np.array_equal(a[1],
                                                                  c[1]))
        keys = a[0].astype(np.int64) * 1000 + a[1]
        assert np.unique(keys).shape == (258,)


def test_ffn_control_split_rounds_on_the_bits_under_jit():
    """The FFN control's ``HIGH`` split gives the graph control's
    round-to-nearest-even parts bit for bit under ``jit``, and holds no
    round trip through bfloat16: on the TPU, ``jit`` folds that away and
    leaves the low part zero, the control one bfloat16 pass."""
    import jax

    rng = np.random.default_rng(3)
    x = (rng.standard_normal(4096)
         * 10.0 ** rng.integers(-30, 30, 4096)).astype(np.float32)
    split = jax.jit(ffn._split)
    hi, lo = split(x)
    want_hi, want_lo = reference._split_bf16(x)
    np.testing.assert_array_equal(np.asarray(hi), want_hi)
    np.testing.assert_array_equal(np.asarray(lo), want_lo)
    assert np.count_nonzero(want_lo) > 4000
    assert "bf16" not in split.lower(x).as_text()


# -- useful work -------------------------------------------------------------


def test_useful_work_ignores_the_packing():
    from repro.core.sparse import SparseMatrix
    from repro.sparse_api import from_sparse_matrix

    cfg = {"graph": {"scale": 11,
                     "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19}}
    coo = graph.matrix(cfg, "gcn", 3)
    nnz = coo[1].shape[0]
    counted, slots = set(), set()
    for tm, k0 in ((128, 4096), (64, 1024), (32, 512)):
        t = from_sparse_matrix(SparseMatrix(*coo), tm=tm, k0=k0,
                               device=False)
        counted.add(work.csr_spmm(*coo[0], int(t.nnz), 128))
        slots.add(int(np.prod(t.data.vals.shape)))
    assert counted == {work.csr_spmm(*coo[0], nnz, 128)}
    assert len(slots) > 1                 # the packings really differ
    w = work.csr_spmm(*coo[0], nnz, 128)
    assert w.flops == 2 * nnz * 128
    assert w.bytes == nnz * 8 + 4 * 2049 + 2 * 2048 * 128 * 4


def test_roofline_time_and_peaks():
    p = work.peaks("TPU v5 lite")
    assert p == {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                 "hbm_bytes": 16e9}
    with pytest.raises(KeyError):
        work.peaks("TPU v99")
    w = work.bsr_spmm(11008, 3840, 258, 128, 128, 32)
    assert w.flops == 2 * 258 * 128 * 128 * 32
    assert w.roofline_s(p) == pytest.approx(w.bytes / 819e9)
    assert (w * 2).bytes == 2 * w.bytes and (w + w).flops == 2 * w.flops


# -- files ---------------------------------------------------------------------


def _ignore_patterns():
    with open(os.path.join(REPO, ".gitignore")) as f:
        return [ln.strip() for ln in f if ln.strip()
                and not ln.startswith("#")]


def test_benchmark_files_are_not_ignored_by_git():
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    pats = _ignore_patterns()
    files = []
    for p in spec["paths"]:
        for d, dirs, fs in os.walk(os.path.join(REPO, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            files += [os.path.relpath(os.path.join(d, f), REPO) for f in fs
                      if not f.endswith(".pyc")]
    assert files
    for rel in files + ["BENCHMARK.json"]:
        parts = rel.split(os.sep)
        for pat in pats:
            pat = pat.rstrip("/")
            assert not any(fnmatch.fnmatchcase(x, pat) for x in parts), (
                f"{rel} is matched by .gitignore pattern {pat!r}")


def test_every_named_file_exists():
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    names = {w["traffic"] for w in spec["workloads"]}
    for t in names:
        tr = json.load(open(os.path.join(REPO, "bench", "traffic",
                                         t + ".json")))
        assert os.path.exists(os.path.join(REPO, "bench", "generators",
                                           tr["generator"] + ".py"))
    for m in spec["per_layer"]:
        assert os.path.exists(os.path.join(REPO, "bench", "metrics",
                                           m["name"] + ".py"))
    for c in spec["configs"]:
        assert os.path.exists(os.path.join(REPO, c["file"]))


def test_every_config_has_a_tiny_size_and_every_cell_file_a_cell():
    spec = load_json(os.path.join(REPO, "BENCHMARK.json"))
    configs = {c["name"] for c in spec["configs"]}
    assert {n for n in configs if not os.path.exists(tiny_file(n))} == set()
    tiny = {os.path.splitext(f)[0]
            for f in os.listdir(os.path.join(HERE, "tiny"))}
    assert tiny <= configs, tiny - configs
    cells = {w["name"] for w in spec["workloads"]}
    for f in sorted(os.listdir(os.path.join(HERE, "cells"))):
        name, ext = os.path.splitext(f)
        assert ext == ".json" and name in cells, f
        exp = load_json(os.path.join(HERE, "cells", f))
        assert set(exp) <= set(DEFAULT), (f, sorted(exp))
        assert set(exp.get("faults", [])) <= set(FAULTS), (f, exp)
        assert all(s.startswith("sextans.")
                   for s in exp.get("window_spans", [])), (f, exp)


def test_without_a_tpu_the_command_prints_no_result():
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         spec["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr
