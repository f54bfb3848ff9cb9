"""HLO loop-aware analysis (the dry-run profiler) — exactness tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.hloparse import analyze, parse_module
from repro.launch.mesh import make_mesh_for


def _compile(fn, *specs, **jkw):
    return jax.jit(fn, **jkw).lower(*specs).compile()


def test_scan_trip_count_flops():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    c = _compile(f, jax.ShapeDtypeStruct((8, 64), jnp.float32),
                 jax.ShapeDtypeStruct((64, 64), jnp.float32))
    r = analyze(c.as_text())
    assert r["flops"] == 7 * 2 * 8 * 64 * 64
    # cost_analysis counts the body once — we must exceed it
    assert r["flops"] > c.cost_analysis()["flops"]


def test_nested_scan():
    def f(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c3, _ = jax.lax.scan(inner, c, None, length=3)
            return c3, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    c = _compile(f, jax.ShapeDtypeStruct((8, 64), jnp.float32),
                 jax.ShapeDtypeStruct((64, 64), jnp.float32))
    assert analyze(c.as_text())["flops"] == 15 * 2 * 8 * 64 * 64


def test_sharded_collectives_counted():
    mesh = make_mesh_for(4, model_parallel=2)

    def g(x, w):
        return (x @ w).sum(axis=1)

    jf = jax.jit(g, in_shardings=(NamedSharding(mesh, P("data", "model")),
                                  NamedSharding(mesh, P("model", None))),
                 out_shardings=NamedSharding(mesh, P("data")))
    c = jf.lower(jax.ShapeDtypeStruct((256, 512), jnp.float32),
                 jax.ShapeDtypeStruct((512, 128), jnp.float32)).compile()
    r = analyze(c.as_text())
    assert r["flops"] == 2 * 128 * 256 * 128        # per-device program
    coll = r["collectives"]
    assert coll.counts.get("all-reduce", 0) >= 1
    assert coll.wire_bytes > 0


def test_collectives_inside_scan_multiplied():
    mesh = make_mesh_for(4, model_parallel=2)

    def f(x, w):
        def body(c, _):
            y = c @ w
            return y, None
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out

    jf = jax.jit(f, in_shardings=(NamedSharding(mesh, P("data", None)),
                                  NamedSharding(mesh, P(None, "model"))),
                 out_shardings=NamedSharding(mesh, P("data", None)))
    c = jf.lower(jax.ShapeDtypeStruct((64, 128), jnp.float32),
                 jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
    r = analyze(c.as_text())
    # whatever collective the partitioner chose, it must be x5
    if r["collectives"].counts:
        per_op = list(r["collectives"].bytes_by_op.values())[0]
        assert per_op > 0
    # the partitioner may shard the dot (x64 output) or all-gather w and
    # keep the full output (x128) — both are x5 trip-counted
    assert r["flops"] in (5 * 2 * 16 * 128 * 64, 5 * 2 * 16 * 128 * 128,
                          5 * 2 * 64 * 128 * 64)


def test_hbm_bytes_positive_and_loop_scaled():
    def f(x):
        def body(c, _):
            return jnp.tanh(c) * 2.0, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    c1 = _compile(f, jax.ShapeDtypeStruct((1024, 1024), jnp.float32))
    r1 = analyze(c1.as_text())
    assert r1["hbm_bytes"] >= 10 * 1024 * 1024 * 4  # at least trip-scaled


def test_loop_invariant_weights_charged_once():
    """A weight matrix re-used every scan step is loop-invariant: HBM bytes
    must scale ~O(1) in trip count, not O(T) (it stays resident on TPU)."""
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=100)
        return y

    xs = jax.ShapeDtypeStruct((8, 512), jnp.float32)
    ws = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    c = _compile(f, xs, ws)
    r = analyze(c.as_text())
    w_bytes = 512 * 512 * 4
    # all per-trip traffic is O(x) = 8*512*4 = 16KB; with the weight charged
    # per trip this would exceed 100 * 1MB = 100MB
    assert r["hbm_bytes"] < 30 * w_bytes, r["hbm_bytes"]
    assert r["flops"] == 100 * 2 * 8 * 512 * 512


def test_iota_replica_group_cross_pod_decode():
    """Exact decode of iota replica groups incl. transpose specs: groups
    spanning the pod boundary (id >= 256) must be flagged."""
    from repro.launch.roofline import _group_size_and_crosspod

    # contiguous within-pod groups: [32,16]<=[512] -> ids 0..15 etc: no cross
    size, cross = _group_size_and_crosspod(
        "replica_groups=[32,16]<=[512]", pod_boundary=256)
    assert size == 16 and not cross
    # (pod,data) groups on a (2,16,16) mesh: transpose puts pod inside the
    # group -> ids {m, 16+m, ..., 256+m, ...}: crosses
    size, cross = _group_size_and_crosspod(
        "replica_groups=[16,32]<=[2,16,16]T(2,0,1)", pod_boundary=256)
    assert size == 32 and cross
    # pure model-axis groups (fastest axis): no cross
    size, cross = _group_size_and_crosspod(
        "replica_groups=[32,16]<=[2,16,16]T(0,1,2)", pod_boundary=256)
    assert size == 16 and not cross
