"""The span recorder (``repro.tracing``) and the spans at the program's
layer boundaries: pack, plan build, engine call, layers, plan run."""

import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro import tracing
from repro.tracing import span


def _change(before, name):
    after = tracing.totals().get(name, {"count": 0, "wall_s": 0.0,
                                        "self_s": 0.0})
    b = before.get(name, {"count": 0, "wall_s": 0.0, "self_s": 0.0})
    return {k: after[k] - b[k] for k in after}


# -- the recorder ------------------------------------------------------------


def test_nesting_and_self_time():
    t0 = tracing.totals()
    with span("test.outer") as outer:
        time.sleep(0.01)
        with span("test.inner") as inner:
            time.sleep(0.02)
    o, i = _change(t0, "test.outer"), _change(t0, "test.inner")
    assert o["count"] == i["count"] == 1
    assert o["wall_s"] == pytest.approx(outer.wall_s)
    assert i["wall_s"] == pytest.approx(inner.wall_s)
    assert inner.wall_s >= 0.02 and outer.wall_s >= 0.03
    assert o["self_s"] == pytest.approx(outer.wall_s - inner.wall_s)
    assert i["self_s"] == pytest.approx(i["wall_s"])


def test_a_span_on_another_thread_is_no_child():
    t0 = tracing.totals()

    def work():
        with span("test.thread"):
            time.sleep(0.02)

    with span("test.main") as main:
        th = threading.Thread(target=work)
        th.start()
        th.join()
    m = _change(t0, "test.main")
    assert _change(t0, "test.thread")["count"] == 1
    assert m["self_s"] == pytest.approx(main.wall_s)     # nothing taken off


def test_totals_is_a_copy():
    with span("test.copy"):
        pass
    snap = tracing.totals()
    snap["test.copy"]["count"] = -5
    snap["test.invented"] = {"count": 1, "wall_s": 1.0, "self_s": 1.0}
    again = tracing.totals()
    assert again["test.copy"]["count"] >= 1
    assert "test.invented" not in again


def test_an_exception_closes_the_span():
    t0 = tracing.totals()
    with pytest.raises(ZeroDivisionError):
        with span("test.raises"):
            with span("test.raises.inner"):
                1 / 0
    assert _change(t0, "test.raises")["count"] == 1
    assert _change(t0, "test.raises.inner")["count"] == 1
    # the thread's stack is back at the top: a new span has no parent
    with span("test.after") as after:
        time.sleep(0.005)
    a = _change(t0, "test.after")
    assert a["self_s"] == pytest.approx(after.wall_s)
    assert _change(t0, "test.raises")["self_s"] == pytest.approx(
        _change(t0, "test.raises")["wall_s"]
        - _change(t0, "test.raises.inner")["wall_s"])


def test_a_span_opens_a_profiler_annotation(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    with span("sextans.test"):
        with span("sextans.test.inner"):
            pass
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    names = [e.name for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for ln in p.lines
             for e in ln.events]
    assert names.count("sextans.test") == 1
    assert names.count("sextans.test.inner") == 1


# -- the program's boundaries -------------------------------------------------


def test_engine_spans_pack_build_call_and_run(rng):
    from repro.core.engine import SextansEngine
    from repro.core.sparse import power_law_sparse

    eng = SextansEngine(tm=64, k0=64, chunk=8, impl="jnp")
    a = power_law_sparse(128, 160, 5, seed=3)
    b = jnp.asarray(rng.standard_normal((160, 8)), jnp.float32)
    t0 = tracing.totals()
    t = eng.pack(a)
    eng.spmm(t, b)
    eng.spmm(t, b).block_until_ready()
    assert _change(t0, "sextans.pack")["count"] == 1
    assert _change(t0, "sextans.engine.spmm")["count"] == 2
    assert _change(t0, "sextans.plan.run")["count"] == 2
    build = _change(t0, "sextans.plan.build")
    assert build["count"] == 1                 # the second call hits the cache
    # the engine's build seconds are the span's seconds
    st = eng.stats_snapshot()
    assert st.plan_build_cold_s + st.plan_build_warm_s == pytest.approx(
        build["wall_s"])
    # engine.spmm's self time excludes the plan build and run inside it
    e = _change(t0, "sextans.engine.spmm")
    assert e["self_s"] < e["wall_s"]


def test_layer_spans_nest_group_stack_values_and_run(rng):
    from repro.models.common import Initializer
    from repro.models.layers import SparseLinear, SparseLinearGroup

    t0 = tracing.totals()
    layers, params = zip(*[
        SparseLinear.create(Initializer(40 + i, jnp.float32), 32, 64,
                            block=(16, 16), density=0.5) for i in range(2)])
    grp = SparseLinearGroup(layers)
    assert _change(t0, "sextans.pack")["count"] == 1
    x = jnp.asarray(rng.standard_normal((8, 32)), jnp.float32)
    grp(list(params), x, use_plan=True)
    layers[0](params[0], x, use_plan=True)
    t1 = tracing.totals()
    grp(list(params), x, use_plan=True)
    y = layers[0](params[0], x, use_plan=True)
    y.block_until_ready()
    assert _change(t0, "sextans.plan.build")["count"] == 2
    assert _change(t1, "sextans.plan.build")["count"] == 0
    for name, count in (("sextans.layer.group", 1),
                        ("sextans.layer.stack_values", 1),
                        ("sextans.layer.linear", 1),
                        ("sextans.plan.run", 2)):
        assert _change(t1, name)["count"] == count, name
    # the group's self time excludes its value stacking and plan run
    g = _change(t1, "sextans.layer.group")
    stacking = _change(t1, "sextans.layer.stack_values")["wall_s"]
    assert g["self_s"] < g["wall_s"] - stacking
    assert y.shape == (8, 64)
