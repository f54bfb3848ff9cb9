"""2-D (K-window x N-tile) out-of-core streaming tests.

Acceptance criteria of the 2-D tier:

* forcing any ``n_tile`` (tail tile included) reproduces the single-shot
  result **bit for bit** on both backends — column tiling never
  reassociates a column's add sequence;
* a problem whose budget cannot hold even one full-N window chunk tiles N
  (``n_tiles > 1``), keeps ``peak_payload_bytes`` under the budget, and
  still matches bitwise; tiled runs return host numpy (the full C does not
  fit on device by premise);
* ``values=`` substitution, differentiation, the engine and the serving
  scheduler all work through the tiled path with consistent stats.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.sparse_api as sp
from repro.core.sparse import power_law_sparse

PALLAS_OPTS = dict(tn=8, interpret=True)


def _packed(m=300, k=500, seed=1, n=16, tm=64, k0=64):
    rng = np.random.default_rng(seed)
    a = power_law_sparse(m, k, 6, seed=seed)
    A = sp.from_sparse_matrix(a, tm=tm, k0=k0, chunk=8, bucket=True)
    b = rng.standard_normal((k, n)).astype(np.float32)
    c = rng.standard_normal((m, n)).astype(np.float32)
    return a, A, b, c


class TestStreamingPlan2D:
    @pytest.mark.parametrize("wc,nt", [
        (1, 16), (1, 8), (1, 5), (1, 1),     # nt=5: padded tail tile
        (2, 8), (2, 5), (3, 4), (8, 5),
    ])
    def test_bit_identical_jnp(self, wc, nt):
        _, A, b, c = _packed()
        y_ref = np.asarray(sp.spmm(A, b, c, 1.25, -0.5, backend="jnp"))
        P = sp.plan(A, 16, backend="jnp", stream=True, window_chunk=wc,
                    n_tile=nt)
        assert P.n_tile == nt and P.n_tiles == -(-16 // nt)
        assert P.window_dispatches == P.steps * P.n_tiles
        out = P.run(b, c, 1.25, -0.5)
        if P.n_tiles > 1:
            assert isinstance(out, np.ndarray)   # host-resident stripes
        np.testing.assert_array_equal(np.asarray(out), y_ref)

    @pytest.mark.parametrize("wc,nt", [(1, 8), (2, 5), (3, 16)])
    def test_bit_identical_pallas(self, wc, nt):
        _, A, b, c = _packed()
        y_ref = np.asarray(sp.spmm(A, b, c, 2.0, 0.5, backend="pallas",
                                   **PALLAS_OPTS))
        P = sp.plan(A, 16, backend="pallas", stream=True, window_chunk=wc,
                    n_tile=nt, **PALLAS_OPTS)
        np.testing.assert_array_equal(np.asarray(P.run(b, c, 2.0, 0.5)),
                                      y_ref)

    @pytest.mark.parametrize("backend,opts", [("jnp", {}),
                                              ("pallas", PALLAS_OPTS)])
    def test_huge_n_budget_forces_column_tiling(self, backend, opts):
        """The tentpole acceptance criterion: a budget below one full-N
        window chunk still executes — via N-tiling — bit-identically and
        under budget."""
        rng = np.random.default_rng(5)
        a = power_law_sparse(300, 500, 6, seed=5)
        A = sp.from_sparse_matrix(a, tm=64, k0=64, chunk=8, bucket=True)
        n = 64
        b = rng.standard_normal((500, n)).astype(np.float32)
        c = rng.standard_normal((300, n)).astype(np.float32)
        # below the wc=1 full-N floor (forces column tiling) AND below the
        # resident working set (so the budget alone selects the tier)
        full_n_floor = sp.plan(A, n, backend=backend, stream=True,
                               window_chunk=1, **opts).peak_payload_bytes
        cap = min(int(full_n_floor * 0.6), A.nbytes)
        P = sp.plan(A, n, backend=backend, device_bytes=cap, **opts)
        assert isinstance(P, sp.StreamingPlan)
        assert P.n_tiles > 1                    # full N cannot fit
        assert P.peak_payload_bytes <= cap
        out = P.run(b, c, 1.5, -0.25)
        assert isinstance(out, np.ndarray)
        y_ref = np.asarray(sp.spmm(A, b, c, 1.5, -0.25, backend=backend,
                                   **opts))
        np.testing.assert_array_equal(out, y_ref)

    def test_budget_prefers_untiled_n(self):
        """N stays untiled whenever a full-N wc=1 chunk fits: column tiling
        only kicks in when the budget forces it."""
        _, A, _, _ = _packed()
        floor = sp.plan(A, 16, backend="jnp", stream=True,
                        window_chunk=1).peak_payload_bytes
        P = sp.plan(A, 16, backend="jnp", stream=True,
                    device_bytes=floor + 1024)
        assert P.n_tiles == 1 and P.n_tile == 16

    def test_values_substitution_tiled(self):
        """Double-buffer regression: ``run(values=...)`` must re-stage every
        (tile, chunk) cell from the substituted payload — a stale staged
        buffer would corrupt exactly one window of one stripe."""
        _, A, b, _ = _packed(seed=4)
        P = sp.plan(A, 16, backend="jnp", stream=True, window_chunk=3,
                    n_tile=4)
        assert P.n_tiles > 1
        v2 = np.asarray(A.values) * 3.0
        y = np.asarray(P.run(b, values=v2))
        y_ref = np.asarray(sp.spmm(A.with_values(jnp.asarray(v2)), b,
                                   backend="jnp"))
        np.testing.assert_array_equal(y, y_ref)
        # and the original payload is untouched by the substitution
        np.testing.assert_array_equal(
            np.asarray(P.run(b)),
            np.asarray(sp.spmm(A, b, backend="jnp")))

    def test_tiled_plans_share_step_executables(self):
        """The step/finish exec keys record the tile width, not the logical
        N — a plan tiled at n_tile=8 reuses the executables of a natural
        N=8 plan (HFlex at the column-tile level)."""
        _, A, b, _ = _packed()
        sp.plan(A, 8, backend="jnp", stream=True, window_chunk=2).run(b[:, :8])
        m0 = sp.PLAN_STATS["exec_misses"]
        P = sp.plan(A, 16, backend="jnp", stream=True, window_chunk=2,
                    n_tile=8)
        P.run(b)
        assert sp.PLAN_STATS["exec_misses"] == m0

    def test_dispatch_stats_tiled(self):
        _, A, b, _ = _packed()
        P = sp.plan(A, 16, backend="jnp", stream=True, window_chunk=2,
                    n_tile=4)
        d0 = sp.PLAN_STATS["dispatches"]
        w0 = sp.PLAN_STATS["window_dispatches"]
        P.run(b)
        assert (sp.PLAN_STATS["window_dispatches"] - w0
                == P.steps * P.n_tiles == P.window_dispatches)
        # one epilogue per column tile
        assert (sp.PLAN_STATS["dispatches"] - d0
                == P.window_dispatches + P.n_tiles)

    def test_validation(self):
        _, A, b, _ = _packed()
        for bad in (0, 17):
            with pytest.raises(ValueError):
                sp.plan(A, 16, backend="jnp", stream=True, n_tile=bad)
        with pytest.raises(ValueError):
            sp.plan(A, 16, backend="jnp", n_tile=4)      # resident plan
        with pytest.raises(ValueError):
            sp.spmm_streaming(A, b, window_chunk=2, n_tile=0)
        with pytest.raises(ValueError):
            sp.spmm_streaming(A, b, window_chunk=2, n_tile=17)


class TestSpmmStreaming2D:
    @pytest.mark.parametrize("backend,opts", [("jnp", {}),
                                              ("pallas", PALLAS_OPTS)])
    def test_forward_bit_identical(self, backend, opts):
        _, A, b, c = _packed()
        y_ref = np.asarray(sp.spmm(A, b, c, 1.25, -0.5, backend=backend,
                                   **opts))
        for wc, nt in ((1, 4), (2, 5), (3, 16), (8, 1)):
            y = np.asarray(sp.spmm_streaming(A, b, c, 1.25, -0.5,
                                             window_chunk=wc, n_tile=nt,
                                             backend=backend, **opts))
            np.testing.assert_array_equal(y, y_ref,
                                          err_msg=f"wc={wc} nt={nt}")

    def test_grad_matches_dense_oracle_tiled(self):
        _, A, b_np, c_np = _packed(seed=2)
        b, c = jnp.asarray(b_np), jnp.asarray(c_np)

        def loss(vals, b_, c_, al, be):
            out = sp.spmm_streaming(A.with_values(vals), b_, c_, al, be,
                                    window_chunk=3, n_tile=5, backend="jnp")
            return jnp.sum(jnp.sin(out))

        def loss_dense(vals, b_, c_, al, be):
            dense = A.with_values(vals).todense()
            return jnp.sum(jnp.sin(al * dense @ b_ + be * c_))

        args = (A.values, b, c, jnp.float32(1.3), jnp.float32(0.7))
        g = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2, 3, 4))(*args)
        valid = (np.arange(A.data.lw).reshape(A.data.vals.shape[-2:])
                 < np.asarray(A.data.nse)[:, :, None, None])
        np.testing.assert_allclose(np.asarray(g[0])[valid],
                                   np.asarray(gd[0])[valid],
                                   rtol=1e-4, atol=1e-4, err_msg="vals")
        assert np.all(np.asarray(g[0])[~valid] == 0.0)
        for name, x, y in zip(("b", "c", "alpha", "beta"), g[1:], gd[1:]):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-4, atol=1e-4, err_msg=name)

    def test_grads_agree_with_untiled(self):
        _, A, b, _ = _packed(seed=7)
        g_tiled = jax.grad(lambda v: jnp.sum(sp.spmm_streaming(
            A.with_values(v), b, window_chunk=2, n_tile=4,
            backend="jnp") ** 2))(A.values)
        g_full = jax.grad(lambda v: jnp.sum(sp.spmm_streaming(
            A.with_values(v), b, window_chunk=2, backend="jnp") ** 2))(
                A.values)
        np.testing.assert_allclose(np.asarray(g_tiled), np.asarray(g_full),
                                   rtol=1e-5, atol=1e-5)


class TestEngineAndScheduler2D:
    def test_engine_n_tile_routing_and_stats(self):
        from repro.core.engine import SextansEngine

        rng = np.random.default_rng(1)
        a = power_law_sparse(300, 500, 6, seed=1)
        b = rng.standard_normal((500, 16)).astype(np.float32)
        eng = SextansEngine(tm=64, k0=64, chunk=8, impl="jnp")
        t = eng.pack(a, device=False)
        y_res = np.asarray(eng.spmm(eng.pack(a), jnp.asarray(b)))
        y = eng.spmm_streaming(t, b, device_bytes=t.nbytes // 4, n_tile=4)
        pl = eng.last_streaming_plan
        assert pl.n_tiles == 4
        np.testing.assert_array_equal(np.asarray(y), y_res)
        assert eng.stats.n_tiles == 4
        assert eng.stats.window_dispatches == pl.steps * 4
        # distinct n_tile -> distinct cached plan; same n_tile -> cache hit
        plans0 = len(eng._plans)
        eng.spmm_streaming(t, b, device_bytes=t.nbytes // 4, n_tile=4)
        assert len(eng._plans) == plans0
        eng.spmm_streaming(t, b, device_bytes=t.nbytes // 4, n_tile=8)
        assert len(eng._plans) == plans0 + 1

    def test_scheduler_oversized_lane_tiles_end_to_end(self):
        from repro.core.engine import SextansEngine
        from repro.launch.serve import SpmmRequest, SpmmScheduler
        from repro.core.sparse import spmm_reference

        rng = np.random.default_rng(0)
        reqs = [SpmmRequest(
            a=power_law_sparse(128, 128, 5, seed=i),
            b=rng.standard_normal((128, 16)).astype(np.float32))
            for i in range(3)]
        big = power_law_sparse(600, 2000, 8, seed=99)
        reqs.append(SpmmRequest(
            a=big, b=rng.standard_normal((2000, 16)).astype(np.float32)))

        probe = SextansEngine(tm=64, k0=64, chunk=8, impl="jnp")
        cap = (probe.pack(reqs[0].a).nbytes + probe.pack(big).nbytes) // 2

        sched = SpmmScheduler(
            SextansEngine(tm=64, k0=64, chunk=8, impl="jnp"),
            device_bytes=cap, n_tile=4)
        for r in reqs:
            sched.submit(r)
        outs = sched.flush()
        st = sched.stats
        pl = sched.engine.last_streaming_plan
        assert st["streamed"] == 1
        assert st["n_tiles"] == pl.n_tiles == 4
        assert st["window_dispatches"] == pl.steps * 4
        assert st["dispatches"] == (st["groups"] + st["window_dispatches"]
                                    + pl.n_tiles)
        assert st["last_flush"]["n_tiles"] == 4
        for r, o in zip(reqs, outs):
            ref = spmm_reference(
                r.a, r.b, np.zeros((r.a.shape[0], r.b.shape[1]), np.float32))
            np.testing.assert_allclose(
                o, ref, rtol=2e-4, atol=2e-4 * max(1, np.abs(ref).max()))
