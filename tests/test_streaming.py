"""Out-of-core K-window streaming tests.

Acceptance criteria of the streaming tier:

* ``SparseTensor.windows(w0, w1)`` is a self-describing, unstack-compatible
  window slice;
* a matrix whose payload exceeds an artificial ``device_bytes`` cap
  (cap < payload/4) executes through :class:`StreamingPlan` bit-identically
  to the unplanned ``spmm``, with ``window_dispatches > 1``, on both the
  jnp and Pallas (interpret) backends;
* ``spmm_streaming`` (the differentiable twin) is bit-identical for every
  window-chunk size and its gradients match the dense oracle;
* the engine / serving scheduler route oversized problems through the
  streaming lane with consistent dispatch stats.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.sparse_api as sp
from repro.core.sparse import power_law_sparse, spmm_reference

PALLAS_OPTS = dict(tn=16, interpret=True)


def _packed(m=300, k=500, seed=1, n=16, tm=64, k0=64, bucket=True):
    rng = np.random.default_rng(seed)
    a = power_law_sparse(m, k, 6, seed=seed)
    A = sp.from_sparse_matrix(a, tm=tm, k0=k0, chunk=8, bucket=bucket)
    b = rng.standard_normal((k, n)).astype(np.float32)
    c = rng.standard_normal((m, n)).astype(np.float32)
    return a, A, b, c


class TestWindows:
    def test_slice_shapes_and_metadata(self):
        _, A, _, _ = _packed()
        d = A.data
        W = A.windows(2, 5)
        dw = W.data
        assert dw.vals.shape == (d.mb, 3) + d.vals.shape[-2:]
        assert dw.lw == d.lw
        assert dw.q.shape == (d.mb, 3)
        assert dw.nse.shape == (d.mb, 3)
        assert W.shape == (A.m, 3 * d.k0)
        assert W.nnz == int(np.asarray(d.nse[:, 2:5]).sum())
        np.testing.assert_array_equal(np.asarray(dw.q),
                                      np.asarray(d.q[:, 2:5]))

    def test_tail_slice_has_ragged_k(self):
        _, A, _, _ = _packed()
        d = A.data
        W = A.windows(d.nw - 2, d.nw)
        assert W.shape[1] == A.k - (d.nw - 2) * d.k0

    def test_self_describing_todense_concat(self):
        """Concatenating the dense views of a window partition recovers the
        full dense matrix — slices are complete, self-contained matrices."""
        a, A, _, _ = _packed()
        d = A.data
        parts = [np.asarray(A.windows(w, min(w + 3, d.nw)).todense())
                 for w in range(0, d.nw, 3)]
        np.testing.assert_array_equal(np.concatenate(parts, axis=1),
                                      np.asarray(A.todense()))

    def test_window_contribution_sums_to_spmm(self):
        _, A, b, _ = _packed()
        d = A.data
        total = np.zeros((A.m, b.shape[1]), np.float32)
        for w in range(d.nw):
            W = A.windows(w, w + 1)
            bw = b[w * d.k0: w * d.k0 + W.k]
            total += np.asarray(sp.spmm(W, bw, backend="jnp"))
        ref = np.asarray(sp.spmm(A, b, backend="jnp"))
        np.testing.assert_allclose(total, ref, rtol=2e-4,
                                   atol=2e-4 * max(1, np.abs(ref).max()))

    def test_batched_slice_unstack_compatible(self):
        _, A1, _, _ = _packed(seed=1)
        _, A2, _, _ = _packed(seed=2)
        S = sp.stack_hflex([A1, A2])
        W = S.windows(1, 4)
        assert W.batch == 2
        m1, m2 = W.unstack()
        np.testing.assert_array_equal(np.asarray(m1.data.vals),
                                      np.asarray(A1.windows(1, 4).data.vals))
        assert m2.nnz == A2.windows(1, 4).nnz

    def test_bounds_validation(self):
        _, A, _, _ = _packed()
        nw = A.num_windows
        for w0, w1 in ((-1, 2), (0, 0), (2, 1), (0, nw + 1)):
            with pytest.raises(ValueError):
                A.windows(w0, w1)


class TestSizeHelpers:
    def test_tensor_nbytes(self):
        _, A, _, _ = _packed()
        d = A.data
        expect = (d.vals.nbytes + d.cols.nbytes + d.rows.nbytes
                  + d.q.nbytes + d.nse.nbytes)
        assert A.nbytes == expect

    def test_bsr_nbytes(self):
        rng = np.random.default_rng(0)
        B = sp.from_dense(rng.standard_normal((64, 96)).astype(np.float32),
                          format=sp.Format.BSR, block=(16, 16))
        d = B.data
        assert B.nbytes == d.blocks.nbytes + d.brow.nbytes + d.indptr.nbytes

    def test_plan_payload_bytes(self):
        _, A, _, _ = _packed()
        P = sp.plan(A, 16, backend="jnp")
        assert P.payload_bytes > 0
        # the flat jnp plan holds vals + global cols/rows ids
        assert P.payload_bytes == sum(x.nbytes for x in P._operands)

    def test_streaming_plan_payload_bytes(self):
        _, A, _, _ = _packed()
        P1 = sp.plan(A, 16, backend="jnp", stream=True, window_chunk=1)
        P2 = sp.plan(A, 16, backend="jnp", stream=True, window_chunk=2)
        assert P2.payload_bytes == A.nbytes
        # chunk working set scales with the window chunk; peak adds the
        # double buffer + accumulator + epilogue operands on top
        assert P2.chunk_payload_bytes == 2 * P1.chunk_payload_bytes
        assert P2.peak_payload_bytes > 2 * P2.chunk_payload_bytes
        assert P1.peak_payload_bytes < P2.peak_payload_bytes


class TestStreamingPlan:
    @pytest.mark.parametrize("wc", [1, 2, 3, 5, 8])
    def test_bit_identical_jnp_all_chunk_sizes(self, wc):
        _, A, b, c = _packed()
        y_ref = np.asarray(sp.spmm(A, b, c, 1.25, -0.5, backend="jnp"))
        P = sp.plan(A, 16, backend="jnp", stream=True, window_chunk=wc)
        assert P.steps == -(-A.num_windows // wc)
        np.testing.assert_array_equal(np.asarray(P.run(b, c, 1.25, -0.5)),
                                      y_ref)

    @pytest.mark.parametrize("wc", [1, 3, 8])
    def test_bit_identical_pallas(self, wc):
        _, A, b, c = _packed()
        y_ref = np.asarray(sp.spmm(A, b, c, 2.0, 0.5, backend="pallas",
                                   **PALLAS_OPTS))
        P = sp.plan(A, 16, backend="pallas", stream=True, window_chunk=wc,
                    **PALLAS_OPTS)
        np.testing.assert_array_equal(np.asarray(P.run(b, c, 2.0, 0.5)),
                                      y_ref)

    @pytest.mark.parametrize("backend,opts", [("jnp", {}),
                                              ("pallas", PALLAS_OPTS)])
    def test_acceptance_cap_under_quarter_payload(self, backend, opts):
        """A payload over 4x the device budget streams bit-identically with
        multiple window dispatches — the tentpole acceptance criterion."""
        _, A, b, c = _packed()
        cap = A.nbytes // 5
        P = sp.plan(A, 16, backend=backend, device_bytes=cap, **opts)
        assert isinstance(P, sp.StreamingPlan)
        assert P.window_dispatches > 1
        assert P.window_chunk < A.num_windows   # slabs chunked, not resident
        y_ref = np.asarray(sp.spmm(A, b, c, 1.5, -0.25, backend=backend,
                                   **opts))
        np.testing.assert_array_equal(np.asarray(P.run(b, c, 1.5, -0.25)),
                                      y_ref)

    def test_device_bytes_selects_tier(self):
        _, A, _, _ = _packed()
        assert isinstance(sp.plan(A, 16, backend="jnp",
                                  device_bytes=A.nbytes // 4),
                          sp.StreamingPlan)
        assert isinstance(sp.plan(A, 16, backend="jnp",
                                  device_bytes=1 << 30), sp.SpmmPlan)

    def test_matches_reference(self):
        a, A, b, c = _packed(seed=3)
        P = sp.plan(A, 16, backend="jnp", stream=True, window_chunk=2)
        ref = spmm_reference(a, b, c, 1.5, -0.25)
        np.testing.assert_allclose(np.asarray(P.run(b, c, 1.5, -0.25)), ref,
                                   rtol=2e-4, atol=2e-4 * np.abs(ref).max())

    def test_values_substitution(self):
        _, A, b, _ = _packed(seed=4)
        P = sp.plan(A, 16, backend="jnp", stream=True, window_chunk=3)
        v2 = np.asarray(A.values) * 3.0
        y = np.asarray(P.run(b, values=v2))
        y_ref = np.asarray(sp.spmm(A.with_values(jnp.asarray(v2)), b,
                                   backend="jnp"))
        np.testing.assert_array_equal(y, y_ref)

    def test_alpha_beta_are_runtime_operands(self):
        """Epilogue sweeps reuse the streaming executables (HFlex)."""
        _, A, b, c = _packed(seed=5)
        P = sp.plan(A, 16, backend="jnp", stream=True, window_chunk=4)
        t0 = sp.BACKEND_STATS["traces"]
        m0 = sp.PLAN_STATS["exec_misses"]
        for alpha, beta in [(1.0, 0.0), (0.5, 0.5), (2.0, -1.0)]:
            P.run(b, c, alpha, beta)
        assert sp.BACKEND_STATS["traces"] == t0
        assert sp.PLAN_STATS["exec_misses"] == m0

    def test_bucket_mates_share_step_executable(self):
        _, A1, b, _ = _packed(seed=6)
        _, A2, _, _ = _packed(seed=60)
        assert A1.geometry == A2.geometry
        sp.plan(A1, 16, backend="jnp", stream=True, window_chunk=2)
        m0 = sp.PLAN_STATS["exec_misses"]
        P2 = sp.plan(A2, 16, backend="jnp", stream=True, window_chunk=2)
        assert sp.PLAN_STATS["exec_misses"] == m0
        np.testing.assert_array_equal(
            np.asarray(P2.run(b)),
            np.asarray(sp.spmm(A2, b, backend="jnp")))

    def test_window_dispatch_stats(self):
        _, A, b, _ = _packed()
        P = sp.plan(A, 16, backend="jnp", stream=True, window_chunk=2)
        d0 = sp.PLAN_STATS["dispatches"]
        w0 = sp.PLAN_STATS["window_dispatches"]
        P.run(b)
        assert sp.PLAN_STATS["window_dispatches"] - w0 == P.steps == 4
        assert sp.PLAN_STATS["dispatches"] - d0 == P.steps + 1

    def test_plan_pins_no_device_payload(self):
        """The streaming plan re-homes its payload references to the host
        copies: dropping the caller's packed tensor must leave nothing of
        the device payload alive through the plan."""
        _, A, _, _ = _packed()
        P = sp.plan(A, 16, backend="jnp", stream=True, window_chunk=2)
        for leaf in (P.a.data.vals, P.a.data.cols, P.a.data.rows,
                     P.a.data.q, P.a.data.nse):
            assert isinstance(leaf, np.ndarray), type(leaf)
        assert P.payload_bytes == A.nbytes          # sizes still reported

    def test_c_dtype_mismatch_is_cast_not_crash(self):
        """Regression: the AOT executables are compiled for the planned
        dtype; a c of another dtype must be cast (the batched scheduler's
        treatment), not crash the dispatch."""
        _, A, b, c = _packed(seed=8)
        c16 = c.astype(np.float16)
        P = sp.plan(A, 16, backend="jnp", stream=True, window_chunk=2)
        y = np.asarray(P.run(b, c16, 1.0, 1.0))
        y_ref = np.asarray(sp.spmm(A, b, c16.astype(np.float32), 1.0, 1.0,
                                   backend="jnp"))
        np.testing.assert_array_equal(y, y_ref)
        Pr = sp.plan(A, 16, backend="jnp")          # resident: same gap
        np.testing.assert_array_equal(np.asarray(Pr.run(b, c16, 1.0, 1.0)),
                                      y_ref)

    def test_budget_overrun_warns(self):
        """A budget below the wc=1 floor cannot be honored — the plan must
        say so instead of silently overrunning on a real device."""
        _, A, _, _ = _packed()
        with pytest.warns(UserWarning, match="exceeds device_bytes"):
            P = sp.plan(A, 16, backend="jnp", device_bytes=1024)
        assert P.window_chunk == 1

    def test_validation(self):
        _, A, b, _ = _packed()
        with pytest.raises(ValueError):
            sp.plan(A, 16, backend="jnp", stream=True, window_chunk=0)
        with pytest.raises(ValueError):
            sp.plan(A, 16, backend="jnp", stream=True,
                    window_chunk=A.num_windows + 1)
        P = sp.plan(A, 16, backend="jnp", stream=True, window_chunk=2)
        with pytest.raises(ValueError):
            P.run(b[:, :8])                      # wrong N
        with pytest.raises(ValueError):
            P.run(b, values=np.zeros((2, 2), np.float32))
        S = sp.stack_hflex([A, A])
        with pytest.raises(ValueError):
            sp.plan(S, 16, backend="jnp", stream=True)   # batched
        rng = np.random.default_rng(0)
        B = sp.from_dense(rng.standard_normal((64, 96)).astype(np.float32),
                          format=sp.Format.BSR, block=(16, 16))
        with pytest.raises(ValueError):
            sp.plan(B, 8, backend="jnp", stream=True)    # BSR


class TestSpmmStreamingDifferentiable:
    @pytest.mark.parametrize("backend,opts,wcs", [
        ("jnp", {}, (1, 2, 3, 5, 8)),
        ("pallas", PALLAS_OPTS, (1, 3, 8)),
    ])
    def test_forward_bit_identical_all_chunk_sizes(self, backend, opts, wcs):
        _, A, b, c = _packed()
        y_ref = np.asarray(sp.spmm(A, b, c, 1.25, -0.5, backend=backend,
                                   **opts))
        for wc in wcs:
            y = np.asarray(sp.spmm_streaming(A, b, c, 1.25, -0.5,
                                             window_chunk=wc,
                                             backend=backend, **opts))
            np.testing.assert_array_equal(y, y_ref, err_msg=f"wc={wc}")

    def test_grad_matches_dense_oracle(self):
        """d loss/d {vals, b, c, alpha, beta} under streaming vs jax.grad on
        the dense compute — the acceptance gradient criterion."""
        rng = np.random.default_rng(2)
        _, A, b_np, c_np = _packed(seed=2)
        b = jnp.asarray(b_np)
        c = jnp.asarray(c_np)

        def loss(vals, b_, c_, al, be):
            out = sp.spmm_streaming(A.with_values(vals), b_, c_, al, be,
                                    window_chunk=3, backend="jnp")
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

    def test_epilogue_casts_to_b_dtype_not_c(self):
        """Regression: with c in a different dtype than b, the resident
        paths cast the result to b's dtype — streaming must do the same."""
        _, A, b, c = _packed(seed=9)
        c16 = jnp.asarray(c, jnp.float16)
        y_ref = sp.spmm(A, b, c16, 1.5, 0.5, backend="jnp")
        y_s = sp.spmm_streaming(A, b, c16, 1.5, 0.5, window_chunk=3,
                                backend="jnp")
        assert y_s.dtype == y_ref.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(y_s), np.asarray(y_ref))

    def test_grads_agree_with_single_shot(self):
        _, A, b, _ = _packed(seed=7)
        g_stream = jax.grad(lambda v: jnp.sum(sp.spmm_streaming(
            A.with_values(v), b, window_chunk=2, backend="jnp") ** 2))(
                A.values)
        g_single = jax.grad(lambda v: jnp.sum(sp.spmm(
            A.with_values(v), b, backend="jnp") ** 2))(A.values)
        np.testing.assert_allclose(np.asarray(g_stream),
                                   np.asarray(g_single),
                                   rtol=1e-5, atol=1e-5)

    def test_validation(self):
        _, A, b, _ = _packed()
        with pytest.raises(ValueError):
            sp.spmm_streaming(A, b, window_chunk=0)
        with pytest.raises(ValueError):
            sp.spmm_streaming(A, b[:100])        # wrong K
        with pytest.raises(ValueError):
            sp.spmm_streaming(sp.stack_hflex([A, A]),
                              np.stack([b, b]))  # batched


class TestNonInterleavedTailPad:
    @pytest.mark.parametrize("backend,opts", [("jnp", {}),
                                              ("pallas", PALLAS_OPTS)])
    def test_block_major_layout_pads_out_of_bounds(self, backend, opts):
        """Regression: tail-chunk pad rows must map out of [0, M) in the
        block-major (interleave=False) layout too — rows=TM would land in
        the NEXT block's first row for every block but the last."""
        rng = np.random.default_rng(4)
        a = power_law_sparse(300, 500, 6, seed=4)
        A = sp.from_sparse_matrix(a, tm=64, k0=64, chunk=8, bucket=True,
                                  interleave=False)
        assert not A.data.interleaved and A.data.mb > 1
        b = rng.standard_normal((500, 16)).astype(np.float32)
        y_ref = np.asarray(sp.spmm(A, b, backend=backend, **opts))
        # window_chunk=3 over NW=8 leaves a 1-window padded tail chunk
        P = sp.plan(A, 16, backend=backend, stream=True, window_chunk=3,
                    **opts)
        np.testing.assert_array_equal(np.asarray(P.run(b)), y_ref)


class TestEngineStreaming:
    def test_bit_identical_and_stats(self):
        from repro.core.engine import SextansEngine

        rng = np.random.default_rng(1)
        a = power_law_sparse(300, 500, 6, seed=1)
        b = rng.standard_normal((500, 16)).astype(np.float32)
        eng = SextansEngine(tm=64, k0=64, chunk=8, impl="jnp")
        t = eng.pack(a)
        y_res = np.asarray(eng.spmm(t, jnp.asarray(b)))
        y_str = np.asarray(eng.spmm_streaming(t, b,
                                              device_bytes=t.nbytes // 4))
        np.testing.assert_array_equal(y_res, y_str)
        assert eng.stats.streamed == 1
        assert eng.stats.window_dispatches > 1
        assert (eng.stats.peak_payload_bytes
                == eng.last_streaming_plan.peak_payload_bytes > 0)
        # second call reuses the cached streaming plan
        plans0 = len(eng._plans)
        eng.spmm_streaming(t, b, device_bytes=t.nbytes // 4)
        assert len(eng._plans) == plans0
        # the resident entry is untouched by the streaming key: spmm still
        # runs resident (regression: a StreamingPlan must never shadow the
        # resident cache slot)
        y2 = np.asarray(eng.spmm(t, jnp.asarray(b)))
        np.testing.assert_array_equal(y2, y_res)
        assert isinstance(eng.plan_for(t, 16, np.float32), sp.SpmmPlan)

    def test_plan_for_rejects_budget_without_stream(self):
        from repro.core.engine import SextansEngine

        eng = SextansEngine(tm=64, k0=64, chunk=8, impl="jnp")
        t = eng.pack(power_law_sparse(100, 128, 5, seed=0))
        with pytest.raises(ValueError):
            eng.plan_for(t, 8, device_bytes=1024)


class TestSchedulerStreamingLane:
    def test_oversized_requests_ride_streaming_lane(self):
        from repro.core.engine import SextansEngine
        from repro.launch.serve import SpmmRequest, SpmmScheduler

        rng = np.random.default_rng(0)
        reqs = []
        for i in range(6):
            a = power_law_sparse(256, 256, 5, seed=i)
            reqs.append(SpmmRequest(
                a=a, b=rng.standard_normal((256, 16)).astype(np.float32)))
        big = power_law_sparse(600, 2000, 8, seed=99)
        reqs.append(SpmmRequest(
            a=big, b=rng.standard_normal((2000, 16)).astype(np.float32)))

        probe = SextansEngine(tm=64, k0=64, chunk=8, impl="jnp")
        small_b = probe.pack(reqs[0].a).nbytes
        big_b = probe.pack(big).nbytes
        cap = (small_b + big_b) // 2

        sched = SpmmScheduler(
            SextansEngine(tm=64, k0=64, chunk=8, impl="jnp"),
            device_bytes=cap)
        for r in reqs:
            sched.submit(r)
        outs = sched.flush()
        st = sched.stats
        assert st["streamed"] == 1
        assert st["window_dispatches"] > 1
        assert st["batched_requests"] == 6      # mates still group
        # consistent accounting: group dispatches + streamed window steps
        # + one epilogue per column tile of the streamed plan
        pl = sched.engine.last_streaming_plan
        assert st["dispatches"] == (st["groups"] + st["window_dispatches"]
                                    + pl.n_tiles)
        assert st["n_tiles"] == pl.n_tiles >= 1
        lf = st["last_flush"]
        assert lf["requests"] == len(reqs)
        assert lf["dispatches"] == st["dispatches"]
        assert lf["streamed"] == 1
        for r, o in zip(reqs, outs):
            ref = spmm_reference(
                r.a, r.b, np.zeros((r.a.shape[0], r.b.shape[1]), np.float32))
            np.testing.assert_allclose(
                o, ref, rtol=2e-4, atol=2e-4 * max(1, np.abs(ref).max()))

    def test_per_flush_stats_reset(self):
        from repro.core.engine import SextansEngine
        from repro.launch.serve import SpmmRequest, SpmmScheduler

        rng = np.random.default_rng(3)
        sched = SpmmScheduler(SextansEngine(tm=64, k0=64, chunk=8,
                                            impl="jnp"))
        a = power_law_sparse(128, 128, 5, seed=0)
        for _ in range(2):
            sched.submit(SpmmRequest(
                a=a, b=rng.standard_normal((128, 8)).astype(np.float32)))
        sched.flush()
        first = dict(sched.stats["last_flush"])
        sched.submit(SpmmRequest(
            a=a, b=rng.standard_normal((128, 8)).astype(np.float32)))
        sched.flush()
        second = sched.stats["last_flush"]
        assert first["requests"] == 2
        assert second["requests"] == 1
        assert sched.stats["requests"] == 3
        assert sched.stats["flushes"] == 2
