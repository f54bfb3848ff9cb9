"""Batched group execution tests: stack_hflex structure, batched spmm
(forward bit-identity + gradients), group plans (one dispatch per group),
the geometry-bucketing serving scheduler, and the plan-routed sharded
engine path on a 1-device mesh."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.sparse_api as sp
from repro.core.engine import SextansEngine
from repro.core.sparse import power_law_sparse, random_sparse, spmm_reference
from repro.launch.serve import SpmmRequest, SpmmScheduler, serve_spmm_requests


def _mates(g=4, m=256, k=200, seed0=0, tm=64, k0=64):
    """G bucket-mate matrices + their packed tensors (shared geometry)."""
    mats = [power_law_sparse(m, k, 5, seed=seed0 + i) for i in range(g)]
    ts = [sp.from_sparse_matrix(a, tm=tm, k0=k0, chunk=8, bucket=True)
          for a in mats]
    assert len({t.geometry for t in ts}) == 1, "bucket precondition"
    return mats, ts


class TestStackHflex:
    def test_stack_structure_and_batch_property(self):
        _, ts = _mates(4)
        s = sp.stack_hflex(ts)
        assert s.batch == 4
        assert s.shape == ts[0].shape
        assert s.data.vals.shape == (4, *ts[0].data.vals.shape)
        assert s.data.q.shape == (4, *ts[0].data.q.shape)
        assert s.nnz == sum(t.nnz for t in ts)
        assert s.geometry == ts[0].geometry
        for t in ts:
            assert t.batch is None

    def test_unstack_round_trip(self):
        _, ts = _mates(3, seed0=10)
        s = sp.stack_hflex(ts)
        back = s.unstack()
        assert len(back) == 3
        for t, u in zip(ts, back):
            assert u.nnz == t.nnz
            assert np.array_equal(np.asarray(u.todense()),
                                  np.asarray(t.todense()))
        # single-member indexing
        assert np.array_equal(np.asarray(s[1].todense()),
                              np.asarray(ts[1].todense()))

    def test_geometry_checked(self):
        _, ts = _mates(2)
        other = sp.from_sparse_matrix(power_law_sparse(256, 200, 5, seed=0),
                                      tm=32, k0=64, chunk=8, bucket=True)
        with pytest.raises(ValueError, match="geometry"):
            sp.stack_hflex([ts[0], other])

    def test_shape_checked(self):
        # same slab geometry, different logical shape -> explicit error
        a1 = sp.from_sparse_matrix(
            random_sparse(60, 64, 0.01, seed=1), tm=32, k0=64, chunk=8)
        a2 = sp.from_sparse_matrix(
            random_sparse(64, 64, 0.01, seed=2), tm=32, k0=64, chunk=8)
        if a1.geometry != a2.geometry:
            pytest.skip("lw buckets diverged for this seed")
        with pytest.raises(ValueError, match="shape"):
            sp.stack_hflex([a1, a2])

    def test_rejects_nested_and_bsr(self):
        _, ts = _mates(2)
        s = sp.stack_hflex(ts)
        with pytest.raises(ValueError, match="already-batched"):
            sp.stack_hflex([s])
        bsr = sp.from_dense(np.eye(32, dtype=np.float32),
                            format=sp.Format.BSR, block=(16, 16))
        with pytest.raises(ValueError, match="HFLEX"):
            sp.stack_hflex([bsr])


class TestBatchedSpmm:
    def test_jnp_bit_identical_per_member(self, rng):
        mats, ts = _mates(4)
        s = sp.stack_hflex(ts)
        b = jnp.asarray(rng.standard_normal((4, 200, 16)), jnp.float32)
        c = jnp.asarray(rng.standard_normal((4, 256, 16)), jnp.float32)
        y = sp.spmm(s, b, c, 1.5, -0.5, backend="jnp")
        assert y.shape == (4, 256, 16)
        for i in range(4):
            yi = sp.spmm(ts[i], b[i], c[i], 1.5, -0.5, backend="jnp")
            assert np.array_equal(np.asarray(y[i]), np.asarray(yi))

    def test_pallas_batch_grid_bit_identical(self, rng):
        _, ts = _mates(3, seed0=5)
        s = sp.stack_hflex(ts)
        b = jnp.asarray(rng.standard_normal((3, 200, 8)), jnp.float32)
        opts = dict(tn=8, interpret=True)
        y = sp.spmm(s, b, alpha=2.0, backend="pallas", **opts)
        for i in range(3):
            yi = sp.spmm(ts[i], b[i], alpha=2.0, backend="pallas", **opts)
            assert np.array_equal(np.asarray(y[i]), np.asarray(yi))

    def test_matches_dense_reference(self, rng):
        mats, ts = _mates(4, seed0=7)
        s = sp.stack_hflex(ts)
        b = rng.standard_normal((4, 200, 16)).astype(np.float32)
        c = rng.standard_normal((4, 256, 16)).astype(np.float32)
        y = np.asarray(sp.spmm(s, jnp.asarray(b), jnp.asarray(c), 1.25, 0.5,
                               backend="jnp"))
        ref = np.stack([spmm_reference(mats[i], b[i], c[i], 1.25, 0.5)
                        for i in range(4)])
        np.testing.assert_allclose(y, ref, rtol=2e-4,
                                   atol=2e-4 * np.abs(ref).max())

    def test_operand_validation(self, rng):
        _, ts = _mates(2)
        s = sp.stack_hflex(ts)
        b2 = jnp.zeros((200, 8), jnp.float32)
        with pytest.raises(ValueError, match=r"\(G, K, N\)"):
            sp.spmm(s, b2)                       # missing group axis
        with pytest.raises(ValueError, match=r"\(G, K, N\)"):
            sp.spmm(s, jnp.zeros((3, 200, 8), jnp.float32))   # wrong G

    def test_gradients_match_dense_oracle(self, rng):
        """Batched spmm grads vs the dense oracle on stacked inputs: the
        vjp reduces over the group axis correctly and padding-slot
        cotangents are masked per member."""
        mats, ts = _mates(3, seed0=11)
        s = sp.stack_hflex(ts)
        dense = np.stack([np.asarray(t.todense()) for t in ts])
        b = jnp.asarray(rng.standard_normal((3, 200, 8)), jnp.float32)
        c = jnp.asarray(rng.standard_normal((3, 256, 8)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((3, 256, 8)), jnp.float32)
        al, be = jnp.float32(1.5), jnp.float32(-0.25)

        def f(bb, cc, a_, b_):
            return (sp.spmm(s, bb, cc, a_, b_, backend="jnp") * w).sum()

        def f_dense(bb, cc, a_, b_):
            y = a_ * jnp.einsum("gmk,gkn->gmn", jnp.asarray(dense), bb) \
                + b_ * cc
            return (y * w).sum()

        g = jax.grad(f, argnums=(0, 1, 2, 3))(b, c, al, be)
        gd = jax.grad(f_dense, argnums=(0, 1, 2, 3))(b, c, al, be)
        for got, want in zip(g, gd):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-4, atol=2e-4)

    def test_padding_slot_grads_masked_per_member(self, rng):
        _, ts = _mates(3, seed0=13)
        s = sp.stack_hflex(ts)
        b = jnp.asarray(rng.standard_normal((3, 200, 8)), jnp.float32)

        dv = jax.grad(
            lambda v: sp.spmm(s.with_values(v), b, backend="jnp").sum()
        )(s.values)
        d = s.data
        slot = np.arange(d.lw).reshape(d.vals.shape[-2:])
        pad = slot >= np.asarray(d.nse)[..., None, None]
        assert bool(jnp.all(jnp.where(pad, dv, 0) == 0))
        assert int(pad.sum()) > 0    # the mask actually covers something


class TestPlanGroup:
    def test_one_dispatch_bit_identical(self, rng):
        """G >= 8 bucket-mates execute through ONE compiled-call dispatch,
        bit-identical to per-member plan execution."""
        _, ts = _mates(8, seed0=20)
        p = sp.plan_group(ts, 16, backend="jnp")
        assert p.group == 8
        b = jnp.asarray(rng.standard_normal((8, 200, 16)), jnp.float32)
        d0 = sp.PLAN_STATS["dispatches"]
        y = p.run(b)
        assert sp.PLAN_STATS["dispatches"] - d0 == 1
        for i in range(8):
            yi = sp.plan(ts[i], 16, backend="jnp").run(b[i])
            assert np.array_equal(np.asarray(y[i]), np.asarray(yi))

    def test_group_values_substitution(self, rng):
        _, ts = _mates(4, seed0=25)
        p = sp.plan_group(ts, 8, backend="jnp")
        b = jnp.asarray(rng.standard_normal((4, 200, 8)), jnp.float32)
        v2 = p.a.values * 3.0
        y2 = p.run(b, values=v2)
        y_ref = sp.spmm(p.a.with_values(v2), b, backend="jnp")
        assert np.array_equal(np.asarray(y2), np.asarray(y_ref))

    def test_group_bucket_mates_share_executable(self, rng):
        _, ts1 = _mates(4, seed0=30)
        _, ts2 = _mates(4, seed0=40)
        sp.plan_group(ts1, 8, backend="jnp")
        t0 = sp.BACKEND_STATS["traces"]
        h0 = sp.PLAN_STATS["exec_hits"]
        sp.plan_group(ts2, 8, backend="jnp")
        assert sp.BACKEND_STATS["traces"] == t0
        assert sp.PLAN_STATS["exec_hits"] == h0 + 1

    def test_group_plan_pallas_payload_path(self, rng):
        _, ts = _mates(3, seed0=45)
        p = sp.plan_group(ts, 8, backend="pallas", tn=8, interpret=True)
        b = jnp.asarray(rng.standard_normal((3, 200, 8)), jnp.float32)
        y = p.run(b)
        for i in range(3):
            yi = sp.spmm(ts[i], b[i], backend="pallas", tn=8, interpret=True)
            assert np.array_equal(np.asarray(y[i]), np.asarray(yi))

    def test_engine_spmm_group_stats(self, rng):
        _, ts = _mates(4, seed0=50)
        eng = SextansEngine(tm=64, k0=64, chunk=8, impl="jnp")
        b = jnp.asarray(rng.standard_normal((4, 200, 8)), jnp.float32)
        y = eng.spmm_group(ts, b)
        assert y.shape == (4, 256, 8)
        assert eng.stats.calls == 4
        assert eng.stats.dispatches == 1
        assert eng.stats.group_calls == 1
        # one executable serves all members: 1 miss + G-1 hits (HFlex)
        assert eng.stats.cache_misses == 1
        assert eng.stats.cache_hits == 3
        assert eng.stats.dispatches_per_call == 0.25


class TestScheduler:
    def _pool(self, rng, g=8):
        """g bucket-mates (ragged N inside one bucket) + 2 odd singletons."""
        reqs = []
        for i in range(g):
            a = power_law_sparse(256, 256, 5, seed=i)
            n = 12 if i % 2 else 16          # both pad to the N=16 bucket
            reqs.append(SpmmRequest(
                a=a, b=rng.standard_normal((256, n)).astype(np.float32),
                c=rng.standard_normal((256, n)).astype(np.float32),
                alpha=1.5, beta=-0.5))
        reqs.append(SpmmRequest(
            a=random_sparse(100, 180, 0.05, seed=90),
            b=rng.standard_normal((180, 16)).astype(np.float32)))
        reqs.append(SpmmRequest(
            a=random_sparse(400, 90, 0.02, seed=91),
            b=rng.standard_normal((90, 16)).astype(np.float32)))
        return reqs

    def test_group_of_8_is_one_dispatch_bit_identical(self, rng):
        """The acceptance pool: G=8 same-bucket requests -> exactly one
        compiled-call dispatch for the group; results bit-identical to
        per-request spmm."""
        reqs = self._pool(rng)
        eng = SextansEngine(tm=64, k0=64, chunk=8, impl="jnp")
        sched = SpmmScheduler(eng)
        tickets = [sched.submit(r) for r in reqs]
        assert tickets == list(range(10)) and sched.pending == 10
        d0 = sp.PLAN_STATS["dispatches"]
        outs = sched.flush()
        assert sched.pending == 0
        # 1 group dispatch (8 mates) + 2 singletons
        assert sched.stats["groups"] == 3
        assert sched.stats["dispatches"] == 3
        assert sp.PLAN_STATS["dispatches"] - d0 == 3
        assert eng.stats.group_calls == 1
        assert sched.batched_fraction == pytest.approx(0.8)
        assert sched.dispatches_per_request == pytest.approx(0.3)
        for r, o in zip(reqs, outs):
            t = sp.from_sparse_matrix(r.a, tm=64, k0=64, chunk=8, bucket=True)
            y = sp.spmm(t, jnp.asarray(r.b),
                        None if r.c is None else jnp.asarray(r.c),
                        r.alpha, r.beta, backend="jnp")
            assert o.shape == (r.a.shape[0], r.b.shape[1])
            assert np.array_equal(o, np.asarray(y))

    def test_ragged_shapes_group_via_embedding(self, rng):
        """Bucket-mates with different logical (M, K) stack through the
        bounding-shape embedding, bit-exactly."""
        a1 = random_sparse(60, 60, 0.01, seed=1)
        a2 = random_sparse(64, 64, 0.01, seed=2)
        eng = SextansEngine(tm=32, k0=64, chunk=8, impl="jnp")
        t1, t2 = eng.pack(a1), eng.pack(a2)
        if t1.geometry != t2.geometry:
            pytest.skip("lw buckets diverged for this seed")
        reqs = [
            SpmmRequest(a=a1, b=rng.standard_normal((60, 8)).astype(np.float32)),
            SpmmRequest(a=a2, b=rng.standard_normal((64, 8)).astype(np.float32)),
        ]
        sched = SpmmScheduler(eng)
        for r in reqs:
            sched.submit(r)
        outs = sched.flush()
        assert sched.stats["groups"] == 1           # they DID group
        assert sched.batched_fraction == 1.0
        for r, o in zip(reqs, outs):
            y = sp.spmm(sp.from_sparse_matrix(r.a, tm=32, k0=64, chunk=8,
                                              bucket=True),
                        jnp.asarray(r.b), backend="jnp")
            assert np.array_equal(o, np.asarray(y))

    def test_max_group_splits(self, rng):
        reqs = self._pool(rng)[:8]
        sched = SpmmScheduler(SextansEngine(tm=64, k0=64, chunk=8,
                                            impl="jnp"), max_group=3)
        for r in reqs:
            sched.submit(r)
        sched.flush()
        assert sched.stats["groups"] == 3           # 3 + 3 + 2
        assert sched.stats["batched_requests"] == 8

    def test_ragged_flushes_share_one_executable(self, rng):
        """Group embedding uses the geometry-constant (MB*TM, NW*K0)
        bounds, so ragged flushes whose largest member changes still hit
        one cached group executable (no per-flush recompile)."""
        eng = SextansEngine(tm=32, k0=64, chunk=8, impl="jnp")
        sched = SpmmScheduler(eng)

        def flush_pool(ms):
            for m in ms:
                a = random_sparse(m, 64, 0.01, seed=m)
                sched.submit(SpmmRequest(
                    a=a, b=rng.standard_normal((64, 8)).astype(np.float32)))
            return sched.flush()

        flush_pool([60, 58])                       # warm: compiles the group
        if sched.stats["batched_requests"] == 0:
            pytest.skip("lw buckets diverged for these seeds")
        m0 = sp.PLAN_STATS["exec_misses"]
        t0 = sp.BACKEND_STATS["traces"]
        flush_pool([61, 57])                       # different max member
        assert sp.PLAN_STATS["exec_misses"] == m0
        assert sp.BACKEND_STATS["traces"] == t0

    def test_submit_normalizes_and_validates(self, rng):
        sched = SpmmScheduler(SextansEngine(tm=32, k0=64, chunk=8,
                                            impl="jnp"))
        a = random_sparse(32, 32, 0.05, seed=1)
        # array-like b accepted and normalized
        sched.submit(SpmmRequest(a=a, b=[[1.0] * 8] * 32))
        outs = sched.flush()
        assert outs[0].shape == (32, 8)
        with pytest.raises(ValueError, match="2-D"):
            sched.submit(SpmmRequest(a=a, b=np.ones(32, np.float32)))
        with pytest.raises(ValueError, match="must be \\(M, N\\)"):
            sched.submit(SpmmRequest(a=a, b=np.ones((32, 8), np.float32),
                                     c=np.ones((8, 8), np.float32)))

    def test_flush_failure_restores_queue(self, rng):
        sched = SpmmScheduler(SextansEngine(tm=32, k0=64, chunk=8,
                                            impl="jnp"))
        good = SpmmRequest(a=random_sparse(32, 32, 0.05, seed=1),
                           b=np.ones((32, 8), np.float32))
        bad = SpmmRequest(a=random_sparse(32, 32, 0.05, seed=2),
                          b=np.ones((32, 8), np.float32))
        sched.submit(good)
        sched.submit(bad)
        bad.b = np.ones(7, np.float32)   # corrupt after submit-validation
        with pytest.raises(Exception):
            sched.flush()
        assert sched.pending == 2        # nothing silently dropped

    def test_serve_wrapper_stats_and_equivalence(self, rng):
        reqs = self._pool(rng)
        outs_b, st_b = serve_spmm_requests(
            reqs, SextansEngine(tm=64, k0=64, chunk=8, impl="jnp"),
            batched=True)
        outs_s, st_s = serve_spmm_requests(
            reqs, SextansEngine(tm=64, k0=64, chunk=8, impl="jnp"),
            batched=False)
        for x, y in zip(outs_b, outs_s):
            assert np.array_equal(x, y)
        assert st_b["batched_fraction"] > 0
        assert st_b["dispatches_per_request"] < 1.0
        assert st_s["batched_fraction"] == 0.0
        for st in (st_b, st_s):
            assert st["compute_gflops"] >= st["gflops"] > 0


class TestShardedEnginePlan:
    def test_shard_specs_structure(self):
        specs = SextansEngine.shard_specs()
        from jax.sharding import PartitionSpec as P

        assert specs["vals"] == P("data", None, None, None)
        assert specs["b"] == P(None, "model")
        assert specs["c"] == P("data", "model")

    def test_sharded_spmm_fn_1device_bit_exact(self, rng):
        """sharded_spmm_fn on a 1-device mesh: lower + run, bit-exact
        against the unsharded plan path (same backend body, same ops)."""
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        eng = SextansEngine(tm=32, k0=64, chunk=8, impl="jnp")
        a = power_law_sparse(96, 128, 4, seed=3)
        packed = eng.pack(a)
        b = jnp.asarray(rng.standard_normal((128, 8)), jnp.float32)
        c = jnp.asarray(rng.standard_normal((96, 8)), jnp.float32)
        fn = eng.sharded_spmm_fn(mesh, packed, 8, alpha=1.5, beta=0.5)
        out = fn(packed, b, c)
        assert fn.plan.mesh is mesh
        ref = eng.plan_for(packed, 8).run(b, c, 1.5, 0.5)
        assert np.array_equal(np.asarray(out), np.asarray(ref))
        refm = spmm_reference(a, np.asarray(b), np.asarray(c), 1.5, 0.5)
        np.testing.assert_allclose(np.asarray(out), refm, rtol=2e-4,
                                   atol=2e-4 * np.abs(refm).max())

    def test_sharded_values_substitution(self, rng):
        """fn(a, b, c) substitutes a's values into the planned structure
        (live weight update on the sharded path)."""
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        eng = SextansEngine(tm=32, k0=64, chunk=8, impl="jnp")
        a = random_sparse(64, 64, 0.05, seed=5)
        packed = eng.pack(a)
        b = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
        c = jnp.zeros((64, 8), jnp.float32)
        fn = eng.sharded_spmm_fn(mesh, packed, 8)
        y1 = fn(packed, b, c)
        y2 = fn(packed.with_values(packed.values * 2.0), b, c)
        np.testing.assert_allclose(np.asarray(y2), np.asarray(y1) * 2.0,
                                   rtol=1e-6, atol=1e-6)

    def test_sharded_rejects_structure_mismatch(self, rng):
        """fn(a, ...) must reject a structurally different matrix instead
        of silently executing its values against the planned indices."""
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        eng = SextansEngine(tm=32, k0=64, chunk=8, impl="jnp")
        packed = eng.pack(random_sparse(64, 64, 0.05, seed=5))
        other = eng.pack(random_sparse(64, 64, 0.05, seed=6))
        fn = eng.sharded_spmm_fn(mesh, packed, 8)
        b = jnp.zeros((64, 8), jnp.float32)
        c = jnp.zeros((64, 8), jnp.float32)
        with pytest.raises(ValueError, match="structure"):
            fn(other, b, c)
        # a re-packed copy of the SAME matrix is fine (content-checked once)
        same = eng.pack(random_sparse(64, 64, 0.05, seed=5))
        assert np.array_equal(np.asarray(fn(same, b, c)),
                              np.asarray(fn(packed, b, c)))

    @pytest.mark.parametrize("n", [16, 8])
    def test_row_split_four_devices_bit_exact(self, rng, n):
        """A Pallas mesh plan runs each device's own row blocks (shard_map
        over ``data``) at the lane N selects (128 columns for N = 16, the
        narrow 8 for N = 8): MB = 15 is padded to 16 with empty blocks,
        every device holds only its quarter of the slabs, and the result
        is bit-identical to the one-device plan."""
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        mesh = jax.make_mesh((4, 1), ("data", "model"))
        eng = SextansEngine(tm=64, k0=128, chunk=8, impl="pallas",
                            interpret=True)
        a = power_law_sparse(900, 700, 5, seed=7)
        packed = eng.pack(a, device=False)
        assert packed.data.mb == 15
        b = jnp.asarray(rng.standard_normal((700, n)), jnp.float32)
        c = jnp.asarray(rng.standard_normal((900, n)), jnp.float32)
        fn = eng.sharded_spmm_fn(mesh, packed, n, alpha=1.5, beta=-0.5)
        assert fn.plan.opts["tn"] == sp.hflex_lane(n)
        vals = fn.plan._operands[0]
        assert {s.data.shape[0] for s in vals.addressable_shards} == {4}
        out = np.asarray(fn(None, b, c))
        ref = eng.plan_for(packed, n).run(b, c, 1.5, -0.5)
        np.testing.assert_array_equal(out, np.asarray(ref))
        refm = spmm_reference(a, np.asarray(b), np.asarray(c), 1.5, -0.5)
        np.testing.assert_allclose(out, refm, rtol=2e-4,
                                   atol=2e-4 * np.abs(refm).max())
        # values substitution pads and places the new payload the same way
        y2 = np.asarray(fn(packed.with_values(packed.values * 2.0), b,
                           jnp.zeros_like(c)))
        y1 = np.asarray(fn(packed, b, jnp.zeros_like(c)))
        np.testing.assert_allclose(y2, 2.0 * y1, rtol=1e-6, atol=1e-5)

    def test_group_plan_carries_mesh(self, rng):
        """plan_group(..., mesh=...) — the multi-chip and batched paths
        unified on one plan abstraction."""
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        _, ts = _mates(4, seed0=60)
        p = sp.plan_group(ts, 8, backend="jnp", mesh=mesh)
        assert p.group == 4 and p.mesh is mesh
        b = jnp.asarray(rng.standard_normal((4, 200, 8)), jnp.float32)
        y = p.run(b)
        y_ref = sp.plan_group(ts, 8, backend="jnp").run(b)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-6, atol=1e-6)


class TestVectorEpilogue:
    """Per-member (G,) (alpha, beta) on batched spmm — the primitive the
    serving policy's epilogue folding stands on.  Each member's result
    must be bit-identical to its own scalar-epilogue call (same FMA, the
    scalar merely broadcast per member)."""

    def _pool(self, g=4, seed0=21):
        mats, ts = _mates(g, seed0=seed0)
        al = np.asarray([1.0, 0.5, 2.0, -1.5][:g], np.float32)
        be = np.asarray([0.0, 1.0, 0.5, 2.0][:g], np.float32)
        return mats, ts, al, be

    def test_jnp_bit_identical_to_scalar_members(self, rng):
        _, ts, al, be = self._pool()
        s = sp.stack_hflex(ts)
        b = jnp.asarray(rng.standard_normal((4, 200, 16)), jnp.float32)
        c = jnp.asarray(rng.standard_normal((4, 256, 16)), jnp.float32)
        y = sp.spmm(s, b, c, jnp.asarray(al), jnp.asarray(be),
                    backend="jnp")
        for i in range(4):
            yi = sp.spmm(ts[i], b[i], c[i], float(al[i]), float(be[i]),
                         backend="jnp")
            assert np.array_equal(np.asarray(y[i]), np.asarray(yi))

    def test_pallas_bit_identical_to_scalar_members(self, rng):
        _, ts, al, be = self._pool(3)
        s = sp.stack_hflex(ts)
        b = jnp.asarray(rng.standard_normal((3, 200, 8)), jnp.float32)
        c = jnp.asarray(rng.standard_normal((3, 256, 8)), jnp.float32)
        opts = dict(tn=8, interpret=True)
        y = sp.spmm(s, b, c, jnp.asarray(al[:3]), jnp.asarray(be[:3]),
                    backend="pallas", **opts)
        for i in range(3):
            yi = sp.spmm(ts[i], b[i], c[i], float(al[i]), float(be[i]),
                         backend="pallas", **opts)
            assert np.array_equal(np.asarray(y[i]), np.asarray(yi))

    def test_plan_group_vector_epilogue(self, rng):
        _, ts, al, be = self._pool()
        p = sp.plan_group(ts, 16, backend="jnp")
        b = jnp.asarray(rng.standard_normal((4, 200, 16)), jnp.float32)
        c = jnp.asarray(rng.standard_normal((4, 256, 16)), jnp.float32)
        y = p.run(b, c, jnp.asarray(al), jnp.asarray(be))
        s = sp.stack_hflex(ts)
        y2 = sp.spmm(s, b, c, jnp.asarray(al), jnp.asarray(be),
                     backend="jnp")
        assert np.array_equal(np.asarray(y), np.asarray(y2))

    def test_mixed_scalar_vector(self, rng):
        """One side scalar, the other a (G,) vector — the scalar side
        broadcasts, bit-identical to passing it as a constant vector."""
        _, ts, al, _ = self._pool()
        s = sp.stack_hflex(ts)
        b = jnp.asarray(rng.standard_normal((4, 200, 16)), jnp.float32)
        c = jnp.asarray(rng.standard_normal((4, 256, 16)), jnp.float32)
        y = sp.spmm(s, b, c, jnp.asarray(al), 0.5, backend="jnp")
        y2 = sp.spmm(s, b, c, jnp.asarray(al),
                     jnp.full((4,), 0.5, jnp.float32), backend="jnp")
        assert np.array_equal(np.asarray(y), np.asarray(y2))

    def test_vector_shape_validated(self, rng):
        _, ts, al, be = self._pool()
        s = sp.stack_hflex(ts)
        b = jnp.zeros((4, 200, 16), jnp.float32)
        with pytest.raises(ValueError):
            sp.spmm(s, b, alpha=jnp.asarray(al[:3]), backend="jnp")
        with pytest.raises(ValueError):
            sp.spmm(ts[0], jnp.zeros((200, 16), jnp.float32),
                    alpha=jnp.asarray(al), backend="jnp")

    def test_gradients_match_scalar_members(self, rng):
        """d/db and d/dvals of the vector-epilogue batched spmm equal the
        per-member scalar-epilogue grads."""
        _, ts, al, be = self._pool(3)
        s = sp.stack_hflex(ts)
        b = jnp.asarray(rng.standard_normal((3, 200, 8)), jnp.float32)
        c = jnp.asarray(rng.standard_normal((3, 256, 8)), jnp.float32)

        gb = jax.grad(lambda bb: sp.spmm(
            s, bb, c, jnp.asarray(al[:3]), jnp.asarray(be[:3]),
            backend="jnp").sum())(b)
        for i in range(3):
            gbi = jax.grad(lambda bb: sp.spmm(
                ts[i], bb, c[i], float(al[i]), float(be[i]),
                backend="jnp").sum())(b[i])
            np.testing.assert_allclose(np.asarray(gb[i]), np.asarray(gbi),
                                       rtol=1e-6, atol=1e-6)

        gv = jax.grad(lambda v: sp.spmm(
            s.with_values(v), b, c, jnp.asarray(al[:3]),
            jnp.asarray(be[:3]), backend="jnp").sum())(s.values)
        for i in range(3):
            gvi = jax.grad(lambda v: sp.spmm(
                ts[i].with_values(v), b[i], c[i], float(al[i]),
                float(be[i]), backend="jnp").sum())(ts[i].values)
            np.testing.assert_allclose(np.asarray(gv[i]), np.asarray(gvi),
                                       rtol=1e-6, atol=1e-6)
