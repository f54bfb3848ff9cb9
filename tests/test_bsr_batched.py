"""Grouped BSR execution tests: stack_bsr structure, batched spmm
(forward bit-identity on jnp + pallas, gradients vs the dense oracle,
padding-slot masking), group plans, the BSR serving lane of the
scheduler (one dispatch per bucket, packed-request passthrough), the
skinny-N routing table (BSR never takes the SpMV lane), DLMC-style
pattern generators, and the grouped model layers (SparseLinearGroup /
SparseMoE) end to end."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.sparse_api as sp
from repro.core.engine import SextansEngine
from repro.data.matrices import (
    DLMC_SPARSITIES, banded_pruned, block_random_pruned, dlmc_suite,
    magnitude_pruned)
from repro.launch.serve import SpmmRequest, SpmmScheduler, serve_spmm_requests

BLK = 16


def _bsr_pool(g=4, m=96, k=64, seed0=0, sparsity=0.75):
    """G same-geometry pruned weights: dense (m, k) numpy masks + packed
    BSR tensors.  Same sparsity -> exact same kept-block count."""
    dense, ts = [], []
    for i in range(g):
        w = magnitude_pruned(k, m, sparsity, block=(BLK, BLK),
                             seed=seed0 + i)          # (k, m) = (d_in, d_out)
        dense.append(np.asarray(w.T, np.float32))     # logical (M, K)
        ts.append(sp.from_dense(w.T, format=sp.Format.BSR,
                                block=(BLK, BLK)))
    return dense, ts


def _ragged_pool(seed0=0):
    """Members with different kept-block counts (still one stack)."""
    dense, ts = [], []
    for i, s in enumerate((0.70, 0.80, 0.90)):
        w = block_random_pruned(64, 96, s, block=(BLK, BLK), seed=seed0 + i)
        dense.append(np.asarray(w.T, np.float32))
        ts.append(sp.from_dense(w.T, format=sp.Format.BSR,
                                block=(BLK, BLK)))
    return dense, ts


class TestStackBsr:
    def test_stack_structure_and_batch_property(self):
        _, ts = _bsr_pool(4)
        s = sp.stack_bsr(ts)
        nb = ts[0].data.nb
        nb_pad = sp.bucket_block_count(nb)
        assert s.batch == 4
        assert s.shape == ts[0].shape
        assert s.data.blocks.shape == (4, nb_pad, BLK, BLK)
        assert s.data.brow.shape == (4, nb_pad)
        assert s.data.indptr.shape == (4, ts[0].data.indptr.shape[0])
        assert s.nnz == sum(t.nnz for t in ts)
        for gi in range(4):
            assert int(s.data.indptr[gi, -1]) == ts[gi].data.nb
        for t in ts:
            assert t.batch is None

    def test_ragged_members_pad_to_shared_bucket(self):
        _, ts = _ragged_pool()
        s = sp.stack_bsr(ts)
        nb_pad = sp.bucket_block_count(max(t.data.nb for t in ts))
        assert s.data.blocks.shape[1] == nb_pad
        for gi, t in enumerate(ts):
            nb = t.data.nb
            assert int(s.data.indptr[gi, -1]) == nb
            # padded slots: zero blocks, in-bounds brow
            assert np.all(np.asarray(s.data.blocks[gi, nb:]) == 0)
            assert np.all(np.asarray(s.data.brow[gi, nb:]) == 0)

    def test_unstack_round_trip(self):
        _, ts = _ragged_pool(seed0=5)
        s = sp.stack_bsr(ts)
        back = s.unstack()
        assert len(back) == 3
        for t, u in zip(ts, back):
            assert u.nnz == t.nnz
            assert np.array_equal(np.asarray(u.todense()),
                                  np.asarray(t.todense()))
        assert np.array_equal(np.asarray(s[1].todense()),
                              np.asarray(ts[1].todense()))

    def test_host_stack_matches_device_stack(self):
        _, ts = _bsr_pool(3, seed0=9)
        sh = sp.stack_bsr(ts, device=False)
        sd = sp.stack_bsr(ts)
        assert sh.on_host and not sd.on_host
        for leaf_h, leaf_d in zip(
                jax.tree_util.tree_leaves(sh.data),
                jax.tree_util.tree_leaves(sd.data)):
            assert np.array_equal(np.asarray(leaf_h), np.asarray(leaf_d))

    def test_bucket_block_count(self):
        assert sp.bucket_block_count(1) == 8
        assert sp.bucket_block_count(8) == 8
        assert sp.bucket_block_count(9) == 16
        assert sp.bucket_block_count(100) == 128

    def test_error_cases(self):
        _, ts = _bsr_pool(2)
        with pytest.raises(ValueError, match="at least one"):
            sp.stack_bsr([])
        with pytest.raises(ValueError, match="already-batched"):
            sp.stack_bsr([sp.stack_bsr(ts)])
        hf = sp.from_dense(np.eye(64, dtype=np.float32))
        with pytest.raises(ValueError, match="BSR"):
            sp.stack_bsr([hf])
        other = sp.from_dense(
            np.asarray(magnitude_pruned(64, 96, 0.75, block=(32, 32),
                                        seed=0).T, np.float32),
            format=sp.Format.BSR, block=(32, 32))
        with pytest.raises(ValueError, match="geometry"):
            sp.stack_bsr([ts[0], other])


class TestBatchedBsrSpmm:
    def test_jnp_bit_identical_per_member(self, rng):
        _, ts = _bsr_pool(4)
        s = sp.stack_bsr(ts)
        m, k = s.shape
        b = jnp.asarray(rng.standard_normal((4, k, 8)), jnp.float32)
        c = jnp.asarray(rng.standard_normal((4, m, 8)), jnp.float32)
        y = sp.spmm(s, b, c, 1.5, -0.5, backend="jnp")
        assert y.shape == (4, m, 8)
        for i in range(4):
            yi = sp.spmm(ts[i], b[i], c[i], 1.5, -0.5, backend="jnp")
            assert np.array_equal(np.asarray(y[i]), np.asarray(yi))

    def test_jnp_ragged_members_bit_identical(self, rng):
        _, ts = _ragged_pool(seed0=3)
        s = sp.stack_bsr(ts)
        m, k = s.shape
        b = jnp.asarray(rng.standard_normal((3, k, 8)), jnp.float32)
        y = sp.spmm(s, b, backend="jnp")
        for i in range(3):
            yi = sp.spmm(ts[i], b[i], backend="jnp")
            assert np.array_equal(np.asarray(y[i]), np.asarray(yi))

    def test_pallas_batch_grid_bit_identical(self, rng):
        _, ts = _ragged_pool(seed0=7)
        s = sp.stack_bsr(ts)
        m, k = s.shape
        b = jnp.asarray(rng.standard_normal((3, k, 8)), jnp.float32)
        opts = dict(tn=8, interpret=True)
        y = sp.spmm(s, b, alpha=2.0, backend="pallas", **opts)
        for i in range(3):
            yi = sp.spmm(ts[i], b[i], alpha=2.0, backend="pallas", **opts)
            assert np.array_equal(np.asarray(y[i]), np.asarray(yi))

    def test_matches_dense_reference(self, rng):
        dense, ts = _bsr_pool(4, seed0=11)
        s = sp.stack_bsr(ts)
        m, k = s.shape
        b = rng.standard_normal((4, k, 8)).astype(np.float32)
        y = np.asarray(sp.spmm(s, jnp.asarray(b), backend="jnp"))
        ref = np.einsum("gmk,gkn->gmn", np.stack(dense), b)
        np.testing.assert_allclose(y, ref, rtol=2e-4,
                                   atol=2e-4 * np.abs(ref).max())

    def test_gradients_match_dense_oracle(self, rng):
        """Grouped BSR grads vs the dense oracle (the acceptance
        criterion): d/d(blocks) reaches exactly the stored blocks,
        d/db matches the stacked dense einsum."""
        dense, ts = _ragged_pool(seed0=21)
        s = sp.stack_bsr(ts)
        m, k = s.shape
        b = jnp.asarray(rng.standard_normal((3, k, 8)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((3, m, 8)), jnp.float32)

        def f(vals, bb):
            return (sp.spmm(s.with_values(vals), bb, backend="jnp")
                    * w).sum()

        dvals, db = jax.grad(f, argnums=(0, 1))(s.values, b)

        def f_dense(dd, bb):
            return (jnp.einsum("gmk,gkn->gmn", dd, bb) * w).sum()

        dd, db_ref = jax.grad(f_dense, argnums=(0, 1))(
            jnp.asarray(np.stack(dense)), b)
        np.testing.assert_allclose(np.asarray(db), np.asarray(db_ref),
                                   rtol=2e-4, atol=2e-4)
        # scatter dvals back into dense block positions and compare with
        # the dense cotangent at the *stored* blocks
        ip = np.asarray(s.data.indptr)
        brow = np.asarray(s.data.brow)
        tk = s.data.tk
        for gi in range(3):
            nb = int(ip[gi, -1])
            bcol = np.searchsorted(ip[gi], np.arange(nb),
                                   side="right") - 1
            for bi in range(nb):
                r0, c0 = bcol[bi] * BLK, brow[gi, bi] * tk
                want = np.asarray(dd[gi, r0:r0 + BLK, c0:c0 + tk]).T
                got = np.asarray(dvals[gi, bi])
                np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_padding_slot_grads_masked_per_member(self, rng):
        _, ts = _ragged_pool(seed0=31)
        s = sp.stack_bsr(ts)
        _, k = s.shape
        b = jnp.asarray(rng.standard_normal((3, k, 8)), jnp.float32)
        dv = jax.grad(
            lambda v: sp.spmm(s.with_values(v), b, backend="jnp").sum()
        )(s.values)
        ip = np.asarray(s.data.indptr)
        npad = 0
        for gi in range(3):
            nb = int(ip[gi, -1])
            assert np.all(np.asarray(dv[gi, nb:]) == 0)
            npad += dv.shape[1] - nb
        assert npad > 0      # the mask actually covers something


class TestValidatorCoversGroups:
    def test_stacked_bsr_validates(self):
        from repro.analysis.validate import validate

        _, ts = _ragged_pool(seed0=41)
        validate(sp.stack_bsr(ts))

    def test_corrupt_padding_rejected(self):
        import dataclasses

        from repro.analysis.validate import InvariantViolation, validate

        _, ts = _ragged_pool(seed0=43)
        s = sp.stack_bsr(ts)
        blocks = np.asarray(s.data.blocks).copy()
        blocks[0, -1] = 1.0                      # padded slot must be zero
        bad = dataclasses.replace(
            s, data=dataclasses.replace(s.data, blocks=jnp.asarray(blocks)))
        with pytest.raises(InvariantViolation):
            validate(bad)

    def test_overflowing_true_count_rejected(self):
        import dataclasses

        from repro.analysis.validate import InvariantViolation, validate

        _, ts = _ragged_pool(seed0=47)
        s = sp.stack_bsr(ts)
        ip = np.asarray(s.data.indptr).copy()
        ip[1, -1] = s.data.blocks.shape[1] + 3   # claims more than NB_pad
        bad = dataclasses.replace(
            s, data=dataclasses.replace(s.data, indptr=jnp.asarray(ip)))
        with pytest.raises(InvariantViolation):
            validate(bad)

    def test_plan_time_hook(self, sextans_check):
        """SEXTANS_CHECK=1 validates stacked BSR at stack/plan time."""
        _, ts = _ragged_pool(seed0=51)
        s = sp.stack_bsr(ts)                     # maybe_validate fires here
        assert s.batch == 3


class TestSkinnyRoutingTable:
    """Pins the documented auto-policy table: the SpMV lane is HFLEX-only.
    BSR never routes to it, at ANY width — a skinny BSR matmul takes the
    tile kernel (pallas on TPU, jnp elsewhere)."""

    def _cases(self, rng):
        hf = sp.from_dense(
            np.asarray(rng.standard_normal((64, 64)), np.float32) *
            (rng.uniform(size=(64, 64)) < 0.05))
        w = magnitude_pruned(64, 96, 0.75, block=(BLK, BLK), seed=1)
        bsr = sp.from_dense(w.T, format=sp.Format.BSR, block=(BLK, BLK))
        grp = sp.stack_bsr([bsr, bsr.with_values(bsr.values * 2.0)])
        return hf, bsr, grp

    @pytest.mark.parametrize("n", [1, 4, 8, 64])
    def test_bsr_never_takes_spmv_lane(self, rng, n):
        _, bsr, grp = self._cases(rng)
        for platform in ("cpu", "tpu"):
            for t, bshape in ((bsr, (64, n)), (grp, (2, 64, n))):
                picked = sp.resolve_backend(
                    "auto", t, jnp.zeros(bshape, jnp.float32),
                    platform=platform)
                assert not sp.is_skinny(t, picked, n)
                assert picked == ("pallas" if platform == "tpu" else "jnp")

    def test_hflex_skinny_does_take_the_lane(self, rng):
        hf, _, _ = self._cases(rng)
        for n, skinny in ((4, True), (64, False)):
            picked = sp.resolve_backend(
                "auto", hf, jnp.zeros((64, n), jnp.float32), platform="cpu")
            assert picked == "jnp"
            assert sp.is_skinny(hf, picked, n) == skinny
        assert sp.resolve_backend(
            "auto", hf, jnp.zeros((64, 4), jnp.float32),
            platform="tpu") == "pallas"
        assert sp.hflex_lane(4) == 8


class TestDlmcGenerators:
    @pytest.mark.parametrize("fn", [magnitude_pruned, banded_pruned,
                                    block_random_pruned])
    @pytest.mark.parametrize("s", DLMC_SPARSITIES)
    def test_exact_block_count_and_seeded(self, fn, s):
        w = fn(128, 192, s, block=(BLK, BLK), seed=3)
        assert w.shape == (128, 192) and w.dtype == np.float32
        norms = np.linalg.norm(
            w.reshape(8, BLK, 12, BLK), axis=(1, 3))
        exp = max(1, round((1 - s) * norms.size))
        assert (norms > 0).sum() == exp
        assert np.array_equal(w, fn(128, 192, s, block=(BLK, BLK), seed=3))
        assert not np.array_equal(
            w, fn(128, 192, s, block=(BLK, BLK), seed=4))

    def test_bsr_packs_with_zero_fill_in(self):
        for e in dlmc_suite(64, 96, block=(BLK, BLK),
                            sparsities=(0.80, 0.95)):
            t = sp.from_dense(e.weight.T, format=sp.Format.BSR,
                              block=(BLK, BLK))
            exp = max(1, round((1 - e.sparsity) * (64 // BLK) * (96 // BLK)))
            assert t.data.nb == exp
        assert len(dlmc_suite(64, 96, block=(BLK, BLK))) == 15

    def test_same_sparsity_members_stack_unpadded(self):
        """Equal sparsity -> equal kept-block count across patterns, so a
        mixed-pattern pool stacks into one bucket."""
        ws = [fn(64, 96, 0.90, block=(BLK, BLK), seed=i)
              for i, fn in enumerate(
                  (magnitude_pruned, banded_pruned, block_random_pruned))]
        ts = [sp.from_dense(w.T, format=sp.Format.BSR, block=(BLK, BLK))
              for w in ws]
        assert len({t.data.nb for t in ts}) == 1
        assert sp.stack_bsr(ts).batch == 3


class TestPlanGroupBsr:
    def test_one_dispatch_bit_identical(self, rng):
        _, ts = _bsr_pool(8, seed0=60)
        p = sp.plan_group(ts, 16, backend="jnp")
        assert p.group == 8
        m, k = ts[0].shape
        b = jnp.asarray(rng.standard_normal((8, k, 16)), jnp.float32)
        d0 = sp.PLAN_STATS["dispatches"]
        y = p.run(b)
        assert sp.PLAN_STATS["dispatches"] - d0 == 1
        for i in range(8):
            yi = sp.plan(ts[i], 16, backend="jnp").run(b[i])
            assert np.array_equal(np.asarray(y[i]), np.asarray(yi))

    def test_group_values_substitution(self, rng):
        _, ts = _ragged_pool(seed0=70)
        p = sp.plan_group(ts, 8, backend="jnp")
        _, k = ts[0].shape
        b = jnp.asarray(rng.standard_normal((3, k, 8)), jnp.float32)
        v2 = p.a.values * 3.0
        y2 = p.run(b, values=v2)
        y_ref = sp.spmm(p.a.with_values(v2), b, backend="jnp")
        assert np.array_equal(np.asarray(y2), np.asarray(y_ref))

    def test_engine_spmm_group_bsr(self, rng):
        _, ts = _bsr_pool(4, seed0=80)
        eng = SextansEngine(tm=64, k0=64, chunk=8, impl="jnp")
        m, k = ts[0].shape
        b = jnp.asarray(rng.standard_normal((4, k, 8)), jnp.float32)
        y = eng.spmm_group(ts, b)
        assert y.shape == (4, m, 8)
        assert eng.stats.dispatches == 1
        assert eng.stats.group_calls == 1


class TestBsrScheduler:
    def _pool(self, rng, g=8, sparsity=0.90, n=16, seed0=0):
        reqs = []
        patterns = (magnitude_pruned, banded_pruned, block_random_pruned)
        for i in range(g):
            w = patterns[i % 3](64, 96, sparsity, block=(BLK, BLK),
                                seed=seed0 + i)
            reqs.append(SpmmRequest(
                a=sp.from_dense(w.T, format=sp.Format.BSR,
                                block=(BLK, BLK)),
                b=rng.standard_normal((64, n)).astype(np.float32)))
        return reqs

    def test_group_of_8_is_one_dispatch_bit_identical(self, rng):
        """The acceptance pool: G=8 same-geometry BSR weights flush as
        ONE grouped dispatch (dispatches/request <= 0.25), bit-identical
        to per-request spmm."""
        reqs = self._pool(rng)
        eng = SextansEngine(tm=64, k0=64, chunk=8, impl="jnp")
        sched = SpmmScheduler(eng)
        for r in reqs:
            sched.submit(r)
        outs = sched.flush()
        assert sched.stats["groups"] == 1
        assert sched.stats["dispatches"] == 1
        assert sched.stats["batched_requests"] == 8
        assert sched.dispatches_per_request <= 0.25
        assert sched.batched_fraction == 1.0
        for r, o in zip(reqs, outs):
            y = sp.spmm(r.a, jnp.asarray(r.b), backend="jnp")
            assert np.array_equal(o, np.asarray(y))

    def test_mixed_sparsities_group_by_bucket(self, rng):
        """Ragged kept-block counts spread over power-of-two buckets:
        dispatches = occupied buckets, not requests."""
        reqs = []
        for i, s in enumerate((0.70, 0.70, 0.90, 0.90, 0.95, 0.95)):
            w = magnitude_pruned(64, 96, s, block=(BLK, BLK), seed=i)
            reqs.append(SpmmRequest(
                a=sp.from_dense(w.T, format=sp.Format.BSR,
                                block=(BLK, BLK)),
                b=rng.standard_normal((64, 8)).astype(np.float32)))
        nbuckets = len({sp.bucket_block_count(r.a.data.nb) for r in reqs})
        sched = SpmmScheduler(SextansEngine(tm=64, k0=64, chunk=8,
                                            impl="jnp"))
        for r in reqs:
            sched.submit(r)
        outs = sched.flush()
        assert sched.stats["groups"] == nbuckets
        assert sched.stats["batched_requests"] == len(reqs)
        for r, o in zip(reqs, outs):
            y = sp.spmm(r.a, jnp.asarray(r.b), backend="jnp")
            assert np.array_equal(o, np.asarray(y))

    def test_mixed_hflex_and_bsr_pool(self, rng):
        """BSR groups coexist with HFLEX bucket groups in one flush."""
        from repro.core.sparse import power_law_sparse

        reqs = self._pool(rng, g=4)
        for i in range(4):
            reqs.append(SpmmRequest(
                a=power_law_sparse(96, 64, 5, seed=i),
                b=rng.standard_normal((64, 16)).astype(np.float32)))
        eng = SextansEngine(tm=64, k0=64, chunk=8, impl="jnp")
        sched = SpmmScheduler(eng)
        for r in reqs:
            sched.submit(r)
        outs = sched.flush()
        assert sched.stats["groups"] == 2
        assert sched.stats["batched_requests"] == 8
        for r, o in zip(reqs[:4], outs[:4]):
            y = sp.spmm(r.a, jnp.asarray(r.b), backend="jnp")
            assert np.array_equal(o, np.asarray(y))

    def test_async_pipeline_bit_identical(self, rng):
        reqs = self._pool(rng, seed0=30)
        eng = SextansEngine(tm=64, k0=64, chunk=8, impl="jnp")
        sched = SpmmScheduler(eng, async_pipeline=True)
        futs = [sched.submit(r) for r in reqs]
        sched.flush()
        for r, f in zip(reqs, futs):
            y = sp.spmm(r.a, jnp.asarray(r.b), backend="jnp")
            assert np.array_equal(f.result(), np.asarray(y))
        assert sched.stats["dispatches"] == 1

    def test_serve_wrapper_grouped_vs_sequential(self, rng):
        reqs = self._pool(rng, seed0=40)
        outs_b, st_b = serve_spmm_requests(
            reqs, SextansEngine(tm=64, k0=64, chunk=8, impl="jnp"),
            batched=True)
        outs_s, st_s = serve_spmm_requests(
            reqs, SextansEngine(tm=64, k0=64, chunk=8, impl="jnp"),
            batched=False)
        for x, y in zip(outs_b, outs_s):
            assert np.array_equal(x, y)
        assert st_b["batched_fraction"] == 1.0
        assert st_b["dispatches_per_request"] <= 0.25
        assert st_s["batched_fraction"] == 0.0
        assert st_b["gflops"] > 0 and st_s["gflops"] > 0


class TestGroupedLayers:
    def _cfg(self, **kw):
        from repro.models.common import ModelConfig

        base = dict(name="t", family="moe", num_layers=1, d_model=32,
                    num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64,
                    num_experts=4, experts_per_token=2, moe_group_size=16)
        base.update(kw)
        return ModelConfig(**base)

    def _init(self, seed=0):
        from repro.models.common import Initializer

        return Initializer(seed, jnp.float32)

    def test_sparse_linear_group_matches_members(self, rng):
        from repro.models.layers import SparseLinear, SparseLinearGroup

        layers, params = zip(*[
            SparseLinear.create(self._init(10 + i), 32, 64,
                                block=(BLK, BLK), density=0.5)
            for i in range(6)])
        grp = SparseLinearGroup(layers)
        assert grp.batch == 6 and grp.skeleton.batch == 6
        x = jnp.asarray(rng.standard_normal((8, 32)), jnp.float32)
        y = grp(list(params), x)
        assert y.shape == (6, 8, 64)
        for i, (l, p) in enumerate(zip(layers, params)):
            assert np.array_equal(np.asarray(y[i]), np.asarray(l(p, x)))
        y_plan = grp(list(params), x, use_plan=True)
        assert np.array_equal(np.asarray(y_plan), np.asarray(y))

    def test_sparse_linear_group_one_dispatch(self, rng):
        from repro.models.layers import SparseLinear, SparseLinearGroup

        layers, params = zip(*[
            SparseLinear.create(self._init(20 + i), 32, 64,
                                block=(BLK, BLK), density=0.5)
            for i in range(4)])
        grp = SparseLinearGroup(layers)
        x = jnp.asarray(rng.standard_normal((8, 32)), jnp.float32)
        grp(list(params), x, use_plan=True)      # warm the plan cache
        d0 = sp.PLAN_STATS["dispatches"]
        grp(list(params), x, use_plan=True)
        assert sp.PLAN_STATS["dispatches"] - d0 == 1

    def test_sparse_linear_group_scheduler_submit(self, rng):
        from repro.models.layers import SparseLinear, SparseLinearGroup

        layers, params = zip(*[
            SparseLinear.create(self._init(30 + i), 32, 64,
                                block=(BLK, BLK), density=0.5)
            for i in range(8)])
        grp = SparseLinearGroup(layers)
        x = rng.standard_normal((8, 32)).astype(np.float32)
        sched = SpmmScheduler(SextansEngine(tm=64, k0=64, chunk=8,
                                            impl="jnp"))
        grp.submit(sched, list(params), x)
        outs = sched.flush()
        assert sched.stats["dispatches"] == 1
        assert sched.dispatches_per_request <= 0.25
        xj = jnp.asarray(x)
        for (l, p), o in zip(zip(layers, params), outs):
            assert np.array_equal(o, np.asarray(l(p, xj)).T)

    def test_sparse_moe_grouped_end_to_end(self, rng):
        """Acceptance: sparse-MoE expert matrices route through the
        grouped lane — 3 grouped spmm dispatches per apply, output
        matches a per-expert dense-oracle recomputation."""
        from repro.models.common import compute_dtype
        from repro.models.layers import SparseMoE, _act, _moe_route

        cfg = self._cfg()
        moe, p = SparseMoE.create(self._init(), cfg, block=(BLK, BLK),
                                  density=0.5)
        assert moe.num_experts == 4
        assert moe.wi.batch == moe.wg.batch == moe.wo.batch == 4
        x = jnp.asarray(rng.standard_normal((2, 16, 32)), jnp.float32)
        y = moe.apply(p, cfg, x)
        assert y.shape == (2, 16, 32)

        # dense-oracle recomputation of the expert stage
        dtype = compute_dtype(cfg)
        xt = x.reshape(-1, cfg.moe_group_size, 32)
        combine, dispatch, cap = _moe_route(p["router"], cfg, xt, dtype)
        ein = jnp.einsum("gtd,gtec->gecd", xt.astype(dtype), dispatch)
        wi_d = jnp.stack([moe.wi.with_values(p["wi"])[e].todense().T
                          for e in range(4)])
        wg_d = jnp.stack([moe.wg.with_values(p["wg"])[e].todense().T
                          for e in range(4)])
        wo_d = jnp.stack([moe.wo.with_values(p["wo"])[e].todense().T
                          for e in range(4)])
        act = _act(cfg.act)
        h = act(jnp.einsum("gecd,edf->gecf", ein, wg_d)) * jnp.einsum(
            "gecd,edf->gecf", ein, wi_d)
        eout = jnp.einsum("gecf,efd->gecd", h, wo_d)
        y_ref = jnp.einsum("gecd,gtec->gtd", eout, combine).reshape(2, 16, 32)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-4)

    def test_sparse_moe_trains_with_masked_padding(self, rng):
        from repro.models.layers import SparseMoE

        cfg = self._cfg()
        moe, p = SparseMoE.create(self._init(1), cfg, block=(BLK, BLK),
                                  density=0.4)
        x = jnp.asarray(rng.standard_normal((1, 16, 32)), jnp.float32)

        grads = jax.grad(lambda pp: moe.apply(pp, cfg, x).sum())(p)
        ip = np.asarray(moe.wi.data.indptr)
        for proj, t in (("wi", moe.wi), ("wg", moe.wg), ("wo", moe.wo)):
            g = np.asarray(grads[proj])
            ipp = np.asarray(t.data.indptr)
            assert np.abs(g).sum() > 0
            for gi in range(t.batch):
                assert np.all(g[gi, int(ipp[gi, -1]):] == 0)
        assert np.abs(np.asarray(grads["router"])).sum() > 0

    def test_sparse_moe_with_shared_expert(self, rng):
        from repro.models.layers import SparseMoE

        cfg = self._cfg(shared_expert=True, shared_expert_ff=32)
        moe, p = SparseMoE.create(self._init(2), cfg, block=(BLK, BLK),
                                  density=0.5)
        assert "shared" in p
        x = jnp.asarray(rng.standard_normal((1, 16, 32)), jnp.float32)
        assert moe.apply(p, cfg, x).shape == (1, 16, 32)


class TestBsrVectorEpilogue:
    """Per-member (G,) (alpha, beta) on batched BSR spmm — bit-identical
    to each member's own scalar-epilogue call (the BSR leg of the serving
    policy's epilogue folding)."""

    def test_jnp_bit_identical_to_scalar_members(self, rng):
        _, ts = _bsr_pool(4, seed0=31)
        s = sp.stack_bsr(ts)
        m, k = s.shape
        al = np.asarray([1.0, 0.5, 2.0, -1.5], np.float32)
        be = np.asarray([0.0, 1.0, 0.5, 2.0], np.float32)
        b = jnp.asarray(rng.standard_normal((4, k, 8)), jnp.float32)
        c = jnp.asarray(rng.standard_normal((4, m, 8)), jnp.float32)
        y = sp.spmm(s, b, c, jnp.asarray(al), jnp.asarray(be),
                    backend="jnp")
        for i in range(4):
            yi = sp.spmm(ts[i], b[i], c[i], float(al[i]), float(be[i]),
                         backend="jnp")
            assert np.array_equal(np.asarray(y[i]), np.asarray(yi))

    def test_pallas_bit_identical_to_scalar_members(self, rng):
        _, ts = _bsr_pool(3, seed0=41)
        s = sp.stack_bsr(ts)
        m, k = s.shape
        al = np.asarray([2.0, 0.5, 1.0], np.float32)
        be = np.asarray([1.0, 0.0, 0.5], np.float32)
        b = jnp.asarray(rng.standard_normal((3, k, 8)), jnp.float32)
        c = jnp.asarray(rng.standard_normal((3, m, 8)), jnp.float32)
        opts = dict(interpret=True)
        y = sp.spmm(s, b, c, jnp.asarray(al), jnp.asarray(be),
                    backend="pallas", **opts)
        for i in range(3):
            yi = sp.spmm(ts[i], b[i], c[i], float(al[i]), float(be[i]),
                         backend="pallas", **opts)
            assert np.array_equal(np.asarray(y[i]), np.asarray(yi))
