"""Cost-model grouping policy contract tests.

Pins the :class:`repro.launch.policy.MergePolicy` decision surface in
isolation (pure host arithmetic, no engine): a constructed near-miss
LW-bucket pair merges when per-dispatch overhead dominates and splits
when padding waste dominates — both directions priced by
:func:`repro.core.perfmodel.packed_event_cycles`, no ad-hoc thresholds.
Also pins the merge-family identity (:func:`family_key` — only the
LW/block-count bucket and padded-N axes are merge-legal), the epilogue
fold gate (registered vector-epilogue backends only), the flusher's
``full_enough`` admission signal, the ``lw=`` flat-cost extension of
``packed_event_cycles``, and the inertness of
:func:`repro.sparse_api.repad_lw` (bit-identical spmm after widening).
"""

import numpy as np
import pytest

import repro.sparse_api as sp
from repro.core.perfmodel import packed_event_cycles
from repro.core.sparse import power_law_sparse, spmm_reference
from repro.launch.policy import (ABVEC_BACKENDS, GroupSketch,
                                 MergeCluster, MergePolicy, family_key)
from repro.core.hflex import slab_lw
from repro.sparse_api import Format, from_sparse_matrix, repad_lw


def _sketch(key, q, n=16, k0=64, lw=None, flat=False):
    q = np.asarray(q, np.int64)
    if q.ndim == 2:
        q = q[None]
    return GroupSketch(key=key, q=q,
                       n=n, k0=k0,
                       lw=int(q.max()) if lw is None else int(lw), flat=flat)


def _hflex_key(lw, n_b=16, ab=(None, None)):
    # mirrors SpmmScheduler._group_key's HFLEX layout:
    # (fmt, (mb, nw, lw, tm, k0, chunk, interleaved), None, n_b, dtype, a, b)
    return (Format.HFLEX, (2, 4, lw, 64, 64, 8, True), None, n_b,
            "<f4") + tuple(ab)


def _bsr_key(nb_b, n_b=16, ab=(None, None)):
    return (Format.BSR, (nb_b, 128, 128, 32, 32), (128, 128), n_b,
            "<f4") + tuple(ab)


# ---------------------------------------------------------------------------
# The merge/split contract — both directions from the same cost model
# ---------------------------------------------------------------------------


class TestMergeContract:
    def test_near_miss_pair_merges_when_overhead_dominates(self):
        """Tiny work per group + expensive dispatches: the cost model must
        decide that one padded dispatch beats two."""
        pol = MergePolicy(dispatch_overhead_cycles=1e6)
        a = _sketch(_hflex_key(64), np.full((2, 4), 60), lw=64)
        b = _sketch(_hflex_key(128), np.full((2, 4), 120), lw=128)
        assert pol.should_merge([a, b])
        plan = pol.plan_merges([a, b])
        assert len(plan) == 1
        (cl,) = plan
        assert sorted(cl.keys) == sorted([a.key, b.key])
        assert cl.lw == 128 and cl.saved_cycles > 0

    def test_near_miss_pair_splits_when_padding_dominates(self):
        """Free dispatches + a flat backend that walks every padded slot:
        widening the narrow group to the fat bucket costs more than the
        dispatch it saves — the same model must refuse the merge."""
        pol = MergePolicy(dispatch_overhead_cycles=1.0)
        a = _sketch(_hflex_key(64), np.full((8, 2, 4), 60), lw=64,
                    flat=True)
        b = _sketch(_hflex_key(8192), np.full((2, 4), 8000), lw=8192,
                    flat=True)
        assert not pol.should_merge([a, b])
        assert pol.plan_merges([a, b]) == []

    def test_decision_flips_with_overhead_alone(self):
        """Same sketches, only dispatch_overhead_cycles moves: the
        decision boundary belongs to the cost model, not a threshold."""
        a = _sketch(_hflex_key(64), np.full((4, 2, 4), 60), lw=64,
                    flat=True)
        b = _sketch(_hflex_key(1024), np.full((2, 4), 1000), lw=1024,
                    flat=True)
        merged = [MergePolicy(dispatch_overhead_cycles=d).should_merge(
            [a, b]) for d in (0.0, 1e9)]
        assert merged == [False, True]

    def test_pallas_lw_padding_free(self):
        """Trip-count backends (flat=False) never pay for LW padding, so
        any positive overhead makes the near-miss merge worthwhile."""
        pol = MergePolicy(dispatch_overhead_cycles=1.0)
        a = _sketch(_hflex_key(64), np.full((2, 4), 60), lw=64)
        b = _sketch(_hflex_key(8192), np.full((2, 4), 8000), lw=8192)
        assert pol.group_cycles(a, lw=8192) == pol.group_cycles(a)
        assert pol.should_merge([a, b])

    def test_merged_cycles_single_dispatch_overhead(self):
        pol = MergePolicy(dispatch_overhead_cycles=1e5)
        a = _sketch(_hflex_key(64), np.full((2, 4), 60), lw=64)
        b = _sketch(_hflex_key(64, n_b=32), np.full((2, 4), 60), lw=64,
                    n=32)
        split = pol.group_cycles(a) + pol.group_cycles(b)
        merged = pol.merged_cycles([a, b])
        # exactly one overhead charge dropped; members re-priced at the
        # union width N=32
        assert merged == pytest.approx(
            pol.group_cycles(a, n=32) + pol.group_cycles(b) - 1e5)
        assert merged < split

    def test_plan_respects_max_group(self):
        pol = MergePolicy(dispatch_overhead_cycles=1e9)
        sks = [_sketch(_hflex_key(64 * 2 ** i),
                       np.full((3, 2, 4), 60), lw=64 * 2 ** i)
               for i in range(3)]
        plan = pol.plan_merges(sks, max_group=6)
        assert plan and all(
            sum(3 for _ in cl.keys) <= 6 for cl in plan)
        assert pol.plan_merges(sks, max_group=3) == []

    def test_bsr_block_count_buckets_merge(self):
        pol = MergePolicy(dispatch_overhead_cycles=1e6)
        a = _sketch(_bsr_key(8), [[6]], lw=8, k0=32)
        b = _sketch(_bsr_key(16), [[14]], lw=16, k0=32)
        plan = pol.plan_merges([a, b])
        assert len(plan) == 1 and plan[0].lw == 16


# ---------------------------------------------------------------------------
# Merge families: which keys may ever share a dispatch
# ---------------------------------------------------------------------------


class TestFamilyKey:
    def test_lw_and_n_scrubbed(self):
        assert family_key(_hflex_key(64, n_b=16)) == family_key(
            _hflex_key(4096, n_b=64))

    def test_structural_axes_split_families(self):
        base = family_key(_hflex_key(64))
        mb = (Format.HFLEX, (4, 4, 64, 64, 64, 8, True), None, 16,
              "<f4", None, None)
        nw = (Format.HFLEX, (2, 8, 64, 64, 64, 8, True), None, 16,
              "<f4", None, None)
        assert family_key(mb) != base
        assert family_key(nw) != base

    def test_dtype_and_epilogue_split_families(self):
        assert family_key(_hflex_key(64)) != family_key(
            (Format.HFLEX, (2, 4, 64, 64, 64, 8, True), None, 16,
             "<f8", None, None))
        # unfolded scalar epilogues must match exactly to merge
        assert family_key(_hflex_key(64, ab=(1.0, 0.0))) != family_key(
            _hflex_key(64, ab=(2.0, 0.0)))
        assert family_key(_hflex_key(64, ab=(1.0, 0.0))) == family_key(
            _hflex_key(128, ab=(1.0, 0.0)))

    def test_bsr_block_bucket_scrubbed_tiling_kept(self):
        assert family_key(_bsr_key(8)) == family_key(_bsr_key(32))
        other_tile = (Format.BSR, (8, 128, 128, 64, 64), (128, 128), 16,
                      "<f4", None, None)
        assert family_key(_bsr_key(8)) != family_key(other_tile)

    def test_formats_never_mix(self):
        assert family_key(_hflex_key(64)) != family_key(_bsr_key(64))


# ---------------------------------------------------------------------------
# Epilogue fold gate + admission
# ---------------------------------------------------------------------------


class TestFoldGateAndAdmission:
    def test_fold_gate_matches_registry(self):
        pol = MergePolicy()
        for b in ABVEC_BACKENDS:
            assert pol.fold_epilogue(b)
        # unknown/custom backends conservatively keep scalars in the key
        assert not pol.fold_epilogue("my_custom_backend")

    def test_abvec_backends_are_registered(self):
        # both built-in backends fold: the kernel's SMEM (G, 2) epilogue
        # and jnp's broadcast one
        assert ABVEC_BACKENDS == set(sp.list_backends()) == {"pallas", "jnp"}

    def test_full_enough_grows_with_members(self):
        pol = MergePolicy(dispatch_overhead_cycles=5e3, fill_ratio=0.5)
        small = _sketch(_hflex_key(64), np.full((1, 2, 4), 8), lw=64)
        assert not pol.full_enough(small)
        big = _sketch(_hflex_key(64), np.full((64, 2, 4), 60), lw=64)
        assert pol.full_enough(big)
        # max_group is an unconditional admit
        assert pol.full_enough(small, max_group=1)

    def test_ctor_validation(self):
        with pytest.raises(ValueError):
            MergePolicy(dispatch_overhead_cycles=-1.0)
        with pytest.raises(ValueError):
            MergePolicy(fill_ratio=0.0)


# ---------------------------------------------------------------------------
# packed_event_cycles(lw=): the flat-cost pricing extension
# ---------------------------------------------------------------------------


class TestPackedEventCyclesLW:
    def test_lw_charges_full_slab_width(self):
        q = np.array([[3, 5], [7, 2]])
        base = packed_event_cycles(q, 16, k0=64)
        at_lw = packed_event_cycles(q, 16, k0=64, lw=64)
        full = packed_event_cycles(np.full_like(q, 64), 16, k0=64)
        assert at_lw == full > base

    def test_lw_monotone(self):
        q = np.array([[3, 5], [7, 2]])
        costs = [packed_event_cycles(q, 16, k0=64, lw=w)
                 for w in (8, 64, 512)]
        assert costs == sorted(costs) and costs[0] < costs[-1]

    def test_lw_none_is_trip_count(self):
        q = np.array([[3, 5], [7, 2]])
        assert packed_event_cycles(q, 16, k0=64) == packed_event_cycles(
            q, 16, k0=64, lw=None)


# ---------------------------------------------------------------------------
# repad_lw: the widening primitive merges rely on
# ---------------------------------------------------------------------------


class TestRepadLW:
    def test_bit_identical_spmm_after_widening(self, rng):
        a = power_law_sparse(96, 80, 4, seed=3)
        t = from_sparse_matrix(a, tm=32, k0=32, chunk=8, bucket=False)
        lw = t.geometry[2]
        wide = repad_lw(t, slab_lw(lw * 4))
        assert wide.geometry[2] == slab_lw(lw * 4) > lw
        assert wide.nse == t.nse
        np.testing.assert_array_equal(np.asarray(wide.data.q),
                                      np.asarray(t.data.q))
        b = rng.standard_normal((80, 8)).astype(np.float32)
        c = rng.standard_normal((96, 8)).astype(np.float32)
        for backend in ("pallas", "jnp"):
            y0 = np.asarray(sp.spmm(t, b, c, 1.5, 0.5, backend=backend))
            y1 = np.asarray(sp.spmm(wide, b, c, 1.5, 0.5, backend=backend))
            np.testing.assert_array_equal(y0, y1)
        np.testing.assert_allclose(
            y0, spmm_reference(a, b, c, 1.5, 0.5), rtol=1e-5, atol=1e-5)

    def test_padding_slots_inert_zero(self):
        a = power_law_sparse(64, 64, 3, seed=1)
        t = from_sparse_matrix(a, tm=32, k0=32, chunk=8, bucket=False)
        lw = t.geometry[2]
        wide = repad_lw(t, slab_lw(lw * 2))
        flat = wide.data.flat_slabs
        assert np.all(flat(np.asarray(wide.data.vals))[..., lw:] == 0.0)
        assert np.all(flat(np.asarray(wide.data.cols))[..., lw:] == 0)

    def test_noop_and_errors(self):
        a = power_law_sparse(64, 64, 3, seed=1)
        t = from_sparse_matrix(a, tm=32, k0=32, chunk=8, bucket=False)
        assert repad_lw(t, t.geometry[2]) is t
        with pytest.raises(ValueError):
            repad_lw(t, t.geometry[2] // 2)
        bsr = sp.from_dense(np.eye(64, dtype=np.float32),
                            format=Format.BSR, block=(32, 32))
        with pytest.raises(ValueError):
            repad_lw(bsr, 64)
        with pytest.raises(TypeError):
            repad_lw(np.eye(4), 64)
