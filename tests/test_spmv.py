"""Skinny-N SpMV lane tests.

Acceptance criteria of the vector lane:

* ``pallas`` on HFLEX takes its column tile from N (``hflex_lane``): the
  narrow lane, 8·⌈N/8⌉, for N <= ``skinny_n_max()``, else 128; an
  explicit ``tn=`` wins.  The narrow lane is **bit-identical** to the
  128-column kernel (per-column math is shared discipline);
* the default ``auto`` policy picks the backend from platform, format and
  density only — ``pallas`` on TPU, ``jnp`` elsewhere — and the lane
  follows N (the (backend, lane) table is pinned below);
* plans, the engine and the serving scheduler count SpMV-shaped
  dispatches (``is_skinny`` -> ``skinny_dispatches``), and the lane
  streams and differentiates.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.sparse_api as sp
from repro.core.sparse import power_law_sparse, spmm_reference
from repro.sparse_api.backends import _default_auto_policy

TALL_OPTS = dict(tn=16, interpret=True)


def _packed(m=300, k=500, seed=1, n=5, tm=64, k0=64):
    rng = np.random.default_rng(seed)
    a = power_law_sparse(m, k, 6, seed=seed)
    A = sp.from_sparse_matrix(a, tm=tm, k0=k0, chunk=8, bucket=True)
    b = rng.standard_normal((k, n)).astype(np.float32)
    c = rng.standard_normal((m, n)).astype(np.float32)
    return a, A, b, c


def _route(a, n, platform):
    """(backend, HFLEX column tile) the default policy gives a width-``n``
    call; the tile is None off the kernel."""
    backend = _default_auto_policy(a, np.zeros((a.shape[1], n), np.float32),
                                   platform=platform)
    return backend, (sp.hflex_lane(n) if backend == "pallas" else None)


class TestSpmvKernel:
    @pytest.mark.parametrize("n", [1, 3, 5, 8])
    def test_bit_identical_to_tall_n_kernel(self, n):
        """The lane drops the NT grid dimension but keeps the per-column
        math — results match the tall-N kernel bit for bit."""
        _, A, b, c = _packed(n=n)
        y_tall = np.asarray(sp.spmm(A, b, c, 1.25, -0.5, backend="pallas",
                                    **TALL_OPTS))
        y_v = np.asarray(sp.spmm(A, b, c, 1.25, -0.5, backend="pallas",
                                 interpret=True))
        np.testing.assert_array_equal(y_v, y_tall)

    def test_onehot_gather_variant(self):
        """The lane is a width, not a name: ``pallas`` at N = 5 runs the
        one-hot kernel at TN = 8, exactly as an explicit ``tn=8`` does,
        and an explicit ``tn=`` wins over the lane."""
        _, A, b, c = _packed()
        assert sp.hflex_lane(b.shape[1]) == 8
        y_lane = np.asarray(sp.spmm(A, b, c, 2.0, 0.5, backend="pallas",
                                    interpret=True))
        y_tn8 = np.asarray(sp.spmm(A, b, c, 2.0, 0.5, backend="pallas",
                                   tn=8, interpret=True))
        np.testing.assert_array_equal(y_lane, y_tn8)
        y_tall = np.asarray(sp.spmm(A, b, c, 2.0, 0.5, backend="pallas",
                                    **TALL_OPTS))
        np.testing.assert_array_equal(y_lane, y_tall)

    def test_matches_reference(self):
        a, A, b, c = _packed(seed=3)
        ref = spmm_reference(a, b, c, 1.5, -0.25)
        y = np.asarray(sp.spmm(A, b, c, 1.5, -0.25, backend="pallas",
                               interpret=True))
        np.testing.assert_allclose(y, ref, rtol=2e-4,
                                   atol=2e-4 * max(1, np.abs(ref).max()))

    def test_batched_group_bit_identical_per_member(self):
        rng = np.random.default_rng(0)
        _, A1, b1, _ = _packed(seed=1)
        _, A2, _, _ = _packed(seed=2)
        S = sp.stack_hflex([A1, A2])
        bg = np.stack([b1, rng.standard_normal(b1.shape).astype(np.float32)])
        yg = np.asarray(sp.spmm(S, bg, backend="pallas", interpret=True))
        for i, Ai in enumerate((A1, A2)):
            np.testing.assert_array_equal(
                yg[i], np.asarray(sp.spmm(Ai, bg[i], backend="pallas",
                                          interpret=True)))

    def test_streams_through_spmv_hooks(self):
        """The kernel's StreamOps carry the raw f32 accumulator bit-exactly
        at the narrow lane — the out-of-core tier works at vector widths
        too."""
        _, A, b, c = _packed()
        y_res = np.asarray(sp.spmm(A, b, c, 1.25, -0.5, backend="pallas",
                                   interpret=True))
        P = sp.plan(A, b.shape[1], backend="pallas", stream=True,
                    window_chunk=3, interpret=True)
        np.testing.assert_array_equal(np.asarray(P.run(b, c, 1.25, -0.5)),
                                      y_res)

    def test_rejects_bsr(self):
        """The lane is HFLEX's: a skinny BSR product keeps the tile
        kernel's 128-row x tile, is not counted as SpMV-shaped, and
        matches the dense product."""
        rng = np.random.default_rng(0)
        w = rng.standard_normal((64, 96)).astype(np.float32)
        B = sp.from_dense(w, format=sp.Format.BSR, block=(16, 16))
        x = rng.standard_normal((96, 4)).astype(np.float32)
        assert not sp.is_skinny(B, "pallas", 4)
        y = np.asarray(sp.spmm(B, x, backend="pallas", interpret=True))
        np.testing.assert_allclose(y, w @ x, rtol=2e-4, atol=1e-4)


class TestSpmvJnpTwin:
    def test_bit_identical_to_jnp(self):
        """Off-TPU a skinny call resolves to ``jnp`` itself: ``auto`` and
        an explicit ``jnp`` are one executable."""
        _, A, b, c = _packed()
        assert sp.resolve_backend("auto", A, b, platform="cpu") == "jnp"
        y_j = np.asarray(sp.spmm(A, b, c, 1.25, -0.5, backend="jnp"))
        y_v = np.asarray(sp.spmm(A, b, c, 1.25, -0.5, backend="auto"))
        np.testing.assert_array_equal(y_v, y_j)

    def test_grads_match_dense_oracle(self):
        _, A, b_np, c_np = _packed(seed=2)
        b, c = jnp.asarray(b_np), jnp.asarray(c_np)

        def loss(v):
            return jnp.sum(jnp.sin(sp.spmm(A.with_values(v), b, c, 1.3, 0.7,
                                           backend="auto")))

        def loss_dense(v):
            return jnp.sum(jnp.sin(1.3 * A.with_values(v).todense() @ b
                                   + 0.7 * c))

        g = jax.grad(loss)(A.values)
        gd = jax.grad(loss_dense)(A.values)
        valid = (np.arange(A.data.lw).reshape(A.data.vals.shape[-2:])
                 < np.asarray(A.data.nse)[:, :, None, None])
        np.testing.assert_allclose(np.asarray(g)[valid],
                                   np.asarray(gd)[valid],
                                   rtol=1e-4, atol=1e-4)


class TestAutoPolicyTable:
    """Pins the default ``auto`` dispatch table as (backend, lane) pairs."""

    def _A(self, density=0.05):
        m, k = 64, 128
        rng = np.random.default_rng(0)
        nnz = max(1, int(m * k * density))
        d = np.zeros((m, k), np.float32)
        d[rng.integers(0, m, nnz), rng.integers(0, k, nnz)] = 1.0
        return sp.from_dense(d, tm=32, k0=32, chunk=8)

    @pytest.mark.parametrize("platform,n,expect", [
        # skinny HFLEX: the kernel's narrow lane on TPU, jnp elsewhere
        ("tpu", 1, ("pallas", 8)),
        ("tpu", sp.SKINNY_N_MAX, ("pallas", 8)),
        ("cpu", 1, ("jnp", None)),
        ("cpu", sp.SKINNY_N_MAX, ("jnp", None)),
        # one past the threshold: the 128-column lane
        ("tpu", sp.SKINNY_N_MAX + 1, ("pallas", 128)),
        ("cpu", sp.SKINNY_N_MAX + 1, ("jnp", None)),
        ("gpu", 64, ("jnp", None)),
    ])
    def test_hflex_width_split(self, platform, n, expect):
        assert _route(self._A(), n, platform) == expect

    def test_unknown_width_keeps_old_rules(self):
        A = self._A()
        assert _default_auto_policy(A, None, platform="tpu") == "pallas"
        assert _default_auto_policy(A, None, platform="cpu") == "jnp"

    def test_dense_ish_tpu_overrides_skinny(self):
        """On TPU the density>0.25 rule wins over the narrow lane (slab
        padding blows up the kernel at any width); off-TPU it is ``jnp``
        either way, and the call still counts as SpMV-shaped."""
        A = self._A(density=0.5)
        assert A.density > 0.25
        assert _route(A, 4, "tpu") == ("jnp", None)
        assert _route(A, 4, "cpu") == ("jnp", None)
        assert sp.is_skinny(A, "jnp", 4)

    def test_bsr_never_takes_the_lane(self):
        rng = np.random.default_rng(0)
        B = sp.from_dense(rng.standard_normal((64, 96)).astype(np.float32),
                          format=sp.Format.BSR, block=(16, 16))
        b = np.zeros((96, 4), np.float32)
        assert _default_auto_policy(B, b, platform="tpu") == "pallas"
        assert _default_auto_policy(B, b, platform="cpu") == "jnp"
        assert not sp.is_skinny(B, "pallas", 4)

    def test_operand_width(self):
        """The lane width table: 8·⌈N/8⌉ up to the threshold, 128 above
        it, and ``narrow=`` forces either side."""
        assert [sp.hflex_lane(n) for n in (1, 5, 8, 9, 128, 1000)] == [
            8, 8, 8, 128, 128, 128]
        assert sp.hflex_lane(20, narrow=True) == 24
        assert sp.hflex_lane(3, narrow=False) == 128

    def test_resolve_backend_n_stub(self):
        """``resolve_backend(..., n=)`` synthesizes a shape stub, so a
        custom policy that reads the operand's width resolves before the
        operand exists."""
        A = self._A()
        try:
            sp.set_auto_policy(lambda a, b, platform=None: (
                "pallas" if b is not None and b.shape[-1] <= 4 else "jnp"))
            assert sp.resolve_backend("auto", A, n=4) == "pallas"
            assert sp.resolve_backend("auto", A, n=64) == "jnp"
            # no operand, no n: the policy sees None
            assert sp.resolve_backend("auto", A) == "jnp"
        finally:
            sp.set_auto_policy(None)


class TestSkinnyThresholdTunable:
    """The skinny-N routing boundary is live-tunable: a
    ``set_skinny_n_max`` override (what ``apply_skinny_from_db`` pushes)
    beats ``$SEXTANS_SKINNY_N_MAX`` beats the built-in 8."""

    def _A(self):
        m, k = 64, 128
        rng = np.random.default_rng(0)
        d = np.zeros((m, k), np.float32)
        nnz = max(1, int(m * k * 0.05))
        d[rng.integers(0, m, nnz), rng.integers(0, k, nnz)] = 1.0
        return sp.from_dense(d, tm=32, k0=32, chunk=8)

    @pytest.mark.parametrize("thr", [2, 12])
    def test_override_moves_the_boundary(self, thr):
        A = self._A()
        lane = -(-thr // 8) * 8
        try:
            sp.set_skinny_n_max(thr)
            assert sp.skinny_n_max() == thr
            assert sp.is_skinny(A, "jnp", thr)
            assert not sp.is_skinny(A, "jnp", thr + 1)
            assert _route(A, thr, "tpu") == ("pallas", lane)
            assert _route(A, thr + 1, "tpu") == ("pallas", 128)
        finally:
            sp.set_skinny_n_max(None)

    def test_zero_disables_the_lane(self):
        A = self._A()
        try:
            sp.set_skinny_n_max(0)
            assert _route(A, 1, "tpu") == ("pallas", 128)
            assert not sp.is_skinny(A, "jnp", 1)
        finally:
            sp.set_skinny_n_max(None)

    def test_env_beats_default_override_beats_env(self, monkeypatch):
        monkeypatch.setenv("SEXTANS_SKINNY_N_MAX", "12")
        assert sp.skinny_n_max() == 12
        assert sp.hflex_lane(12) == 16
        try:
            sp.set_skinny_n_max(3)
            assert sp.skinny_n_max() == 3       # override wins over env
            assert sp.hflex_lane(12) == 128
        finally:
            sp.set_skinny_n_max(None)
        assert sp.skinny_n_max() == 12          # env chain restored

    def test_bad_env_falls_back_to_default(self, monkeypatch):
        monkeypatch.setenv("SEXTANS_SKINNY_N_MAX", "not-a-number")
        assert sp.skinny_n_max() == sp.SKINNY_N_MAX

    def test_plan_routing_follows_live_threshold(self):
        """A plan's lane follows the live threshold, so a DB-tuned value
        changes the kernel's width without re-imports; the plan records
        it in ``opts`` and runs what an explicit ``tn=`` call runs."""
        _, A, b, _ = _packed(n=4)
        try:
            for thr, tn in ((2, 128), (16, 8)):
                sp.set_skinny_n_max(thr)
                P = sp.plan(A, 4, backend="pallas", interpret=True)
                assert sp.is_skinny(A, P.backend, 4) == (tn == 8)
                assert P.opts["tn"] == tn
                np.testing.assert_array_equal(
                    np.asarray(P.run(b)),
                    np.asarray(sp.spmm(A, b, backend="pallas", tn=tn,
                                       interpret=True)))
        finally:
            sp.set_skinny_n_max(None)


class TestSkinnyRouting:
    def test_plan_resolves_lane(self):
        _, A, _, _ = _packed()
        P = sp.plan(A, 4, backend="auto")
        assert sp.is_skinny(A, P.backend, P.n)
        P_tall = sp.plan(A, 64, backend="auto")
        assert not sp.is_skinny(A, P_tall.backend, P_tall.n)

    def test_engine_counts_skinny_dispatches(self):
        from repro.core.engine import SextansEngine

        rng = np.random.default_rng(0)
        a = power_law_sparse(200, 300, 5, seed=0)
        eng = SextansEngine(tm=64, k0=64, chunk=8, impl="auto")
        t = eng.pack(a)
        y = eng.spmm(t, jnp.asarray(
            rng.standard_normal((300, 4)).astype(np.float32)))
        assert eng.stats.skinny_dispatches == 1
        eng.spmm(t, jnp.asarray(
            rng.standard_normal((300, 64)).astype(np.float32)))
        assert eng.stats.skinny_dispatches == 1      # tall call: not skinny
        assert np.isfinite(np.asarray(y)).all()

    def test_scheduler_pool_reports_skinny(self):
        from repro.core.engine import SextansEngine
        from repro.launch.serve import SpmmRequest, serve_spmm_requests

        rng = np.random.default_rng(0)
        reqs = [SpmmRequest(
            a=power_law_sparse(128, 160, 5, seed=i),
            b=rng.standard_normal((160, 4)).astype(np.float32))
            for i in range(4)]
        eng = SextansEngine(tm=64, k0=64, chunk=8, impl="auto")
        outs, stats = serve_spmm_requests(reqs, eng)
        assert stats["skinny_dispatches"] > 0
        for r, o in zip(reqs, outs):
            ref = spmm_reference(
                r.a, r.b, np.zeros((r.a.shape[0], r.b.shape[1]), np.float32))
            np.testing.assert_allclose(
                o, ref, rtol=2e-4, atol=2e-4 * max(1, np.abs(ref).max()))
