"""The dropless MoE serving path (``SparseMoE(..., use_plan=True)``) and
what it is built from: the ragged mode of the BSR kernel, its plan, and
BSR widths that are not multiples of the tile.  Seeded random weights at a
small size (hidden 256, expert width 128, 16 experts, top 6, two shared
experts, a layer-0 FFN of width 320), checked against the plain float32
reference ``repro.models.moe_reference``.

Tolerance: the program and the reference are both float32 at ``HIGHEST``
and differ only in summation order (tiles against dense rows, the
combine's order against the reference's loop over experts), a few units
of float32 rounding per layer, so a token's relative L2 error stays under
``TOL`` = 1e-5 over the three layers (readings are ~3e-7); a dropped,
misrouted or differently weighted pair moves a token by more than 1e-2.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bsr_spmm import (bsr_matmul_pallas_batched,
                                    bsr_matmul_pallas_ragged)
from repro.models import moe_reference as R
from repro.models.common import Initializer, ModelConfig
from repro.models.layers import (SparseFFN, SparseLinear, SparseMoE,
                                 _moe_combine, _moe_dispatch)
from repro.sparse_api import PLAN_STATS, Format, from_dense, stack_bsr

TOL = 1e-5
D, FF, E, K, SHARED, DENSE_FF, T = 256, 128, 16, 6, 256, 320, 64
EPS = 1e-6
BACKENDS = ["jnp", "pallas"]


def _cfg(**kw):
    base = dict(name="moe-test", family="moe", num_layers=3, d_model=D,
                num_heads=2, num_kv_heads=2, d_ff=FF, vocab_size=8,
                num_experts=E, experts_per_token=K, shared_expert=True,
                shared_expert_ff=SHARED, norm_topk_prob=False,
                routed_scaling_factor=1.0, norm_eps=EPS)
    base.update(kw)
    return ModelConfig(**base)


def _dense_ffn(f, p):
    g, u = f.gate_up.layers
    return {"gate": g.skeleton.with_values(p["gate"]["w"]).todense().T,
            "up": u.skeleton.with_values(p["up"]["w"]).todense().T,
            "down": f.down.skeleton.with_values(p["down"]["w"]).todense().T}


def _dense_moe(m, p):
    def stack(t, v):
        return jnp.stack([t.with_values(v)[e].todense().T
                          for e in range(t.batch)])

    return {"router": p["router"],
            "experts": {"gate": stack(m.wg, p["wg"]),
                        "up": stack(m.wi, p["wi"]),
                        "down": stack(m.wo, p["wo"])},
            "shared": _dense_ffn(m.shared, p["shared"])}


def _row_err(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float((np.linalg.norm(got - ref, axis=-1)
                  / np.linalg.norm(ref, axis=-1)).max())


@pytest.fixture(scope="module")
def stack():
    """Layer 0's FFN and two MoE layers, with their dense twins."""
    cfg = _cfg()
    init = Initializer(0, jnp.float32)
    ffn0, p0 = SparseFFN.create(init, D, DENSE_FF, density=0.5)
    moes = [SparseMoE.create(init, cfg, density=0.5) for _ in range(2)]
    dense = {"dense": _dense_ffn(ffn0, p0),
             "moe": [_dense_moe(m, p) for m, p in moes]}
    return cfg, (ffn0, p0), moes, dense


def _tokens(seed=1, t=T):
    return jax.random.normal(jax.random.PRNGKey(seed), (t, D), jnp.float32)


def _norm(x):
    return R.rmsnorm(x, EPS)


@pytest.mark.parametrize("backend", BACKENDS)
def test_serving_stack_matches_reference(stack, backend):
    cfg, (ffn0, p0), moes, dense = stack
    x = _tokens()
    h = x + ffn0(p0, _norm(x), use_plan=True, backend=backend)
    for m, p in moes:
        h = h + m(p, cfg, _norm(h), use_plan=True, backend=backend)
    ref = R.forward(dense, x, k=K, norm_topk_prob=False,
                    routed_scaling_factor=1.0, eps=EPS)
    assert h.shape == (T, D)
    assert _row_err(h, ref) < TOL


@pytest.mark.parametrize("norm,scale", [(False, 1.0), (True, 1.0),
                                        (False, 2.5), (True, 0.5)])
def test_router_options_match_reference(stack, norm, scale):
    _, _, moes, dense = stack
    cfg = _cfg(norm_topk_prob=norm, routed_scaling_factor=scale)
    (m, p), d = moes[0], dense["moe"][0]
    x = _norm(_tokens(2))
    y = m(p, cfg, x, use_plan=True, backend="jnp")
    with jax.default_matmul_precision("highest"):
        ref = R.moe(d, x, k=K, norm_topk_prob=norm,
                    routed_scaling_factor=scale)
    assert _row_err(y, ref) < TOL
    # each option changes the answer
    if (norm, scale) != (False, 1.0):
        base = m(p, _cfg(), x, use_plan=True, backend="jnp")
        assert _row_err(base, ref) > 1e-2


@pytest.mark.parametrize("backend", BACKENDS)
def test_dropless_when_every_token_prefers_the_same_experts(stack, backend):
    """Every token's top 6 are experts 0-5: each of them takes all T
    tokens, five times the capacity router's 30-row buffers, and no
    token is dropped."""
    cfg, _, moes, dense = stack
    (m, p), d = moes[1], dict(dense["moe"][1])
    router = np.full((D, E), -10.0 / D, np.float32)
    for e in range(K):
        router[:, e] = (10.0 - e) / D
    p = dict(p, router=jnp.asarray(router))
    d["router"] = p["router"]
    x = _norm(1.0 + 0.1 * _tokens(3))
    m.reset_stats()
    y = m(p, cfg, x, use_plan=True, backend=backend)
    loads, tiles = np.asarray(m.expert_stats)
    assert loads.tolist() == [T] * K + [0] * (E - K)
    assert loads.sum() == T * K and tiles.tolist() == [1] * K + [0] * (E - K)
    with jax.default_matmul_precision("highest"):
        ref = R.moe(d, x, k=K, norm_topk_prob=False,
                    routed_scaling_factor=1.0)
    assert _row_err(y, ref) < TOL


def test_stats_accumulate_over_calls_without_a_sync(stack):
    cfg, _, moes, _ = stack
    m, p = moes[0]
    m.reset_stats()
    for seed in (4, 5):
        m(p, cfg, _norm(_tokens(seed, t=200)), use_plan=True, backend="jnp")
    assert isinstance(m.expert_stats, jax.Array)
    loads, tiles = np.asarray(m.expert_stats)
    assert loads.sum() == 2 * 200 * K
    assert np.all(tiles >= -(-loads // 128)) and np.all(tiles <= 2 * (
        -(-loads // 256) + 1))


def test_serving_dispatches_and_no_capacity_tensor(stack):
    """Per MoE layer: three ragged dispatches over the resident stacked
    payloads plus the shared expert's two; the routing and combine hold no
    tensor larger than the sorted rows or the (T, k, d) gathered rows."""
    cfg, _, moes, _ = stack
    m, p = moes[0]
    x = _norm(_tokens(6))
    m(p, cfg, x, use_plan=True, backend="jnp")
    before = PLAN_STATS["dispatches"]
    m(p, cfg, x, use_plan=True, backend="jnp")
    assert PLAN_STATS["dispatches"] - before == 5
    plans = [pl for key, pl in m._plans.items() if key[0] == "wg"]
    assert plans and plans[0]._operands[0] is m.wg.data.blocks

    rows = -(-(T * K + E * 127) // 128) * 128
    jx = jax.make_jaxpr(lambda r, x, s: _moe_dispatch(
        r, x, s, k=K, norm=False, scale=1.0, tile=128, rows=rows))(
            p["router"], x, m.expert_stats)
    jc = jax.make_jaxpr(_moe_combine)(jnp.zeros((rows, D)),
                                      jnp.zeros((T, K), jnp.int32),
                                      jnp.zeros((T, K)))
    biggest = max(int(np.prod(v.aval.shape)) for j in (jx, jc)
                  for eqn in j.jaxpr.eqns for v in eqn.outvars)
    assert biggest <= max(rows * D, T * K * D)


def _expert_group(seed=0, e=4, k=256, f=384):
    rng = np.random.default_rng(seed)
    ts = []
    for _ in range(e):
        w = rng.standard_normal((f, k)).astype(np.float32)
        keep = rng.random((f // 128, k // 128)) < 0.5
        w = (w.reshape(f // 128, 128, k // 128, 128)
             * keep[:, None, :, None]).reshape(f, k)
        ts.append(from_dense(w, format=Format.BSR, block=(128, 128)))
    return stack_bsr(ts)


def test_ragged_kernel_bit_identical_to_batched_per_expert():
    s = _expert_group()
    d = s.data
    loads = [130, 0, 5, 256]
    tiles = [-(-n // 128) for n in loads]
    rows = -(-(sum(loads) + 4 * 127) // 128) * 128
    rng = np.random.default_rng(1)
    xs = [rng.standard_normal((n, 256)).astype(np.float32) for n in loads]
    x = np.zeros((rows, 256), np.float32)
    te, off = [], 0
    for e, n in enumerate(loads):
        x[off:off + n] = xs[e]
        te += [e] * tiles[e]
        off += tiles[e] * 128
    used = len(te)
    te += [te[-1]] * (rows // 128 - used)
    te, used_a = jnp.asarray(te, jnp.int32), jnp.asarray([used], jnp.int32)
    y = np.asarray(bsr_matmul_pallas_ragged(
        jnp.asarray(x), d.blocks, d.brow, d.indptr, te, used_a))
    xb = np.zeros((4, max(tiles) * 128, 256), np.float32)
    for e, n in enumerate(loads):
        xb[e, :n] = xs[e]
    yb = np.asarray(bsr_matmul_pallas_batched(jnp.asarray(xb), d.blocks,
                                              d.brow, d.indptr))
    off = 0
    for e, n in enumerate(loads):
        assert np.array_equal(y[off:off + n], yb[e, :n]), e
        off += tiles[e] * 128
    assert not y[off:].any()         # tiles past the used count: zeros

    from repro.sparse_api import plan_ragged
    for backend in BACKENDS:
        got = plan_ragged(s, rows, backend=backend).run(
            jnp.asarray(x), te, used_a)
        np.testing.assert_allclose(np.asarray(got), y, rtol=1e-5,
                                   atol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_width_off_the_tile_matches_unpadded_dense(backend):
    init = Initializer(7, jnp.float32)
    x = _tokens(8, t=40)
    up, pu = SparseLinear.create(init, D, DENSE_FF, density=0.5)
    down, pd = SparseLinear.create(init, DENSE_FF, D, density=0.5)
    assert (up.d_out, down.d_in) == (DENSE_FF, DENSE_FF)
    wu = np.asarray(up.skeleton.todense(), np.float64)      # (320, 256)
    wd = np.asarray(down.skeleton.todense(), np.float64)    # (256, 320)
    h = up(pu, x, use_plan=True, backend=backend)
    assert h.shape == (40, DENSE_FF)
    ref_h = np.asarray(x, np.float64) @ wu.T
    np.testing.assert_allclose(np.asarray(h), ref_h, rtol=1e-5, atol=1e-4)
    y = down(pd, h, use_plan=True, backend=backend)
    np.testing.assert_allclose(np.asarray(y), np.asarray(h, np.float64)
                               @ wd.T, rtol=1e-5, atol=1e-4)
    # the last tile column is half real: its pad half is an exact zero
    w = up.skeleton.data
    last = np.asarray(w.indptr)[-2]
    assert not np.asarray(w.blocks)[last:, :, DENSE_FF % 128:].any()
