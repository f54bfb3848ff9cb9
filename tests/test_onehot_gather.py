"""The HFLEX kernel's one-hot gather is exact.

The kernel gathers B rows with a bfloat16 one-hot against an exact
three-way bfloat16 split of the window (``bf16_split3``), one
default-precision MXU pass per trip.  These tests pin the split's exact
range and check, in interpret mode, the kernel against a float64
reference of the same slabs in every launch mode.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels.sextans_spmm import bf16_split3, sextans_spmm_pallas

F32 = np.finfo(np.float32)


def _samples(kind, rng):
    if kind == "normal":
        return rng.standard_normal(20_000)
    if kind == "wide":                       # magnitudes 2^-60 .. 2^60
        return rng.uniform(1, 2, 20_000) * np.exp2(
            rng.integers(-60, 61, 20_000)) * rng.choice([-1, 1], 20_000)
    if kind == "special":
        return np.array([0.0, -0.0, F32.max, -F32.max, np.nextafter(
            F32.max, 0, dtype=np.float32), 1.0, -1.0, F32.eps, 2.0 ** 127])
    if kind == "small":                      # smallest normal up to 2^-90
        return np.concatenate([
            [F32.tiny, -F32.tiny, 2.0 ** -103, -(2.0 ** -103) * 1.9999999],
            rng.uniform(1, 2, 5_000) * np.exp2(rng.integers(-103, -90, 5_000)),
            F32.tiny * (1 + np.arange(128) / 128.0)])
    raise ValueError(kind)


@pytest.mark.parametrize("kind", ["normal", "wide", "special", "small"])
def test_split_is_exact(kind):
    x = _samples(kind, np.random.default_rng(7)).astype(np.float32)
    parts = bf16_split3(jnp.asarray(x))
    assert len(parts) == 3
    assert all(p.dtype == jnp.bfloat16 for p in parts)
    hi, mid, lo = (np.asarray(p, np.float32) for p in parts)
    np.testing.assert_array_equal((hi + mid) + lo, x)
    # each part is finite and keeps the sign of x
    for p in (hi, mid, lo):
        assert np.all(np.sign(p) * np.sign(x) >= 0)
        assert np.all(np.isfinite(p))


def test_split_parts_are_disjoint():
    """``hi`` is the truncated top of ``x`` and ``mid`` of the remainder,
    so ``hi`` alone is within 2^-7 of ``x`` and ``hi + mid`` within
    2^-15, relatively."""
    x = _samples("wide", np.random.default_rng(3)).astype(np.float32)
    hi, mid, lo = (np.asarray(p, np.float64) for p in bf16_split3(
        jnp.asarray(x)))
    ax = np.abs(x.astype(np.float64))
    assert np.all(np.abs(x - hi) < ax * 2.0 ** -7)
    assert np.all(np.abs(x - hi - mid) < ax * 2.0 ** -15)


MB, NW, R, L, K0, TM = 3, 2, 16, 128, 256, 64


def _operands(lead, n, seed):
    """Slabs in the kernel's layout (padding slots hold zeros, one empty
    slab) and a B that spans 2^-40 .. 2^40 with exact zeros."""
    rng = np.random.default_rng(seed)
    shape = (*lead, MB, NW, R, L)
    q = rng.integers(1, R * L + 1, (*lead, MB, NW)).astype(np.int32)
    q[..., 0, 0] = 0
    live = np.arange(R * L).reshape(R, L) < q[..., None, None]
    vals = np.where(live, rng.standard_normal(shape), 0).astype(np.float32)
    cols = np.where(live, rng.integers(0, K0, shape), 0).astype(np.int32)
    rows = np.where(live, rng.integers(0, TM, shape), 0).astype(np.int32)
    bshape = (*lead, NW * K0, n)
    b = (rng.standard_normal(bshape)
         * np.exp2(rng.integers(-40, 41, bshape))).astype(np.float32)
    b[rng.random(bshape) < 0.05] = 0.0
    c = rng.standard_normal((*lead, MB * TM, n)).astype(np.float32)
    return vals, cols, rows, q, b, c


def _reference(vals, cols, rows, b, c, alpha, beta, accumulate):
    """float64 ``alpha * A @ b + beta * c`` straight from the slabs (padding
    slots hold zeros and add nothing), with the scale of each element's
    terms, ``|alpha| * |A| @ |b| + |beta * c|``; ``accumulate`` seeds from
    ``c`` instead."""
    n = b.shape[-1]
    acc = np.zeros((MB * TM, n))
    mag = np.zeros((MB * TM, n))
    for bi in range(MB):
        for wi in range(NW):
            r = (bi * TM + rows[bi, wi]).ravel()
            br = b[wi * K0 + cols[bi, wi].ravel()].astype(np.float64)
            v = vals[bi, wi].ravel().astype(np.float64)[:, None]
            np.add.at(acc, r, v * br)
            np.add.at(mag, r, np.abs(v * br))
    c = c.astype(np.float64)
    if accumulate:
        return c + acc, np.abs(c) + mag
    return alpha * acc + beta * c, abs(alpha) * mag + np.abs(beta * c)


# The kernel forms each product v * b exactly (exact gather, HIGHEST
# scatter) and sums them in float32.  A float32 sum of k terms, then the
# epilogue's two roundings, errs by at most about (k + 2) * eps times the
# sum of the terms' magnitudes; no output row of these operands takes
# more than 57 live terms (the worst seen is about 3 * eps).
REL_TOL = 64 * float(F32.eps)


@pytest.mark.parametrize("tn", [8, 128])
@pytest.mark.parametrize("mode", ["resident", "batched", "accumulate"])
def test_onehot_bit_identical_to_gather(mode, tn):
    """The one-hot kernel against the float64 reference of its slabs,
    within ``REL_TOL`` of each element's term scale."""
    lead = (2,) if mode == "batched" else ()
    vals, cols, rows, q, b, c = _operands(lead, 2 * tn if tn == 8 else tn,
                                          seed=tn)
    if mode == "batched":
        alphas, betas = [1.5, -0.5], [0.25, 2.0]
        ab = (jnp.asarray(alphas, jnp.float32),
              jnp.asarray(betas, jnp.float32))
    elif mode == "accumulate":
        alphas, betas, ab = [1.0], [1.0], ()
    else:
        alphas, betas, ab = [1.5], [0.25], (1.5, 0.25)
    out = np.asarray(sextans_spmm_pallas(
        vals, cols, rows, q, b, c, *ab, tm=TM, k0=K0, tn=tn,
        interpret=True, accumulate=mode == "accumulate"))
    assert np.isfinite(out).all()
    members = zip(*(x.reshape(-1, *x.shape[len(lead):])
                    for x in (vals, cols, rows, b, c, out)))
    for (v, cl, rw, bm, cm, om), al, be in zip(members, alphas, betas):
        ref, scale = _reference(v, cl, rw, bm, cm, al, be,
                                mode == "accumulate")
        assert np.all(np.abs(om - ref) <= REL_TOL * scale)
