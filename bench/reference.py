"""Plain references the cells are checked against, and their controls.

Nothing here imports the system under test.  The SpMM reference is a
float64 CSR product (scipy); its control is the same product in the
precision one step below the configuration's (float32 at ``HIGHEST``):
``HIGH``, three bfloat16 passes, formed exactly as the MXU forms them
(``a_hi*b_hi + a_hi*b_lo + a_lo*b_hi`` with float32 accumulation).

Errors are measured against the size of each output element's terms:
``|got - ref| / (|alpha| |A| |B| + |beta| |C|)``, elementwise, so that
an error in a row of small results is as visible as one in a hub's row.
"""

from __future__ import annotations

from typing import Optional

import numpy as np
import scipy.sparse as sps


def csr(coo, dtype=np.float64, vals: Optional[np.ndarray] = None):
    shape, row, col, val = coo
    v = val if vals is None else vals
    return sps.csr_matrix((v.astype(dtype), (row, col)), shape=shape)


def spmm(coo, b, c=None, alpha=1.0, beta=0.0) -> np.ndarray:
    """``alpha * A @ B + beta * C`` in float64."""
    y = alpha * (csr(coo) @ np.asarray(b, np.float64))
    if beta != 0.0:
        y = y + beta * np.asarray(c, np.float64)
    return y


def term_scale(coo, b, c=None, alpha=1.0, beta=0.0) -> np.ndarray:
    """``|alpha| |A| |B| + |beta| |C|``: the sum of each element's terms'
    magnitudes, the natural scale of its rounding error."""
    shape, row, col, val = coo
    s = abs(alpha) * (csr(coo, vals=np.abs(val))
                      @ np.abs(np.asarray(b, np.float64)))
    if beta != 0.0:
        s = s + abs(beta) * np.abs(np.asarray(c, np.float64))
    return s


def scaled_error(got, ref, scale) -> float:
    """Largest ``|got - ref| / scale`` over all elements (where the scale
    is 0 the result must be exactly 0 as well)."""
    got = np.asarray(got, np.float64)
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        return float("inf")
    err = np.abs(got - ref)
    zero = scale == 0
    if np.any(err[zero] != 0):
        return float("inf")
    return float((err[~zero] / scale[~zero]).max()) if np.any(~zero) else 0.0


def _split_bf16(x: np.ndarray):
    """``x = hi + lo + rest`` with ``hi`` and ``lo`` bfloat16 values held
    in float32 (round to nearest even)."""
    x = np.asarray(x, np.float32)

    def rnd(v):
        u = v.view(np.uint32).astype(np.uint64)
        u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
        return u.astype(np.uint32).view(np.float32)

    hi = rnd(x)
    lo = rnd((x - hi).astype(np.float32))
    return hi, lo


def spmm_high(coo, b, c=None, alpha=1.0, beta=0.0) -> np.ndarray:
    """The control: the SpMM at ``Precision.HIGH`` (three bfloat16
    passes, float32 sums), float32 epilogue."""
    _, _, _, val = coo
    v_hi, v_lo = _split_bf16(val)
    b_hi, b_lo = _split_bf16(b)
    a_hi = csr(coo, np.float32, v_hi)
    a_lo = csr(coo, np.float32, v_lo)
    y = (a_hi @ b_hi + a_hi @ b_lo + a_lo @ b_hi).astype(np.float32)
    y = np.float32(alpha) * y
    if beta != 0.0:
        y = y + np.float32(beta) * np.asarray(c, np.float32)
    return y
