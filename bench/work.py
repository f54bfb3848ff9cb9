"""Useful work of an SpMM call, counted from the matrix as generated.

The count never looks at the packed payload, the padding or the compiled
program, so it stays the same whatever packs or runs the call: a repack
with another slab geometry, a new kernel or another storage type leaves
the yardstick where it was.

* FLOPs: ``2 * nnz * N`` (one multiply and one add per stored entry and
  output column).
* Bytes: what a CSR input, the dense ``B`` and the result ``C`` must move
  at the declared types: ``nnz`` values plus 4-byte column indices, the
  ``M + 1`` row pointers (or, for block-sparse weights, one 4-byte block
  row index per block and ``F/TF + 1`` pointers), ``K * N`` elements of
  ``B`` read, ``M * N`` of ``C`` written and, when ``beta != 0``, read.
* Roofline time: ``max(FLOPs / peak FLOP/s, bytes / peak bytes/s)``.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, Optional

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)

    def roofline_s(self, peak: Dict[str, float]) -> float:
        return max(self.flops / peak["flops_per_s"],
                   self.bytes / peak["bytes_per_s"])


ZERO = Work(0.0, 0.0)


def csr_spmm(m: int, k: int, nnz: int, n: int, *, value_bytes: int = 4,
             dense_bytes: int = 4, beta: float = 0.0) -> Work:
    """``C = alpha * A @ B + beta * C`` with ``A`` (M, K) in CSR."""
    c_moves = 2 if beta != 0.0 else 1
    nbytes = (nnz * (value_bytes + 4) + 4 * (m + 1)
              + k * n * dense_bytes + c_moves * m * n * dense_bytes)
    return Work(2.0 * nnz * n, float(nbytes))


def bsr_spmm(m: int, k: int, blocks: int, tk: int, tf: int, n: int, *,
             value_bytes: int = 4, dense_bytes: int = 4) -> Work:
    """``Y (N, F) = X (N, K) @ W`` with ``W`` block-sparse, ``blocks``
    stored ``(TK, TF)`` blocks; ``m`` is the output width ``F``."""
    nnz = blocks * tk * tf
    nbytes = (nnz * value_bytes + 4 * blocks + 4 * (m // tf + 1)
              + k * n * dense_bytes + m * n * dense_bytes)
    return Work(2.0 * nnz * n, float(nbytes))


def peaks(device_kind: str, path: Optional[str] = None) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(path or PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]
