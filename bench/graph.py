"""Seeded graph generator for the graph configurations, and the matrices
the graph cells multiply.

``kronecker_edges`` is the Graph 500 Kronecker generator that the GAP
Benchmark Suite's ``kron`` graph uses: ``edge_factor * 2**scale`` edges,
each placed by ``scale`` independent quadrant choices with the initiator
probabilities ``a, b, c`` (and ``1 - a - b - c``), then the vertex ids
permuted at random (the Graph 500 specification permutes them, so that
ids say nothing of degree).  ``undirected`` then does what the GAP
builder does with an undirected edge list: each edge in both directions,
self-loops and repeated edges removed.  Everything is made from the seed
with numpy on the host; the same seed gives the same edges.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

Coo = Tuple[Tuple[int, int], np.ndarray, np.ndarray, np.ndarray]


def kronecker_edges(scale: int, edge_factor: int, a: float, b: float,
                    c: float, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(u, v)`` int64 arrays of the ``edge_factor * 2**scale`` generated
    edges (repeats and self-loops included, as generated)."""
    rng = np.random.default_rng(seed)
    m = edge_factor << scale
    u = np.zeros(m, np.int64)
    v = np.zeros(m, np.int64)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    for bit in range(scale):
        down = rng.random(m, dtype=np.float32) > ab
        right = rng.random(m, dtype=np.float32) > np.where(
            down, np.float32(c_norm), np.float32(a_norm))
        u |= down.astype(np.int64) << bit
        v |= right.astype(np.int64) << bit
    perm = rng.permutation(1 << scale)
    return perm[u], perm[v]


def undirected(nodes: int, u: np.ndarray,
               v: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Both directions of each edge, with self-loops and repeats removed,
    sorted by ``(src, dst)``."""
    ok = u != v
    keys = np.unique(np.concatenate([u[ok] * nodes + v[ok],
                                     v[ok] * nodes + u[ok]]))
    return keys // nodes, keys % nodes


def _canonical(shape, row, col, val) -> Coo:
    """COO triples sorted column-major, int32 indices, float32 values."""
    order = np.lexsort((row, col))
    return (shape, row[order].astype(np.int32), col[order].astype(np.int32),
            val[order].astype(np.float32))


def gcn_matrix(nodes: int, src: np.ndarray, dst: np.ndarray) -> Coo:
    """``D^-1/2 (S + I) D^-1/2`` with ``S`` the symmetrized edge set and
    ``D`` the row sums of ``S + I`` (Kipf and Welling's propagation
    matrix)."""
    a = np.concatenate([src, dst, np.arange(nodes)])
    b = np.concatenate([dst, src, np.arange(nodes)])
    keys = np.unique(a.astype(np.int64) * nodes + b)
    row, col = keys // nodes, keys % nodes
    deg = np.bincount(row, minlength=nodes).astype(np.float64)
    inv = 1.0 / np.sqrt(deg)
    return _canonical((nodes, nodes), row, col, inv[row] * inv[col])


def transition_matrix(nodes: int, src: np.ndarray, dst: np.ndarray) -> Coo:
    """Column-stochastic ``P[dst, src] = 1 / outdeg(src)``; a node with no
    out-edge leaves an empty column (its rank mass is not redistributed,
    as in the GAP PageRank kernel)."""
    outdeg = np.bincount(src, minlength=nodes).astype(np.float64)
    return _canonical((nodes, nodes), dst, src, 1.0 / outdeg[src])


MATRICES = {"gcn": gcn_matrix, "transition": transition_matrix}


def edges(cfg: Dict, seed: int) -> Tuple[int, np.ndarray, np.ndarray]:
    """``(nodes, src, dst)``: the undirected graph of graph configuration
    ``cfg`` for ``seed``, each edge in both directions."""
    g = cfg["graph"]
    nodes = 1 << g["scale"]
    u, v = kronecker_edges(g["scale"], g["edge_factor"], g["a"], g["b"],
                           g["c"], seed)
    return (nodes, *undirected(nodes, u, v))


def matrix(cfg: Dict, kind: str, seed: int) -> Coo:
    """The ``kind`` matrix of graph configuration ``cfg`` for ``seed``."""
    return MATRICES[kind](*edges(cfg, seed))
