"""The BSR kernel's compact walk (``kernels/bsr_spmm.py``): each output
tile takes ``max(count, 1)`` steps over its stored blocks, in block order,
and the walk's static length comes from the shapes.

The kernel runs in interpret mode here.  Its single, batched and ragged
modes are compared with a float64 dense oracle on patterns that stress
the walk (empty output tiles, all-empty and fully dense members, members
padded to a shared block bucket, ragged tiles past the used count), and
the walk tables are checked at the benchmark cells' geometries."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.bsr_spmm import (bsr_matmul_pallas,
                                    bsr_matmul_pallas_batched,
                                    bsr_matmul_pallas_ragged, walk_tables)

TB, TK, TF = 8, 16, 16
KT, NF = 4, 5                     # K tiles, output tiles of the small cases

# kept (K tile, output tile) blocks of a (KT, NF) grid of tiles
PATTERNS = {
    "empty-edge-tiles": [(0, 1), (3, 1), (1, 2), (2, 3), (0, 3)],
    "one-block": [(2, 4)],
    "empty-middle-tiles": [(0, 0), (1, 0), (3, 0), (1, 4), (2, 4)],
    "dense": [(r, c) for r in range(KT) for c in range(NF)],
    "all-empty": [],
}


def _pack(kept, seed, nb=None):
    """BSR payload of a (KT·TK, NF·TF) weight keeping ``kept`` tiles,
    padded to ``nb`` blocks; returns (blocks, brow, indptr, dense)."""
    rng = np.random.default_rng(seed)
    kept = sorted(kept, key=lambda rc: (rc[1], rc[0]))    # by column, row
    nb = len(kept) if nb is None else nb
    blocks = np.zeros((nb, TK, TF), np.float32)
    brow = np.zeros(nb, np.int32)
    dense = np.zeros((KT * TK, NF * TF))
    for i, (r, c) in enumerate(kept):
        blocks[i] = rng.standard_normal((TK, TF))
        brow[i] = r
        dense[r * TK:(r + 1) * TK, c * TF:(c + 1) * TF] = blocks[i]
    indptr = np.zeros(NF + 1, np.int32)
    np.cumsum(np.bincount([c for _, c in kept], minlength=NF),
              out=indptr[1:])
    return blocks, brow, indptr, dense


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", [n for n in PATTERNS if PATTERNS[n]])
def test_single_matches_dense(name):
    blocks, brow, indptr, dense = _pack(PATTERNS[name], seed=1)
    x = np.random.default_rng(2).standard_normal((2 * TB, KT * TK))
    y = bsr_matmul_pallas(jnp.asarray(x, jnp.float32), jnp.asarray(blocks),
                          jnp.asarray(brow), jnp.asarray(indptr),
                          tb=TB, tk=TK, tf=TF)
    _close(y, x.astype(np.float32).astype(np.float64) @ dense)
    for f in range(NF):                  # an empty output tile is zeros
        if indptr[f] == indptr[f + 1]:
            assert not np.asarray(y)[:, f * TF:(f + 1) * TF].any()


def _stack(names, seed=3):
    """Members padded to the densest member's block count."""
    nb = max(len(PATTERNS[n]) for n in names)
    packs = [_pack(PATTERNS[n], seed + i, nb) for i, n in enumerate(names)]
    blocks, brow, indptr, dense = zip(*packs)
    return (jnp.asarray(np.stack(blocks)), jnp.asarray(np.stack(brow)),
            jnp.asarray(np.stack(indptr)), dense)


@pytest.mark.parametrize("names", [
    ("all-empty", "dense"),
    ("empty-edge-tiles", "one-block", "empty-middle-tiles"),
    ("dense", "empty-edge-tiles", "all-empty"),
])
def test_batched_matches_dense(names):
    """Unequal block counts padded to a shared bucket; an all-empty member
    comes back exact zeros."""
    blocks, brow, indptr, dense = _stack(names)
    x = np.random.default_rng(4).standard_normal(
        (len(names), 2 * TB, KT * TK)).astype(np.float32)
    y = np.asarray(bsr_matmul_pallas_batched(jnp.asarray(x), blocks, brow,
                                             indptr, tb=TB, tk=TK, tf=TF))
    for g, name in enumerate(names):
        _close(y[g], x[g].astype(np.float64) @ dense[g])
        if not PATTERNS[name]:
            assert not y[g].any()


@pytest.mark.parametrize("dead_expert", ["last-used", "other"])
def test_ragged_matches_dense(dead_expert):
    """Experts with empty output columns, a dense and an all-empty one;
    row tiles past ``used`` are exact zeros whatever expert they name."""
    names = ("empty-edge-tiles", "dense", "all-empty", "empty-middle-tiles")
    blocks, brow, indptr, dense = _stack(names)
    te = [0, 0, 1, 2, 3, 1]
    used = len(te)
    te += [te[-1] if dead_expert == "last-used" else 2] * 3
    x = np.random.default_rng(5).standard_normal(
        (len(te) * TB, KT * TK)).astype(np.float32)
    y = np.asarray(bsr_matmul_pallas_ragged(
        jnp.asarray(x), blocks, brow, indptr, jnp.asarray(te, jnp.int32),
        jnp.asarray([used], jnp.int32), tb=TB, tk=TK, tf=TF))
    for b, e in enumerate(te[:used]):
        rows = slice(b * TB, (b + 1) * TB)
        _close(y[rows], x[rows].astype(np.float64) @ dense[e])
    assert not y[used * TB:].any()


def _walk_steps(indptr, brow, k_tiles):
    walk, length = walk_tables(jnp.asarray(indptr), jnp.asarray(brow),
                               k_tiles)
    return np.asarray(walk).reshape(-1, length, 4), length


@pytest.mark.parametrize("name", list(PATTERNS))
def test_walk_visits_blocks_in_order(name):
    """Each output tile's live steps are its blocks in stored order, an
    empty tile takes one dead step, and the accumulator is opened and
    stored once per tile; dead steps hold the previous block (no DMA)."""
    blocks, brow, indptr, _ = _pack(PATTERNS[name], seed=6, nb=KT * NF)
    steps, length = _walk_steps(indptr, brow, KT)
    assert length == NF * KT          # NB = KT·NF: the dense walk
    blk, xk, tile, flags = steps[0].T
    live = (flags & 2) != 0
    assert np.array_equal(blk[live], np.arange(indptr[-1]))
    assert np.array_equal(xk, brow[blk])
    assert np.array_equal(np.unique(tile), np.arange(NF))
    assert np.all(np.diff(tile) >= 0)
    assert (flags & 1).astype(bool).sum() == NF
    assert (flags & 4).astype(bool).sum() == NF
    assert flags[-1] & 4
    held = ~live[1:]
    assert np.array_equal(blk[1:][held], blk[:-1][held])


# (G, K tiles, output tiles, blocks kept per member, NB bucket, row tiles
# of x, grid steps a call): the benchmark's BSR calls at 90 % sparsity
GEOMETRIES = {
    "olmo-gate-up": (2, 30, 86, 258, 512, 16, 19136),
    "olmo-down": (1, 86, 30, 258, 258, 16, 4608),
    "moe-ragged-gate-up": (64, 16, 11, 18, 32, 160, 6880),
    "moe-ragged-down": (64, 11, 16, 18, 32, 160, 7680),
}


@pytest.mark.parametrize("name", list(GEOMETRIES))
def test_walk_length_at_cell_geometry(name):
    g, kt, nf, kept, nb, row_tiles, grid = GEOMETRIES[name]
    rng = np.random.default_rng(7)
    indptr = np.zeros((g, nf + 1), np.int32)
    brow = np.zeros((g, nb), np.int32)
    for m in range(g):
        cells = rng.choice(kt * nf, kept, replace=False)
        r, c = cells // nf, cells % nf
        order = np.lexsort((r, c))
        brow[m, :kept] = r[order]
        np.cumsum(np.bincount(c, minlength=nf), out=indptr[m, 1:])
    steps, length = _walk_steps(indptr, brow, kt)
    assert length == min(nf * min(nb, kt), nb + nf)
    # a ragged call walks one member per row tile, a batched call each
    members = 1 if name.startswith("moe") else g
    assert members * row_tiles * length == grid
    live = (steps[..., 3] & 2) != 0
    assert np.array_equal(live.sum(axis=1), indptr[:, -1])
    empty = (np.diff(indptr, axis=1) == 0).sum(axis=1)
    assert np.all(indptr[:, -1] + empty <= length)
