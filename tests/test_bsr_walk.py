"""The BSR kernel's compact walk (``kernels/bsr_spmm.py``): each output
tile takes ``max(count, 1)`` steps over its stored blocks, in block order,
and the walk's static length comes from the shapes.

The kernel runs in interpret mode here.  Its single, batched and ragged
modes are compared with a float64 dense oracle on patterns that stress
the walk (empty output tiles, all-empty and fully dense members, members
padded to a shared block bucket, ragged tiles past the used count), and
the walk tables are checked at the benchmark cells' geometries.  The
single and batched modes also run at the wide row tiles that
``bsr_row_tile`` picks from N, and must give the 128-row tile's result
bit for bit; the plan pins that tile and counts its dispatches."""

import numpy as np
import jax.numpy as jnp
import pytest

import repro.sparse_api as sp
from repro.kernels.bsr_spmm import (bsr_matmul_pallas,
                                    bsr_matmul_pallas_batched,
                                    bsr_matmul_pallas_ragged, walk_tables)

TB, TK, TF = 8, 16, 16
KT, NF = 4, 5                     # K tiles, output tiles of the small cases
# wide row tiles, each run on 1,536 rows of x (6, 4 and 3 tiles) and
# compared with 12 tiles of 128 rows
WIDE_TB, WIDE_ROWS = (256, 384, 512), 1536

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


def _rows(tb):
    """Rows of ``x``: two tiles at the small cases' ``TB``, else
    :data:`WIDE_ROWS`."""
    return 2 * TB if tb == TB else WIDE_ROWS


def _same_as_128(y, call, tb):
    """At a wide row tile, the result is the 128-row tile's, exactly:
    each row is the same sum of the same block products in order."""
    if tb != TB:
        np.testing.assert_array_equal(y, np.asarray(call(tb=128)))


@pytest.mark.parametrize("tb", (TB,) + WIDE_TB)
@pytest.mark.parametrize("name", [n for n in PATTERNS if PATTERNS[n]])
def test_single_matches_dense(name, tb):
    """At a wide tile the payload keeps a block bucket larger than its
    blocks (the walk never reaches the padding)."""
    nb = None if tb == TB else KT * NF
    blocks, brow, indptr, dense = _pack(PATTERNS[name], seed=1, nb=nb)
    x = np.random.default_rng(2).standard_normal((_rows(tb), KT * TK))
    call = lambda tb: bsr_matmul_pallas(  # noqa: E731
        jnp.asarray(x, jnp.float32), jnp.asarray(blocks), jnp.asarray(brow),
        jnp.asarray(indptr), tb=tb, tk=TK, tf=TF)
    y = np.asarray(call(tb))
    _close(y, x.astype(np.float32).astype(np.float64) @ dense)
    _same_as_128(y, call, tb)
    for f in range(NF):                  # an empty output tile is zeros
        if indptr[f] == indptr[f + 1]:
            assert not y[:, f * TF:(f + 1) * TF].any()


def _stack(names, seed=3):
    """Members padded to the densest member's block count."""
    nb = max(len(PATTERNS[n]) for n in names)
    packs = [_pack(PATTERNS[n], seed + i, nb) for i, n in enumerate(names)]
    blocks, brow, indptr, dense = zip(*packs)
    return (jnp.asarray(np.stack(blocks)), jnp.asarray(np.stack(brow)),
            jnp.asarray(np.stack(indptr)), dense)


@pytest.mark.parametrize("tb", (TB,) + WIDE_TB)
@pytest.mark.parametrize("names", [
    ("all-empty", "dense"),
    ("empty-edge-tiles", "one-block", "empty-middle-tiles"),
    ("dense", "empty-edge-tiles", "all-empty"),
])
def test_batched_matches_dense(names, tb):
    """Unequal block counts padded to a shared bucket; an all-empty member
    comes back exact zeros."""
    blocks, brow, indptr, dense = _stack(names)
    x = np.random.default_rng(4).standard_normal(
        (len(names), _rows(tb), KT * TK)).astype(np.float32)
    call = lambda tb: bsr_matmul_pallas_batched(  # noqa: E731
        jnp.asarray(x), blocks, brow, indptr, tb=tb, tk=TK, tf=TF)
    y = np.asarray(call(tb))
    for g, name in enumerate(names):
        _close(y[g], x[g].astype(np.float64) @ dense[g])
        if not PATTERNS[name]:
            assert not y[g].any()
    _same_as_128(y, call, tb)


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
# of x, grid steps a call): the benchmark's BSR calls at 90 % sparsity.
# The Olmo calls' 2,048 tokens are one row tile (``bsr_row_tile``); the
# ragged calls keep 128-row tiles.
GEOMETRIES = {
    "olmo-gate-up": (2, 30, 86, 258, 512, 1, 1196),
    "olmo-down": (1, 86, 30, 258, 258, 1, 288),
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
    if not name.startswith("moe"):
        assert 2048 // sp.bsr_row_tile(2048) == row_tiles
    assert members * row_tiles * length == grid
    live = (steps[..., 3] & 2) != 0
    assert np.array_equal(live.sum(axis=1), indptr[:, -1])
    empty = (np.diff(indptr, axis=1) == 0).sum(axis=1)
    assert np.all(indptr[:, -1] + empty <= length)


@pytest.mark.parametrize("n,tile", [(1, 128), (32, 128), (128, 128),
                                    (384, 384), (2048, 2048), (2100, 128),
                                    (4096, 2048)])
def test_bsr_row_tile(n, tile):
    """The largest multiple of 128 that divides N rounded up to 128, at
    most 2,048: never more row padding than 128-row tiles."""
    assert sp.bsr_row_tile(n) == tile


BLK = 128


def _bsr(seed, m=2 * BLK, k=3 * BLK):
    """A (m, k) BSR tensor of 128x128 blocks keeping about half its tiles,
    and its dense weight."""
    rng = np.random.default_rng(seed)
    keep = rng.random((m // BLK, k // BLK)) < 0.5
    w = rng.standard_normal((m, k)).astype(np.float32)
    w *= np.kron(keep, np.ones((BLK, BLK), np.float32))
    return sp.from_dense(w, format=sp.Format.BSR, block=(BLK, BLK)), w


def test_plan_pins_row_tile_and_counts_wide_dispatches():
    """A prefill-shaped plan (N = 2048) records the 2,048-row tile in its
    executable key and counts its dispatches; a decode-shaped one (N = 32)
    keeps 128 and counts none; an explicit ``tn`` still wins.  Every tile
    gives the 128-row tile's result exactly."""
    t, w = _bsr(11)
    group = sp.stack_bsr([t, _bsr(12)[0]])
    rng = np.random.default_rng(13)
    stats = sp.PLAN_STATS
    for n, tile, wide in ((2048, 2048, 1), (32, 128, 0)):
        single = sp.plan(t, n, backend="pallas")
        grouped = sp.plan_group(group, n, backend="pallas")
        narrow = sp.plan(t, n, backend="pallas", tn=128)
        assert single.opts["tn"] == grouped.opts["tn"] == tile
        assert ("tn", tile) in single.exec_key[2]
        assert narrow.opts["tn"] == 128
        b = jnp.asarray(rng.standard_normal((t.shape[1], n)), jnp.float32)
        d0 = stats["bsr_wide_tile_dispatches"]
        y = np.asarray(single.run(b))
        grouped.run(jnp.stack([b, b]))
        assert stats["bsr_wide_tile_dispatches"] - d0 == 2 * wide
        d0 = stats["bsr_wide_tile_dispatches"]
        np.testing.assert_array_equal(y, np.asarray(narrow.run(b)))
        assert stats["bsr_wide_tile_dispatches"] == d0
        np.testing.assert_allclose(y, w.astype(np.float64) @ np.asarray(b),
                                   rtol=1e-4, atol=1e-3)
    keys = {sp.plan(t, n, backend="pallas").exec_key for n in (128, 2048)}
    assert len(keys) == 2


def test_ragged_plan_keeps_128_row_tiles():
    """The ragged mode's row tiles are per-expert segments: a plan over
    512 rows still walks four 128-row tiles (its ``te`` has one entry per
    tile) and counts no wide dispatch."""
    experts = sp.stack_bsr([_bsr(20 + e)[0] for e in range(3)])
    ws = [_bsr(20 + e)[1] for e in range(3)]
    rows = 4 * BLK
    p = sp.plan_ragged(experts, rows, backend="pallas")
    assert "tn" not in p.opts
    x = np.random.default_rng(21).standard_normal(
        (rows, experts.shape[1])).astype(np.float32)
    te = [0, 2, 1, 1]
    d0 = sp.PLAN_STATS["bsr_wide_tile_dispatches"]
    y = np.asarray(p.run(jnp.asarray(x), jnp.asarray(te, jnp.int32),
                         jnp.asarray([3], jnp.int32)))
    assert sp.PLAN_STATS["bsr_wide_tile_dispatches"] == d0
    for i, e in enumerate(te[:3]):
        r = slice(i * BLK, (i + 1) * BLK)
        np.testing.assert_allclose(y[r], x[r].astype(np.float64) @ ws[e].T,
                                   rtol=1e-4, atol=1e-3)
    assert not y[3 * BLK:].any()
