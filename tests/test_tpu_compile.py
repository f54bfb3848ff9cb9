"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers and compiles one kernel at the shapes
``chip_smoke.py`` drives (an ogbn-arxiv-scale graph at N = 128, its
out-of-core chunk and its SpMV lane, and the grouped qwen2-0.5b FFN
weights), so a kernel Mosaic refuses, a relayout copy of the slab payload
or a program that outgrows the chip fails here instead of on the chip.
The topology is described inside a fixture (never at import), so pytest
workers collect the same tests and only the worker running this file
loads the TPU compiler.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.kernels.bsr_spmm import (bsr_matmul_pallas,
                                    bsr_matmul_pallas_batched,
                                    bsr_matmul_pallas_ragged)
from repro.kernels.sextans_spmm import sextans_spmm_pallas
from repro.sparse_api.plan import row_split_spmm
from repro.sparse_api.tensor import PackedSpMM

HBM_BYTES = 16 * 10**9            # one v5e chip

# ogbn-arxiv scale (169,343 nodes) packed at TM=128, K0=4096: the seeded
# power-law graph of chip_smoke.py packs to LW=8192 slots per slab.
MB, NW, R, L, K0, TM = 1323, 42, 64, 128, 4096, 128
# The gap-kron graph cells (scale 18, 262,144 vertices) packed at TM=128,
# K0=4096: 2,048 row blocks by 64 windows, four 128-lane rows a slab.
KRON_MB, KRON_NW, KRON_R = 2048, 64, 4
# qwen2-0.5b FFN ``wi`` (896 x 4864) at 90% 128x128 block sparsity, 24
# layers in one group: 27 blocks each, padded to the 32-block bucket.
D_MODEL, D_FF, NB_PAD, LAYERS, TOKENS = 896, 4864, 32, 24, 256
# DeepSeek-V2-Lite's routed experts at 90% 128x128 block sparsity: 64
# experts of 2048 -> 1408 (gate, up) and 1408 -> 2048 (down), 18 tiles
# kept each; a 2,048-token chunk's 6 pairs a token sorted into 128-row
# tiles: round_up(2048*6 + 64*127, 128) rows.
MOE_E, MOE_D, MOE_FF, MOE_NB, MOE_ROWS = 64, 2048, 1408, 18, 20480
# Olmo-Hybrid-7B's FFN (3840 -> 11008 -> 3840) at 90% 128x128 block
# sparsity, a 2,048-token prefill chunk: gate and up as one group of two
# (258 blocks each in the 512-block bucket), down alone (258 blocks).
OLMO_D, OLMO_FF, OLMO_T = 3840, 11008, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    """The persistent compile cache is off around these compiles: an
    entry written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo, no_cache):
    """Shape-and-dtype factory placed on one described chip; ``row_major``
    also pins the array's layout to row-major."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(s, dt, row_major=False):
        where = (Format(Layout(major_to_minor=tuple(range(len(s)))), one_chip)
                 if row_major else one_chip)
        return jax.ShapeDtypeStruct(s, dt, sharding=where)

    return make


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total
    # the kernel reads the stored slab payload in place: no relayout copy
    assert mem.temp_size_in_bytes < mem.argument_size_in_bytes // 10, mem
    return mem


def _slabs(shape, lead, nw=NW, mb=MB, r=R):
    s = (*lead, mb, nw, r, L)
    return (shape(s, jnp.float32), shape(s, jnp.int32), shape(s, jnp.int32),
            shape((*lead, mb, nw), jnp.int32))


def _compile_hflex(shape, lead=(), n=128, tn=128, nw=NW, accumulate=False,
                   mb=MB, r=R, row_major=False):
    vals, cols, rows, q = _slabs(shape, lead, nw, mb, r)
    b = shape((*lead, nw * K0, n), jnp.float32, row_major)
    c = shape((*lead, mb * TM, n), jnp.float32, row_major)
    ab = shape(lead, jnp.float32)

    def f(vals, cols, rows, q, b, c, alpha, beta):
        return sextans_spmm_pallas(vals, cols, rows, q, b, c, alpha, beta,
                                   tm=TM, k0=K0, tn=tn, interpret=False,
                                   accumulate=accumulate)

    out = (Format(Layout(major_to_minor=(0, 1)), vals.sharding)
           if row_major else None)
    return _check(jax.jit(f, out_shardings=out)
                  .lower(vals, cols, rows, q, b, c, ab, ab).compile())


def test_hflex_resident(shape):
    _compile_hflex(shape)


def test_hflex_batched_group(shape):
    """Two arxiv-scale bucket-mates in one launch: ~11 GB of slabs."""
    _compile_hflex(shape, lead=(2,))


def test_hflex_accumulate_chunk(shape):
    """The out-of-core step at a quarter-payload budget: 4 windows."""
    _compile_hflex(shape, nw=4, accumulate=True)


def test_spmv_lane(shape):
    """N = 1 pads to 8 lanes and one column tile."""
    _compile_hflex(shape, n=8, tn=8)


@pytest.mark.parametrize("tn", [128, 8])
def test_hflex_kron_cells(shape, tn):
    """The graph cells' geometry at N = 128 (the tall lane) and at N = 1
    padded to 8 lanes (the SpMV lane): every slab is non-empty there, so
    each grid step builds the split B window the one-hot gather reads.
    B, C and the result are row-major, as the plan's padding makes them
    (a free (K, 8) parameter would be given a column-major layout and a
    relayout copy)."""
    _compile_hflex(shape, n=tn, tn=tn, nw=KRON_NW, mb=KRON_MB, r=KRON_R,
                   row_major=True)


def test_bsr_grouped_ffn(shape):
    x = shape((LAYERS, TOKENS, D_MODEL), jnp.float32)
    blocks = shape((LAYERS, NB_PAD, 128, 128), jnp.float32)
    brow = shape((LAYERS, NB_PAD), jnp.int32)
    indptr = shape((LAYERS, D_FF // 128 + 1), jnp.int32)

    def f(x, blocks, brow, indptr):
        return bsr_matmul_pallas_batched(x, blocks, brow, indptr, tb=128,
                                         tk=128, tf=128, interpret=False)

    _check(jax.jit(f).lower(x, blocks, brow, indptr).compile())


@pytest.mark.parametrize("g,k,f,nb", [(2, OLMO_D, OLMO_FF, 512),
                                      (None, OLMO_FF, OLMO_D, 258)])
def test_bsr_olmo_prefill(shape, g, k, f, nb):
    """The Olmo prefill cell's gate+up (batched) and down (single) calls:
    the walk's tables go to scalar prefetch flat, and the kernel keeps
    the name the trace metrics read."""
    lead = () if g is None else (g,)
    x = shape((*lead, OLMO_T, k), jnp.float32)
    blocks = shape((*lead, nb, 128, 128), jnp.float32)
    brow = shape((*lead, nb), jnp.int32)
    indptr = shape((*lead, f // 128 + 1), jnp.int32)
    call = bsr_matmul_pallas if g is None else bsr_matmul_pallas_batched

    def run(x, blocks, brow, indptr):
        return call(x, blocks, brow, indptr, interpret=False)

    compiled = jax.jit(run).lower(x, blocks, brow, indptr).compile()
    _check(compiled)
    assert "bsr_spmm" in compiled.as_text()


@pytest.mark.parametrize("k,f", [(MOE_D, MOE_FF), (MOE_FF, MOE_D)])
def test_bsr_ragged_moe(shape, k, f):
    """The ragged mode at the MoE cell's gate/up and down geometry."""
    x = shape((MOE_ROWS, k), jnp.float32)
    blocks = shape((MOE_E, MOE_NB, 128, 128), jnp.float32)
    brow = shape((MOE_E, MOE_NB), jnp.int32)
    indptr = shape((MOE_E, f // 128 + 1), jnp.int32)
    te = shape((MOE_ROWS // 128,), jnp.int32)
    used = shape((1,), jnp.int32)

    def g(x, blocks, brow, indptr, te, used):
        return bsr_matmul_pallas_ragged(x, blocks, brow, indptr, te, used,
                                        interpret=False)

    compiled = jax.jit(g).lower(x, blocks, brow, indptr, te, used).compile()
    _check(compiled)
    assert "bsr_spmm_ragged" in compiled.as_text()


def test_row_split_four_chips(topo, no_cache):
    """Phase (a) row-split over a described 2x2 host: each chip compiles
    the kernel on its own quarter of the slabs, and no collective moves
    the slab payload (only the (M, N) result is gathered)."""
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"))
    s = lambda shp, dt: jax.ShapeDtypeStruct(shp, dt)
    d = PackedSpMM(vals=s((MB, NW, R, L), jnp.float32),
                   cols=s((MB, NW, R, L), jnp.int32),
                   rows=s((MB, NW, R, L), jnp.int32),
                   q=s((MB, NW), jnp.int32), nse=s((MB, NW), jnp.int32),
                   m=169343, k=169343, tm=TM, k0=K0, chunk=8,
                   interleaved=True, nnz=0)
    traced, mbp, slab_spec, q_spec = row_split_spmm(
        d, mesh, 169343, 169343, 128, None, {"interpret": False})
    on = lambda spec: NamedSharding(mesh, spec)
    rep = on(jax.sharding.PartitionSpec())
    slab = (mbp, NW, R, L)
    args = (jax.ShapeDtypeStruct(slab, jnp.float32, sharding=on(slab_spec)),
            jax.ShapeDtypeStruct(slab, jnp.int32, sharding=on(slab_spec)),
            jax.ShapeDtypeStruct(slab, jnp.int32, sharding=on(slab_spec)),
            jax.ShapeDtypeStruct((mbp, NW), jnp.int32, sharding=on(q_spec)),
            jax.ShapeDtypeStruct((169343, 128), jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((169343, 128), jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=rep))
    compiled = jax.jit(traced, out_shardings=rep).lower(*args).compile()
    mem = _check(compiled)              # per-chip bytes
    assert mem.argument_size_in_bytes < 2 * 10**9   # a quarter of ~5.5 GB
    text = compiled.as_text()
    collectives = [ln for ln in text.splitlines()
                   if re.search(r"all-gather|all-to-all|all-reduce|"
                                r"collective-permute", ln)]
    payload = f",{NW},{R},{L}]"
    assert not [ln for ln in collectives if payload in ln], collectives
