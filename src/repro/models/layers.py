"""Shared neural layers: norms, RoPE, GQA attention (memory-chunked),
FFN (dense / block-sparse via Sextans / MoE with expert parallelism).

Sharding is expressed through ``constrain`` (a no-op outside a mesh
context), keeping the model definitions mesh-agnostic; the step builders in
repro.distributed install the production mesh.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.tracing import span

from .common import Initializer, ModelConfig, compute_dtype

__all__ = [
    "mesh_context", "constrain",
    "linear", "rmsnorm_init", "rmsnorm", "rope", "attention_init", "attention_apply",
    "decode_attention_apply", "ffn_init", "ffn_apply", "moe_init", "moe_apply",
    "SparseLinear", "SparseLinearGroup", "SparseFFN", "SparseMoE",
]

# ---------------------------------------------------------------------------
# mesh context / sharding constraints
# ---------------------------------------------------------------------------

_MESH: contextvars.ContextVar[Optional[Mesh]] = contextvars.ContextVar("repro_mesh", default=None)
_AXIS_MAP: contextvars.ContextVar[Dict[str, Any]] = contextvars.ContextVar(
    "repro_axis_map", default={})


@contextlib.contextmanager
def mesh_context(mesh: Optional[Mesh], axis_map: Optional[Dict[str, Any]] = None):
    """Install a mesh + logical->physical axis mapping for ``constrain``.

    Model code names logical axes ("data", "model"); on the multi-pod mesh
    the mapping sends "data" -> ("pod", "data") so the batch shards across
    both pod and in-pod data axes.
    """
    tok = _MESH.set(mesh)
    tok2 = _AXIS_MAP.set(axis_map or {})
    try:
        yield
    finally:
        _MESH.reset(tok)
        _AXIS_MAP.reset(tok2)


def constrain(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint if a mesh is installed, else identity.

    Dims not divisible by the requested axis product are left unsharded:
    SPMD padding of indivisible dims leaks garbage into reductions (seen as
    NaN gradients), and a partial constraint is always legal.
    """
    mesh = _MESH.get()
    if mesh is None:
        return x
    amap = _AXIS_MAP.get()
    phys = []
    for i, a in enumerate(spec):
        ax = amap.get(a, a) if isinstance(a, str) else a
        if ax is None:
            phys.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for name in axes:
            size *= mesh.shape[name]
        if i < x.ndim and size > 1 and x.shape[i] % size == 0:
            phys.append(ax)
        else:
            phys.append(None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*phys)))


def _scoped(name):
    import functools
    import jax as _jax

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*a, **k):
            with _jax.named_scope(name):
                return fn(*a, **k)
        return inner
    return wrap


def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


def linear(p: Dict[str, Any], x: jax.Array, dtype) -> jax.Array:
    y = jnp.dot(x.astype(dtype), p["w"].astype(dtype))
    if "b" in p:
        y = y + p["b"].astype(dtype)
    return y


def rmsnorm_init(init: Initializer, d: int) -> Dict[str, Any]:
    return {"scale": init.ones(d)}


def rmsnorm(p: Dict[str, Any], x: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (B, S, H, hd); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def attention_init(init: Initializer, cfg: ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    p = {
        "wq": init.dense(d, cfg.q_dim),
        "wk": init.dense(d, cfg.kv_dim),
        "wv": init.dense(d, cfg.kv_dim),
        "wo": init.dense(cfg.q_dim, d),
    }
    if cfg.qkv_bias:
        p["bq"] = init.zeros(cfg.q_dim)
        p["bk"] = init.zeros(cfg.kv_dim)
        p["bv"] = init.zeros(cfg.kv_dim)
    return p


def _chunked_attention(
    q: jax.Array,      # (B, Sq, H, hd)
    k: jax.Array,      # (B, Sk, Hkv, hd)
    v: jax.Array,      # (B, Sk, Hkv, hd)
    q_offset,          # scalar: absolute position of q[0]
    causal: bool,
    window: Optional[int],
    chunk_q: int,
    chunk_k: int,
    skip_masked_blocks: bool = False,
    k_offset=0,
    probs_bf16: bool = False,
) -> jax.Array:
    """Online-softmax (flash-style) attention in pure jnp: O(S·chunk) memory.

    The KV loop is a lax.scan with running (max, sumexp, acc); the Q chunks
    are vmapped. Masking by absolute position keeps it correct under
    sequence-sharded Q (SP) and KV caches.
    """
    b, sq, h, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    scale = 1.0 / math.sqrt(hd)

    cq = min(chunk_q, sq)
    while sq % cq:
        cq //= 2
    if skip_masked_blocks and causal:
        chunk_k = cq          # pair-list needs square blocks
    ck = min(chunk_k, sk)
    while sk % ck:
        ck //= 2
    nq, nk = sq // cq, sk // ck

    # (B, nq, cq, H, hd) -> (nq, B, H, cq, hd)
    qc = q.reshape(b, nq, cq, h, hd).transpose(1, 0, 3, 2, 4) * scale
    kc = k.reshape(b, nk, ck, hkv, hd).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, nk, ck, hkv, hd).transpose(1, 0, 3, 2, 4)

    q_pos0 = jnp.asarray(q_offset, jnp.int32)
    k_pos0 = jnp.asarray(k_offset, jnp.int32)

    def block_update(qi, ki, qblk, kblk, vblk, m, l, acc):
        """One (q-chunk, kv-chunk) online-softmax update."""
        qpos = q_pos0 + qi * cq + jnp.arange(cq, dtype=jnp.int32)
        kpos = k_pos0 + ki * ck + jnp.arange(ck, dtype=jnp.int32)
        kb = jnp.repeat(kblk, g, axis=1)
        vb = jnp.repeat(vblk, g, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", qblk, kb,
                       preferred_element_type=jnp.float32)
        mask = jnp.ones((cq, ck), bool)
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window is not None:
            mask &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        if probs_bf16:
            # flash-standard: store p low-precision, keep m/l stats in f32
            p = p.astype(jnp.bfloat16)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + p.astype(jnp.float32).sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    aligned = isinstance(q_offset, int) and isinstance(k_offset, int) \
        and q_offset == k_offset
    if causal and skip_masked_blocks and cq == ck and nq > 1 and aligned:
        return _pairlist_attention(qc, kc, vc, block_update, nq, cq, window,
                                   b, h, hd, sq)

    def per_qchunk(qi, qblk):  # qblk: (B, H, cq, hd)
        qpos = q_pos0 + qi * cq + jnp.arange(cq, dtype=jnp.int32)

        def kv_step(carry, xs):
            m, l, acc = carry
            ki, kblk, vblk = xs
            kpos = k_pos0 + ki * ck + jnp.arange(ck, dtype=jnp.int32)
            # scores: (B, H, cq, ck); GQA: repeat kv heads g times
            kb = jnp.repeat(kblk, g, axis=1)
            vb = jnp.repeat(vblk, g, axis=1)
            s = jnp.einsum("bhqd,bhkd->bhqk", qblk, kb,
                           preferred_element_type=jnp.float32)
            mask = jnp.ones((cq, ck), bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if window is not None:
                mask &= qpos[:, None] - kpos[None, :] < window
            s = jnp.where(mask[None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(-1))
            # guard fully-masked rows (m_new == -inf)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(mask[None, None], p, 0.0)
            if probs_bf16:
                p = p.astype(jnp.bfloat16)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = l * corr + p.astype(jnp.float32).sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, cq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, cq), jnp.float32)
        a0 = jnp.zeros((b, h, cq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk, dtype=jnp.int32), kc, vc))
        out = acc / jnp.maximum(l, 1e-20)[..., None]
        return out  # (B, H, cq, hd)

    out = jax.lax.map(lambda xs: per_qchunk(*xs),
                      (jnp.arange(nq, dtype=jnp.int32), qc))
    # (nq, B, H, cq, hd) -> (B, nq*cq, H, hd)
    out = out.transpose(1, 0, 3, 2, 4).reshape(b, sq, h, hd)
    return out


def _shard_map_attention(q, k, v, q_off, causal, window, cfg, mesh):
    """Sequence-parallel attention via shard_map (perf lever H-sp).

    Plain-jit SP (sharding constraints on the chunk loop) lets the
    partitioner place per-block collectives *inside* the score einsum —
    measured at 1.4e12 wire bytes/step on qwen2-0.5b prefill. Here each
    model-rank owns a contiguous S/m query slab and loops locally; KV is
    all-gathered once per layer (the intended SP cost). Masks use absolute
    positions so the shard offset is just an index shift."""
    from jax.experimental.shard_map import shard_map

    b_, s, h_, hd_ = q.shape
    msize = mesh.shape.get("model", 1)
    da = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dsize = 1
    for a in da:
        dsize *= mesh.shape[a]
    baxis = (da if len(da) > 1 else da[0]) if (dsize > 1 and b_ % dsize == 0) else None
    if msize <= 1 or s % msize or (s // msize) % 8:
        q = constrain(q, "data", "model", None, None)
        out = _chunked_attention(q, k, v, q_off, causal, window,
                                 cfg.attn_chunk_q, cfg.attn_chunk_k,
                                 cfg.attn_skip_masked_blocks,
                                 probs_bf16=cfg.attn_probs_bf16)
        return constrain(out, "data", "model", None, None)

    s_loc = s // msize
    ck = min(cfg.attn_chunk_k, s)
    static_window = window if isinstance(window, int) else None

    def local(qs, ks, vs, off):
        rank = jax.lax.axis_index("model")
        my_off = off + rank * s_loc
        if causal and static_window is not None and static_window < s - s_loc:
            # SWA slab (lever H-swa): this rank's queries can only see keys
            # in [my_off - window, my_off + s_loc) — slice that slab from
            # the gathered KV instead of sweeping all S keys.
            pad = -(-(static_window) // ck) * ck
            slab = min(s, s_loc + pad)
            start = jnp.clip(my_off - pad, 0, s - slab)
            ks_ = jax.lax.dynamic_slice_in_dim(ks, start, slab, axis=1)
            vs_ = jax.lax.dynamic_slice_in_dim(vs, start, slab, axis=1)
            return _chunked_attention(
                qs, ks_, vs_, my_off, causal=causal, window=window,
                chunk_q=cfg.attn_chunk_q, chunk_k=cfg.attn_chunk_k,
                skip_masked_blocks=False, k_offset=start,
                probs_bf16=cfg.attn_probs_bf16)
        return _chunked_attention(
            qs, ks, vs, my_off, causal=causal, window=window,
            chunk_q=cfg.attn_chunk_q, chunk_k=cfg.attn_chunk_k,
            skip_masked_blocks=False, probs_bf16=cfg.attn_probs_bf16)

    qspec = P(baxis, "model", None, None)
    kvspec = P(baxis, None, None, None)
    fn = shard_map(local, mesh=mesh,
                   in_specs=(qspec, kvspec, kvspec, P()),
                   out_specs=qspec, check_rep=False)
    return fn(q, k, v, jnp.asarray(q_off, jnp.int32))


def _pairlist_attention(qc, kc, vc, block_update, nq, cq, window, b, h, hd, sq):
    """Causal attention over a static (qi, ki<=qi) pair list — skips the
    fully-masked upper-triangle blocks entirely (~2x fewer block updates
    than the rectangular nq x nk sweep; with a sliding window, blocks older
    than the window are dropped too). Hillclimb lever H-attn (§Perf)."""
    import numpy as np

    pairs = []
    for qi in range(nq):
        k_lo = 0
        if window is not None and isinstance(window, int):
            k_lo = max(0, (qi * cq - (window + cq - 1)) // cq)
        for ki in range(k_lo, qi + 1):
            pairs.append((qi, ki))
    qi_a = jnp.asarray(np.array([p[0] for p in pairs], np.int32))
    ki_a = jnp.asarray(np.array([p[1] for p in pairs], np.int32))
    fresh_a = jnp.asarray(np.array(
        [1] + [int(pairs[i][0] != pairs[i - 1][0]) for i in range(1, len(pairs))],
        np.int32))
    last_a = jnp.asarray(np.array(
        [int(i + 1 == len(pairs) or pairs[i + 1][0] != pairs[i][0])
         for i in range(len(pairs))], np.int32))

    m0 = jnp.full((b, h, cq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, cq), jnp.float32)
    a0 = jnp.zeros((b, h, cq, hd), jnp.float32)
    out0 = jnp.zeros((nq, b, h, cq, hd), jnp.float32)

    def step(carry, xs):
        out_buf, m, l, acc = carry
        qi, ki, fresh, last = xs
        m = jnp.where(fresh == 1, m0, m)
        l = jnp.where(fresh == 1, l0, l)
        acc = jnp.where(fresh == 1, a0, acc)
        qblk = jax.lax.dynamic_index_in_dim(qc, qi, 0, keepdims=False)
        kblk = jax.lax.dynamic_index_in_dim(kc, ki, 0, keepdims=False)
        vblk = jax.lax.dynamic_index_in_dim(vc, ki, 0, keepdims=False)
        m2, l2, acc2 = block_update(qi, ki, qblk, kblk, vblk, m, l, acc)
        done = acc2 / jnp.maximum(l2, 1e-20)[..., None]
        out_buf = jax.lax.cond(
            last == 1,
            lambda ob: jax.lax.dynamic_update_index_in_dim(ob, done, qi, 0),
            lambda ob: ob,
            out_buf)
        return (out_buf, m2, l2, acc2), None

    (out_buf, _, _, _), _ = jax.lax.scan(
        step, (out0, m0, l0, a0), (qi_a, ki_a, fresh_a, last_a))
    return out_buf.transpose(1, 0, 3, 2, 4).reshape(b, sq, h, hd)


@_scoped("attention")
def attention_apply(
    p: Dict[str, Any],
    cfg: ModelConfig,
    x: jax.Array,                 # (B, S, D)
    positions: jax.Array,         # (S,) or (B, S)
    causal: bool = True,
    window: Optional[int] = None,
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,  # cross-attn
) -> jax.Array:
    dtype = compute_dtype(cfg)
    b, s, _ = x.shape
    q = jnp.dot(x.astype(dtype), p["wq"].astype(dtype))
    if "bq" in p:
        q = q + p["bq"].astype(dtype)
    q = q.reshape(b, s, cfg.num_heads, cfg.hd)
    if kv_override is None:
        k = jnp.dot(x.astype(dtype), p["wk"].astype(dtype))
        v = jnp.dot(x.astype(dtype), p["wv"].astype(dtype))
        if "bk" in p:
            k = k + p["bk"].astype(dtype)
            v = v + p["bv"].astype(dtype)
        k = k.reshape(b, s, cfg.num_kv_heads, cfg.hd)
        v = v.reshape(b, s, cfg.num_kv_heads, cfg.hd)
        k = rope(k, positions, cfg.rope_theta)
        q = rope(q, positions, cfg.rope_theta)
    else:
        k, v = kv_override
        # cross-attention: no RoPE (enc-dec absolute embeddings)
    # SP: shard the query sequence over the model axis for the O(S^2) op.
    # Full-sequence callers always pass positions = arange(S) (origin 0); a
    # static offset keeps the causal pair-list static.
    q_off = positions[..., 0] if positions.ndim > 1 else 0
    mesh = _MESH.get()
    if cfg.sp_attention and mesh is not None and "model" in mesh.axis_names:
        out = _shard_map_attention(
            q, k, v, q_off, causal, window, cfg, mesh).astype(dtype)
    else:
        q = constrain(q, "data", "model", None, None)
        out = _chunked_attention(
            q, k, v, q_off,
            causal=causal, window=window,
            chunk_q=cfg.attn_chunk_q, chunk_k=cfg.attn_chunk_k,
            skip_masked_blocks=cfg.attn_skip_masked_blocks,
            probs_bf16=cfg.attn_probs_bf16,
        ).astype(dtype)
        out = constrain(out, "data", "model", None, None)
    out = out.reshape(b, s, cfg.q_dim)
    y = jnp.dot(out, p["wo"].astype(dtype))
    return constrain(y, "data", None, None)


def cross_kv(p: Dict[str, Any], cfg: ModelConfig, enc_out: jax.Array) -> Tuple[jax.Array, jax.Array]:
    dtype = compute_dtype(cfg)
    b, s, _ = enc_out.shape
    k = linear({"w": p["wk"]} | ({"b": p["bk"]} if "bk" in p else {}), enc_out, dtype)
    v = linear({"w": p["wv"]} | ({"b": p["bv"]} if "bv" in p else {}), enc_out, dtype)
    return (k.reshape(b, s, cfg.num_kv_heads, cfg.hd),
            v.reshape(b, s, cfg.num_kv_heads, cfg.hd))


@_scoped("attention")
def decode_attention_apply(
    p: Dict[str, Any],
    cfg: ModelConfig,
    x: jax.Array,                    # (B, 1, D)
    position: jax.Array,             # (B,) current position
    k_cache: jax.Array,              # (B, Smax, Hkv, hd)
    v_cache: jax.Array,
    window: Optional[int] = None,
    update_cache: bool = True,
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-token decode. KV cache is model-axis sharded on Smax; XLA
    turns the softmax/PV reductions into the cross-chip flash-decoding
    combine."""
    dtype = compute_dtype(cfg)
    b = x.shape[0]
    q = jnp.dot(x.astype(dtype), p["wq"].astype(dtype))
    if "bq" in p:
        q = q + p["bq"].astype(dtype)
    q = q.reshape(b, 1, cfg.num_heads, cfg.hd)
    if kv_override is None:
        k = jnp.dot(x.astype(dtype), p["wk"].astype(dtype))
        v = jnp.dot(x.astype(dtype), p["wv"].astype(dtype))
        if "bk" in p:
            k = k + p["bk"].astype(dtype)
            v = v + p["bv"].astype(dtype)
        k = k.reshape(b, 1, cfg.num_kv_heads, cfg.hd)
        v = v.reshape(b, 1, cfg.num_kv_heads, cfg.hd)
        pos_b = position.reshape(b, 1)
        q = rope(q, pos_b, cfg.rope_theta)
        k = rope(k, pos_b, cfg.rope_theta)
        if update_cache:
            k_cache = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(c, u, i, 0))(
                k_cache, k[:, 0:1].astype(k_cache.dtype), position)
            v_cache = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(c, u, i, 0))(
                v_cache, v[:, 0:1].astype(v_cache.dtype), position)
    smax = k_cache.shape[1]
    g = cfg.num_heads // cfg.num_kv_heads
    scale = 1.0 / math.sqrt(cfg.hd)
    kk = jnp.repeat(k_cache.astype(dtype), g, axis=2)   # (B, Smax, H, hd)
    vv = jnp.repeat(v_cache.astype(dtype), g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q * scale, kk,
                   preferred_element_type=jnp.float32)   # (B, H, 1, Smax)
    kpos = jnp.arange(smax, dtype=jnp.int32)
    mask = kpos[None, :] <= position[:, None]
    if window is not None:
        mask &= position[:, None] - kpos[None, :] < window
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w.astype(dtype), vv,
                     preferred_element_type=jnp.float32).astype(dtype)
    out = out.reshape(b, 1, cfg.q_dim)
    y = jnp.dot(out, p["wo"].astype(dtype))
    return y, k_cache, v_cache


# ---------------------------------------------------------------------------
# FFN: dense, and MoE with capacity-based expert parallelism
# ---------------------------------------------------------------------------


def ffn_init(init: Initializer, d: int, ff: int) -> Dict[str, Any]:
    return {
        "wi": init.dense(d, ff),       # up
        "wg": init.dense(d, ff),       # gate (SwiGLU)
        "wo": init.dense(ff, d),       # down
    }


@_scoped("ffn")
def ffn_apply(p: Dict[str, Any], cfg: ModelConfig, x: jax.Array) -> jax.Array:
    dtype = compute_dtype(cfg)
    act = _act(cfg.act)
    h = act(jnp.dot(x.astype(dtype), p["wg"].astype(dtype))) * jnp.dot(
        x.astype(dtype), p["wi"].astype(dtype))
    h = constrain(h, "data", None, "model")
    y = jnp.dot(h, p["wo"].astype(dtype))
    return constrain(y, "data", None, None)


def moe_init(init: Initializer, cfg: ModelConfig) -> Dict[str, Any]:
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    p = {
        "router": init.dense(d, e, scale=0.02),
        "wi": init.dense(e, d, ff),
        "wg": init.dense(e, d, ff),
        "wo": init.dense(e, ff, d),
    }
    if cfg.shared_expert:
        p["shared"] = ffn_init(init, d, cfg.shared_expert_ff or ff)
    return p


def _moe_route(router: jax.Array, cfg: ModelConfig, xt: jax.Array, dtype):
    """Shared top-k capacity router (dense and sparse-expert MoE).

    ``xt``: (g, tg, d) grouped tokens.  Returns ``(combine, dispatch,
    cap)`` — both (g, tg, e, cap) — the GShard dispatch/combine pair that
    routes each token's top-k experts into per-expert capacity buffers.
    """
    g, tg, _ = xt.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    cap = max(4, int(math.ceil(tg * k / e * cfg.moe_capacity_factor)))
    cap = min(cap, tg)
    logits = jnp.einsum("gtd,de->gte", xt.astype(dtype), router.astype(dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, idx = jax.lax.top_k(probs, k)                     # (g, tg, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # position of each (token, slot) within its expert's capacity buffer
    oh = jax.nn.one_hot(idx, e, dtype=jnp.int32)            # (g, tg, k, e)
    ohf = oh.reshape(g, tg * k, e)
    pos = jnp.cumsum(ohf, axis=1) - 1                       # (g, tg*k, e)
    pos = (pos * ohf).sum(-1).reshape(g, tg, k)             # (g, tg, k)
    keep = pos < cap
    gate = gate * keep

    # dispatch/combine tensors: (g, tg, e, cap)
    poh = jax.nn.one_hot(pos, cap, dtype=dtype) * keep[..., None]
    eoh = jax.nn.one_hot(idx, e, dtype=dtype)
    combine = jnp.einsum("gtke,gtkc->gtec", eoh * gate[..., None].astype(dtype), poh)
    dispatch = jnp.einsum("gtke,gtkc->gtec", eoh, poh)
    combine = constrain(combine, "data", None, "model", None)
    dispatch = constrain(dispatch, "data", None, "model", None)
    return combine, dispatch, cap


@_scoped("moe")
def moe_apply(p: Dict[str, Any], cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """GShard-style capacity MoE with expert parallelism over `model`.

    Tokens are grouped; per group a (Tg, E, C) combine/dispatch pair routes
    top-k tokens into per-expert capacity buffers. Expert weights are
    sharded over the model axis on E, so the expert matmuls are local and
    the only EP collective is the combine contraction over E.
    """
    dtype = compute_dtype(cfg)
    b, s, d = x.shape
    t = b * s
    tg = min(cfg.moe_group_size, t)
    g = t // tg
    assert g * tg == t, f"tokens {t} not divisible by group {tg}"

    xt = x.reshape(g, tg, d)
    xt = constrain(xt, "data", None, None)
    combine, dispatch, cap = _moe_route(p["router"], cfg, xt, dtype)

    # expert input: (g, e, cap, d), sharded (data, model)
    ein = jnp.einsum("gtd,gtec->gecd", xt.astype(dtype), dispatch)
    ein = constrain(ein, "data", "model", None, None)
    act = _act(cfg.act)
    h = act(jnp.einsum("gecd,edf->gecf", ein, p["wg"].astype(dtype))) * jnp.einsum(
        "gecd,edf->gecf", ein, p["wi"].astype(dtype))
    eout = jnp.einsum("gecf,efd->gecd", h, p["wo"].astype(dtype))
    eout = constrain(eout, "data", "model", None, None)

    y = jnp.einsum("gecd,gtec->gtd", eout, combine)
    y = constrain(y, "data", None, None)
    y = y.reshape(b, s, d)
    if cfg.shared_expert:
        y = y + ffn_apply(p["shared"], cfg, x)
    return y.astype(dtype)


# ---------------------------------------------------------------------------
# SparseLinear: trainable block-sparse projection on the unified sparse API
# ---------------------------------------------------------------------------


def _prune_blocks(w, block: Tuple[int, int], density: float):
    """Magnitude (block-L2) pruning of a dense ``(d_in, d_out)`` weight:
    keep the top-``density`` fraction of ``(bi, bo)`` tiles by L2 norm,
    zero the rest.  A width that is not a multiple of its tile is covered
    by ``ceil(width / tile)`` tiles whose pad part is zero; the result keeps
    the logical shape.  Ties at the threshold are all kept, so the survivor
    count can exceed ``round(density * n_tiles)`` by the tie multiplicity
    (the grouped lane tolerates ragged kept-block counts)."""
    import numpy as np

    bi, bo = block
    d_in, d_out = w.shape
    nbi, nbo = -(-d_in // bi), -(-d_out // bo)
    wp = np.zeros((nbi * bi, nbo * bo), w.dtype)
    wp[:d_in, :d_out] = w
    norms = np.linalg.norm(wp.reshape(nbi, bi, nbo, bo), axis=(1, 3))
    keep_n = max(1, int(round(density * norms.size)))
    thresh = np.sort(norms.reshape(-1))[-keep_n]
    mask = norms >= thresh
    wp = (wp.reshape(nbi, bi, nbo, bo)
          * mask[:, None, :, None]).reshape(nbi * bi, nbo * bo)
    return wp[:d_in, :d_out]


class SparseLinear:
    """``y = x @ W`` for a block-pruned weight, on ``repro.sparse_api``.

    The sparsity *structure* (kept blocks, pointer lists) is static and
    lives on this object as a :class:`~repro.sparse_api.SparseTensor`
    skeleton of shape (d_out, d_in) — i.e. ``W^T`` in the spmm left-operand
    orientation.  The trainable payload is the plain ``(NB, TK, TF)`` float
    array returned from :meth:`create` as ``params["w"]``: it flows through
    the existing AdamW/ZeRO machinery untouched, and because ``spmm`` is
    differentiable (``jax.custom_vjp``), pruned layers *train* — gradients
    reach exactly the stored blocks.
    """

    def __init__(self, skeleton):
        self.skeleton = skeleton                 # SparseTensor (d_out, d_in)
        self._plans: Dict[Any, Any] = {}         # (B, backend, okey) -> SpmmPlan

    @property
    def d_in(self) -> int:
        return self.skeleton.shape[1]

    @property
    def d_out(self) -> int:
        return self.skeleton.shape[0]

    @property
    def density(self) -> float:
        return self.skeleton.density

    @classmethod
    def create(cls, init: Initializer, d_in: int, d_out: int,
               block: Tuple[int, int] = (128, 128),
               density: float = 0.5) -> Tuple["SparseLinear", Dict[str, Any]]:
        """Init a dense weight, keep the top-``density`` fraction of
        (block x block) tiles by L2 norm, pack the survivors.  Returns
        (layer, params) with ``params["w"]`` the trainable block values."""
        import numpy as np

        from repro.sparse_api import Format, from_dense

        bi, bo = block
        w = _prune_blocks(np.asarray(init.dense(d_in, d_out), np.float32),
                          block, density)
        skeleton = from_dense(w.T, format=Format.BSR, block=(bo, bi))
        return cls(skeleton), {"w": skeleton.values}

    def plan_for(self, batch: int, *, backend: str = "auto", **opts):
        """Serving path: an :class:`~repro.sparse_api.SpmmPlan` for a fixed
        flattened batch size, cached on the layer.  ``__call__`` with
        ``use_plan=True`` routes through it, substituting the current weight
        values per call (no repack, no retrace)."""
        from repro.sparse_api import plan as _plan

        key = (int(batch), backend, tuple(sorted(opts.items())))
        pl = self._plans.get(key)
        if pl is None:
            pl = _plan(self.skeleton, int(batch), backend=backend, **opts)
            self._plans[key] = pl
        return pl

    def __call__(self, params: Dict[str, Any], x: jax.Array, *,
                 backend: str = "auto", use_plan: bool = False,
                 **opts) -> jax.Array:
        from repro.sparse_api import spmm

        with span("sextans.layer.linear"):
            lead = x.shape[:-1]
            xb = x.reshape(-1, self.d_in)
            if use_plan:
                # Inference-only fast path (plans are AOT executables, not
                # differentiable): pass the live weights as the values
                # operand.
                pl = self.plan_for(xb.shape[0], backend=backend, **opts)
                y = pl.run(xb.T, values=params["w"]).T        # (B, d_out)
            else:
                a = self.skeleton.with_values(params["w"])
                y = spmm(a, xb.T, backend=backend, **opts).T  # (B, d_out)
            return y.reshape(*lead, self.d_out)


# ---------------------------------------------------------------------------
# Grouped execution: expert/layer groups of pruned weights as ONE dispatch
# ---------------------------------------------------------------------------


class SparseLinearGroup:
    """G same-geometry :class:`SparseLinear` layers as ONE grouped dispatch.

    The classic pruned-serving shape — L transformer layers' q-projections,
    E expert FFN matrices — is many small *same-geometry* BSR weights.  The
    skeletons stack once (``stack_bsr``) behind a leading group axis; per
    call the only work is a values stack plus a single batched spmm, so the
    whole group costs one kernel launch instead of G.

    ``use_plan=True`` routes through a cached
    :func:`repro.sparse_api.plan_group` executable (AOT, inference-only);
    the default path is the differentiable batched ``spmm``.  For pooled
    serving, :meth:`submit` enqueues the members on a
    :class:`repro.launch.serve.SpmmScheduler`, whose bucketed-geometry
    grouping flushes them as one dispatch alongside any other bucket-mates.
    """

    def __init__(self, layers):
        from repro.sparse_api import stack_bsr

        layers = list(layers)
        if not layers:
            raise ValueError("SparseLinearGroup needs at least one layer")
        self.layers = layers
        with span("sextans.pack"):
            self.skeleton = stack_bsr([l.skeleton for l in layers])
        self._plans: Dict[Any, Any] = {}

    @property
    def batch(self) -> int:
        return len(self.layers)

    @property
    def d_in(self) -> int:
        return self.layers[0].d_in

    @property
    def d_out(self) -> int:
        return self.layers[0].d_out

    def stack_values(self, values_list) -> jax.Array:
        """Member payloads ``(nb_g, TK, TF)`` -> the stacked
        ``(G, NB_pad, TK, TF)`` payload.  Pad slots are zero; the grouped
        VJP masks them, so stacked values remain trainable."""
        with span("sextans.layer.stack_values"):
            nb_pad = self.skeleton.values.shape[1]
            vs = []
            for v in values_list:
                v = jnp.asarray(v)
                vs.append(jnp.pad(v, ((0, nb_pad - v.shape[0]), (0, 0),
                                      (0, 0))))
            return jnp.stack(vs)

    def plan_for(self, batch: int, *, backend: str = "auto", **opts):
        from repro.sparse_api import plan_group

        key = (int(batch), backend, tuple(sorted(opts.items())))
        pl = self._plans.get(key)
        if pl is None:
            pl = plan_group(self.skeleton, int(batch), backend=backend, **opts)
            self._plans[key] = pl
        return pl

    def __call__(self, params_list, x: jax.Array, *, backend: str = "auto",
                 use_plan: bool = False, **opts) -> jax.Array:
        """All G members in one grouped dispatch.

        ``x``: (B, d_in) shared input or (G, B, d_in) per-member inputs.
        Returns (G, B, d_out).
        """
        from repro.sparse_api import spmm

        with span("sextans.layer.group"):
            vals = self.stack_values([p["w"] for p in params_list])
            if x.ndim == 2:
                x = jnp.broadcast_to(x[None], (self.batch, *x.shape))
            xb = jnp.swapaxes(x, -1, -2)                  # (G, d_in, B)
            if use_plan:
                pl = self.plan_for(x.shape[1], backend=backend, **opts)
                y = pl.run(xb, values=vals)
            else:
                y = spmm(self.skeleton.with_values(vals), xb,
                         backend=backend, **opts)
            return jnp.swapaxes(y, -1, -2)                # (G, B, d_out)

    def submit(self, scheduler, params_list, x) -> list:
        """Enqueue one pre-packed request per member on an
        :class:`repro.launch.serve.SpmmScheduler`.  Same-geometry members
        share a group key, so a flush executes them as one batched
        dispatch; returns the per-member tickets/futures."""
        import numpy as np

        from repro.launch.serve import SpmmRequest

        xb = np.asarray(x).T                          # (d_in, B)
        return [scheduler.submit(SpmmRequest(
                    a=l.skeleton.with_values(p["w"]), b=xb))
                for l, p in zip(self.layers, params_list)]


class SparseFFN:
    """Gated FFN of block-pruned weights, ``down(act(gate(x)) * up(x))``,
    served as pruned FFN layers are: ``gate`` and ``up`` as ONE
    :class:`SparseLinearGroup` dispatch, ``down`` as a
    :class:`SparseLinear`.  Params: ``{"gate": {"w"}, "up": {"w"},
    "down": {"w"}}``.  Widths need not be multiples of the tile."""

    def __init__(self, gate: SparseLinear, up: SparseLinear,
                 down: SparseLinear):
        self.gate_up = SparseLinearGroup([gate, up])
        self.down = down

    @classmethod
    def create(cls, init: Initializer, d: int, ff: int,
               block: Tuple[int, int] = (128, 128),
               density: float = 0.5) -> Tuple["SparseFFN", Dict[str, Any]]:
        bi, bo = block
        (g, pg), (u, pu) = (SparseLinear.create(init, d, ff, block=block,
                                                density=density)
                            for _ in range(2))
        dn, pd = SparseLinear.create(init, ff, d, block=(bo, bi),
                                     density=density)
        return cls(g, u, dn), {"gate": pg, "up": pu, "down": pd}

    def __call__(self, p: Dict[str, Any], x: jax.Array, *, act: str = "silu",
                 backend: str = "auto", use_plan: bool = False,
                 **opts) -> jax.Array:
        lead = x.shape[:-1]
        gu = self.gate_up([p["gate"], p["up"]], x.reshape(-1, x.shape[-1]),
                          backend=backend, use_plan=use_plan, **opts)
        y = self.down(p["down"], _glu(gu, act=act), backend=backend,
                      use_plan=use_plan, **opts)
        return y.reshape(*lead, y.shape[-1])


@functools.partial(jax.jit, static_argnames=("act",))
def _glu(g, u=None, *, act: str):
    """``act(g) * u``; with one argument, ``g`` stacks gate and up."""
    if u is None:
        g, u = g[0], g[1]
    return _act(act)(g) * u


@functools.partial(jax.jit, static_argnames=(
    "k", "norm", "scale", "tile", "rows"))
def _moe_dispatch(router, x, stats, *, k: int, norm: bool, scale: float,
                  tile: int, rows: int):
    """Dropless top-``k`` routing of the ``(T, d)`` tokens ``x`` and their
    expert-sorted row layout.

    Softmax over every expert's float32 logit (matmul at ``HIGHEST``),
    greedy top-k; gates are the softmax scores, renormalised over the k
    only if ``norm``, then times ``scale``.  The ``T*k`` token-expert pairs
    are sorted by expert, and expert ``e``'s segment starts at a
    ``tile``-aligned row of one buffer of ``rows`` rows, so no pair is
    dropped and no tensor has an expert-by-capacity axis.  Returns the
    buffer ``(rows, d)`` (unused rows zero), the expert of each row tile,
    the count of tiles in use ``(1,)``, each pair's row ``(T, k)``, the
    gates ``(T, k)`` and ``stats`` plus this call's per-expert loads and
    tiles ``(2, E)``."""
    t, d = x.shape
    e = router.shape[1]
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    gate, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    if norm:
        gate = gate / gate.sum(-1, keepdims=True)
    gate = gate * scale
    pair_e = idx.reshape(-1)                      # pair p: token p // k
    order = jnp.argsort(pair_e, stable=True)
    sorted_e = pair_e[order]
    loads = jnp.bincount(pair_e, length=e).astype(jnp.int32)
    tiles = (loads + tile - 1) // tile
    tile_end = jnp.cumsum(tiles)
    first_row = (tile_end - tiles) * tile
    first_pair = jnp.cumsum(loads) - loads
    dest = (first_row[sorted_e] + jnp.arange(t * k, dtype=jnp.int32)
            - first_pair[sorted_e])
    pos = jnp.zeros(t * k, jnp.int32).at[order].set(dest).reshape(t, k)
    src = jnp.full(rows, t, jnp.int32).at[dest].set(order // k)
    xbuf = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[src]
    te = jnp.minimum(jnp.searchsorted(tile_end, jnp.arange(rows // tile),
                                      side="right"), e - 1).astype(jnp.int32)
    return (xbuf, te, tile_end[-1:].astype(jnp.int32), pos, gate,
            stats + jnp.stack([loads, tiles]))


@jax.jit
def _moe_combine(ybuf, pos, gate):
    """Each token's gate-weighted sum of its k expert rows, in float32."""
    return (ybuf[pos].astype(jnp.float32)
            * gate[..., None].astype(jnp.float32)).sum(1).astype(ybuf.dtype)


class SparseMoE:
    """Block-pruned MoE on the grouped BSR lane.

    Each expert's ``wi``/``wg``/``wo`` is magnitude-pruned to (nearly) the
    same kept-block count, so the E experts of each projection stack via
    :func:`repro.sparse_api.stack_bsr` into one batched tensor.  The
    trainable payload is the stacked block array ``(E, NB_pad, TK, TF)``
    per projection, and the grouped VJP pins the pad slots at exact zero,
    so pruned experts *train*.  An optional shared expert
    (:class:`SparseFFN`, params ``p["shared"]``) runs on every token.

    Two paths:

    * :meth:`apply` (training, differentiable): the GShard capacity router
      of :func:`moe_apply`, E experts as one grouped ``spmm`` per
      projection.
    * ``__call__(..., use_plan=True)`` (serving): dropless top-k routing
      (:func:`_moe_dispatch`, honouring ``cfg.norm_topk_prob`` and
      ``cfg.routed_scaling_factor``), each projection one dispatch of a
      cached :class:`~repro.sparse_api.RaggedPlan` over the resident
      stacked payload, a gate-weighted combine, and the shared expert
      through ``plan``-family executables.  Spans ``sextans.moe.route``,
      ``.experts``, ``.combine``, ``.shared``.  :attr:`expert_stats` is a
      device-side int32 ``(2, E)`` accumulator of per-expert routed rows
      (row 0) and row tiles computed (row 1) over every serving call
      since :meth:`reset_stats`; reading it is the caller's sync.
    """

    def __init__(self, wi, wg, wo, shared: Optional[SparseFFN] = None):
        # stacked SparseTensor skeletons, E members each, shapes:
        #   wi/wg: (d_ff, d_model)   wo: (d_model, d_ff)
        self.wi, self.wg, self.wo = wi, wg, wo
        self.shared = shared
        self._plans: Dict[Any, Any] = {}
        self.reset_stats()

    @property
    def num_experts(self) -> int:
        return self.wi.batch

    @property
    def density(self) -> float:
        return self.wi.density

    def reset_stats(self) -> None:
        self.expert_stats = jnp.zeros((2, self.wi.batch), jnp.int32)

    @classmethod
    def create(cls, init: Initializer, cfg: ModelConfig,
               block: Tuple[int, int] = (128, 128),
               density: float = 0.25) -> Tuple["SparseMoE", Dict[str, Any]]:
        """Init dense expert weights, block-prune each expert, stack per
        projection.  ``block`` is the (input-dim, output-dim) tile of each
        projection; a shared expert (``cfg.shared_expert``) is pruned to the
        same density.  Returns (layer, params) with ``params["wi"/"wg"/
        "wo"]`` the stacked trainable block values."""
        import numpy as np

        from repro.sparse_api import Format, from_dense, stack_bsr

        d, ff, e = cfg.d_model, cfg.d_ff, cfg.num_experts
        bi, bo = block

        def stack_proj(w3):
            w3 = np.asarray(w3, np.float32)
            members = []
            for ei in range(e):
                w = _prune_blocks(w3[ei], block, density)
                members.append(from_dense(w.T, format=Format.BSR,
                                          block=(bo, bi)))
            return stack_bsr(members)

        wi = stack_proj(init.dense(e, d, ff))
        wg = stack_proj(init.dense(e, d, ff))
        wo = stack_proj(init.dense(e, ff, d))
        params = {
            "router": init.dense(d, e, scale=0.02),
            "wi": wi.values, "wg": wg.values, "wo": wo.values,
        }
        shared = None
        if cfg.shared_expert:
            shared, params["shared"] = SparseFFN.create(
                init, d, cfg.shared_expert_ff or ff, block=block,
                density=density)
        return cls(wi, wg, wo, shared), params

    @_scoped("sparse_moe")
    def apply(self, p: Dict[str, Any], cfg: ModelConfig, x: jax.Array, *,
              backend: str = "auto", **opts) -> jax.Array:
        from repro.sparse_api import spmm

        dtype = compute_dtype(cfg)
        b, s, d = x.shape
        e = cfg.num_experts
        t = b * s
        tg = min(cfg.moe_group_size, t)
        g = t // tg
        assert g * tg == t, f"tokens {t} not divisible by group {tg}"

        xt = x.reshape(g, tg, d)
        xt = constrain(xt, "data", None, None)
        combine, dispatch, cap = _moe_route(p["router"], cfg, xt, dtype)

        # capacity buffers (g, e, cap, d) -> grouped-spmm right operand
        # (E, d, g*cap): experts become the spmm group axis, so each
        # projection below is ONE batched dispatch over all E experts.
        ein = jnp.einsum("gtd,gtec->gecd", xt.astype(dtype), dispatch)
        xb = ein.transpose(1, 3, 0, 2).reshape(e, d, g * cap)
        act = _act(cfg.act)
        hg = spmm(self.wg.with_values(p["wg"]), xb, backend=backend, **opts)
        hi = spmm(self.wi.with_values(p["wi"]), xb, backend=backend, **opts)
        h = act(hg.astype(dtype)) * hi.astype(dtype)          # (E, ff, T)
        eo = spmm(self.wo.with_values(p["wo"]), h, backend=backend, **opts)
        eout = (eo.reshape(e, d, g, cap)
                  .transpose(2, 0, 3, 1).astype(dtype))       # (g, e, cap, d)

        y = jnp.einsum("gecd,gtec->gtd", eout, combine)
        y = y.reshape(b, s, d)
        if self.shared is not None:
            y = y + self.shared(p["shared"], x, act=cfg.act,
                                backend=backend, **opts).astype(y.dtype)
        return y.astype(dtype)

    def plan_for(self, name: str, rows: int, *, backend: str = "auto",
                 **opts):
        """The cached :class:`~repro.sparse_api.RaggedPlan` of projection
        ``name`` (``"wg"``, ``"wi"``, ``"wo"``) for ``rows`` sorted rows."""
        from repro.sparse_api import plan_ragged

        key = (name, int(rows), backend, tuple(sorted(opts.items())))
        pl = self._plans.get(key)
        if pl is None:
            pl = plan_ragged(getattr(self, name), int(rows), backend=backend,
                             **opts)
            self._plans[key] = pl
        return pl

    def serve(self, p: Dict[str, Any], cfg: ModelConfig, x: jax.Array, *,
              backend: str = "auto", **opts) -> jax.Array:
        """The serving path (see the class docstring); ``x`` is
        ``(..., d_model)``, every leading position a token."""
        from repro.sparse_api.backends import RAGGED_TILE as tile

        lead, d = x.shape[:-1], x.shape[-1]
        xt = x.reshape(-1, d)
        t, e, k = xt.shape[0], self.num_experts, cfg.experts_per_token
        rows = -(-(t * k + e * (tile - 1)) // tile) * tile
        with span("sextans.moe.route"):
            xbuf, te, used, pos, gate, self.expert_stats = _moe_dispatch(
                p["router"], xt, self.expert_stats, k=k,
                norm=cfg.norm_topk_prob,
                scale=float(cfg.routed_scaling_factor), tile=tile, rows=rows)
        def run(name, a):
            pl = self.plan_for(name, rows, backend=backend, **opts)
            return pl.run(a, te, used, values=p[name])

        with span("sextans.moe.experts"):
            h = _glu(run("wg", xbuf), run("wi", xbuf), act=cfg.act)
            ybuf = run("wo", h)
        with span("sextans.moe.combine"):
            y = _moe_combine(ybuf, pos, gate)
        if self.shared is not None:
            with span("sextans.moe.shared"):
                y = y + self.shared(p["shared"], xt, act=cfg.act,
                                    backend=backend, use_plan=True, **opts)
        return y.reshape(*lead, d)

    def __call__(self, p, cfg, x, *, use_plan: bool = False,
                 **kw) -> jax.Array:
        if use_plan:
            return self.serve(p, cfg, x, **kw)
        return self.apply(p, cfg, x, **kw)
