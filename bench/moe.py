"""Seeded block-sparse weights of DeepSeek-V2-Lite's FFN and MoE stack,
its plain reference, and the work of its calls.

The served part is 27 pre-norm blocks ``x <- x + mlp(rmsnorm(x))`` with
unit norm weights: layer 0 a SiLU-gated FFN of ``intermediate_size``,
layers 1.. a mixture of ``n_routed_experts`` SiLU-gated experts of
``moe_intermediate_size`` (softmax over every expert, greedy top
``num_experts_per_tok``, gates renormalised only if ``norm_topk_prob``,
then times ``routed_scaling_factor``) plus ``n_shared_experts`` run as
one FFN of ``n_shared_experts * moe_intermediate_size`` on every token.

Every matrix keeps ``round((1 - sparsity) * tiles)`` of its
``ceil(d_in / b) * ceil(d_out / b)`` tiles, drawn uniformly at random from
the seed on the host; a width that is not a multiple of ``b`` leaves the
pad part of its last tiles an exact zero.  Kept values are drawn on the
device, ``normal / sqrt(kept fan-in)``; router weights ``normal /
sqrt(hidden)``.

``reference_forward`` rebuilds each layer's weights densely from the same
seed, one layer at a time, and runs the stack in ``jax.numpy`` in float32
at a stated matmul precision, the experts as a loop with masks.  It
imports nothing of the system under test.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import ffn
from bench import work as W

MATS = ("gate", "up", "down")
KINDS = ("dense", "expert", "shared")


def widths(cfg: Dict) -> Dict[str, int]:
    """Intermediate width of each kind of FFN."""
    return {"dense": cfg["intermediate_size"],
            "expert": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"]}


def dims(cfg: Dict, kind: str, name: str) -> Tuple[int, int]:
    """``(d_in, d_out)`` of matrix ``name`` of an FFN of ``kind``."""
    h, f = cfg["hidden_size"], widths(cfg)[kind]
    return (f, h) if name == "down" else (h, f)


def moe_layers(cfg: Dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def _tiles(cfg: Dict, kind: str, name: str) -> Tuple[int, int]:
    b = cfg["sparsity"]["block"]
    d_in, d_out = dims(cfg, kind, name)
    return -(-d_in // b), -(-d_out // b)


def kept(cfg: Dict, kind: str, name: str) -> int:
    nk, nf = _tiles(cfg, kind, name)
    return int(round((1.0 - cfg["sparsity"]["block_sparsity"]) * nk * nf))


def pattern(cfg: Dict, seed: int, layer: int, kind: str, name: str,
            members: int = 1):
    """``(brow, bcol)``, each ``(members, kept)``, of the kept tiles of
    ``members`` matrices, sorted by output tile then input tile."""
    nk, nf = _tiles(cfg, kind, name)
    rng = np.random.default_rng([seed, layer, KINDS.index(kind),
                                 MATS.index(name)])
    idx = rng.random((members, nk * nf)).argsort(axis=1)[
        :, :kept(cfg, kind, name)]
    brow, bcol = idx // nf, idx % nf
    order = np.lexsort((brow, bcol), axis=1)
    take = lambda a: np.take_along_axis(a, order, 1).astype(np.int32)  # noqa
    return take(brow), take(bcol)


def real_cells(cfg: Dict, kind: str, name: str, brow, bcol) -> int:
    """Stored cells inside the logical ``(d_in, d_out)`` bounds."""
    b = cfg["sparsity"]["block"]
    d_in, d_out = dims(cfg, kind, name)
    return int((np.clip(d_in - np.asarray(brow) * b, 0, b)
                * np.clip(d_out - np.asarray(bcol) * b, 0, b)).sum())


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _draw(key, brow, bcol, scale, b: int, d_in: int, d_out: int):
    """``(members, kept, b, b)`` normal values times ``scale``, zero in the
    pad part of tiles past ``d_in`` rows or ``d_out`` columns."""
    v = jax.random.normal(key, (*brow.shape, b, b), jnp.float32) * scale
    r = brow[..., None] * b + jnp.arange(b) < d_in
    c = bcol[..., None] * b + jnp.arange(b) < d_out
    return v * (r[..., :, None] & c[..., None, :])


def layer_values(cfg: Dict, seed: int, layer: int) -> Dict:
    """Layer ``layer``'s weights on the device: ``{kind: {name: (values
    (members, kept, b, b), brow, bcol)}}`` for its FFNs (layer 0:
    ``dense``; later: ``expert`` with every expert as a member, and
    ``shared``) and, for a MoE layer, ``router`` ``(hidden, experts)``."""
    b = cfg["sparsity"]["block"]
    key = jax.random.fold_in(ffn._value_key(seed), layer)
    moe = layer >= cfg["first_k_dense_replace"]
    kinds = ("expert", "shared") if moe else ("dense",)
    out: Dict = {}
    for ki, kind in enumerate(kinds):
        members = cfg["n_routed_experts"] if kind == "expert" else 1
        out[kind] = {}
        for mi, name in enumerate(MATS):
            brow, bcol = pattern(cfg, seed, layer, kind, name, members)
            d_in, d_out = dims(cfg, kind, name)
            fan_in = kept(cfg, kind, name) * b * b / d_out
            vals = _draw(jax.random.fold_in(key, 3 * ki + mi),
                         jnp.asarray(brow), jnp.asarray(bcol),
                         float(1.0 / np.sqrt(fan_in)), b, d_in, d_out)
            out[kind][name] = (vals, brow, bcol)
    if moe:
        h, e = cfg["hidden_size"], cfg["n_routed_experts"]
        out["router"] = jax.random.normal(
            jax.random.fold_in(key, 99), (h, e), jnp.float32) / np.sqrt(h)
    return out


# -- work of the calls -------------------------------------------------------


def bsr_work(d_out: int, d_in: int, cells: int, blocks: int, b: int,
             rows: int) -> W.Work:
    """``(rows, d_in) @ W`` for a block-sparse ``W`` with ``cells`` real
    entries in ``blocks`` stored ``(b, b)`` blocks (``bench.work.bsr_spmm``
    with the real entries in place of whole blocks)."""
    nbytes = (cells * 4 + 4 * blocks + 4 * (-(-d_out // b) + 1)
              + 4 * rows * (d_in + d_out))
    return W.Work(2.0 * cells * rows, float(nbytes))


def ragged_work(d_out: int, d_in: int, experts: int, cells: int,
                blocks: int, b: int, pairs: int) -> W.Work:
    """One ragged grouped call: each of ``pairs`` rows through one
    expert's ``cells`` real entries; every expert's blocks read once, the
    pairs' rows in and out once."""
    nbytes = (experts * (cells * 4 + 4 * blocks + 4 * (-(-d_out // b) + 1))
              + 4 * pairs * (d_in + d_out))
    return W.Work(2.0 * cells * pairs, float(nbytes))


def dense_work(d_in: int, d_out: int, rows: int) -> W.Work:
    return W.Work(2.0 * d_in * d_out * rows,
                  float(4 * (d_in * d_out + rows * (d_in + d_out))))


def step_work(cfg: Dict, seed: int, tokens: int) -> Tuple[W.Work, W.Work]:
    """``(whole step, routed experts alone)`` of a ``tokens``-row step:
    the pairs through their experts, the shared experts and layer 0 on
    every row (real entries only), the routers.  Per-matrix cell counts are
    those of the generated matrices: every expert and shared matrix keeps
    the same number of whole tiles; layer 0's pad cells depend on where
    its tiles fell, so it is counted from the seed's pattern."""
    b = cfg["sparsity"]["block"]
    k, e = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    full = lambda kind, n: kept(cfg, kind, n) * b * b  # noqa: E731
    routed = W.ZERO
    for n in MATS:
        routed = routed + ragged_work(*dims(cfg, "expert", n)[::-1], e,
                                      full("expert", n),
                                      kept(cfg, "expert", n), b, tokens * k)
    layer = routed + dense_work(cfg["hidden_size"], e, tokens)
    for n in MATS:
        layer = layer + bsr_work(*dims(cfg, "shared", n)[::-1],
                                 full("shared", n), kept(cfg, "shared", n),
                                 b, tokens)
    n_moe = moe_layers(cfg)
    whole = layer * n_moe
    for layer in range(cfg["first_k_dense_replace"]):
        for n in MATS:
            brow, bcol = pattern(cfg, seed, layer, "dense", n)
            cells = real_cells(cfg, "dense", n, brow, bcol)
            whole = whole + bsr_work(*dims(cfg, "dense", n)[::-1], cells,
                                     kept(cfg, "dense", n), b, tokens)
    return whole, routed * n_moe


# -- the plain reference -----------------------------------------------------


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _dense(values, brow, bcol, b: int, d_in: int, d_out: int):
    """``(members, d_in, d_out)`` dense weights from kept tiles."""
    g, nb = brow.shape
    nk, nf = -(-d_in // b), -(-d_out // b)
    w = jnp.zeros((g, nk, nf, b, b), jnp.float32)
    w = w.at[jnp.arange(g)[:, None], brow, bcol].set(values)
    return w.transpose(0, 1, 3, 2, 4).reshape(g, nk * b, nf * b)[
        :, :d_in, :d_out]


def _mlp(x, wg, wu, wd, precision):
    mm = lambda a, w: ffn._dot(a, w, precision)  # noqa: E731
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _dense_layer(x, ws, precision, eps: float):
    return x + _mlp(ffn.rmsnorm(x, eps), *ws, precision)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9))
def _moe_layer(x, router, experts, shared, precision, eps: float, k: int,
               norm: bool, scale: float, delta: float):
    """One MoE block on ``x`` and each token's near-tie flag: its k-th and
    (k+1)-th softmax scores within a relative ``delta``."""
    h = ffn.rmsnorm(x, eps)
    probs = jax.nn.softmax(ffn._dot(h, router, precision), axis=-1)
    top, idx = jax.lax.top_k(probs, k + 1)
    tie = top[:, k] >= top[:, k - 1] * (1.0 - delta)
    gate, idx = top[:, :k], idx[:, :k]
    if norm:
        gate = gate / gate.sum(-1, keepdims=True)
    gate = gate * scale

    def one(y, e_w):
        e, wg, wu, wd = e_w
        w = jnp.where(idx == e, gate, 0.0).sum(-1)
        return y + w[:, None] * _mlp(h, wg, wu, wd, precision), None

    y0 = _mlp(h, *shared, precision)
    y, _ = jax.lax.scan(one, y0, (jnp.arange(router.shape[1]), *experts))
    return x + y, tie


def reference_forward(cfg: Dict, seed: int, xs, precision: str = "highest",
                      delta: float = 0.0):
    """The stack on each input of ``xs`` (host arrays ``(tokens,
    hidden)``), with weights rebuilt from ``seed``, one layer at a time, in
    float32 with matmuls at ``precision`` (``"highest"`` or ``"high"``).
    Returns the outputs and, per input, each token's flag of a near tie
    (``_moe_layer``, relative ``delta``) at any MoE layer."""
    b, eps = cfg["sparsity"]["block"], float(cfg["rms_norm_eps"])
    x = jnp.asarray(np.stack(xs), jnp.float32)
    s, t = x.shape[:2]
    x = x.reshape(s * t, -1)
    ties = jnp.zeros(s * t, bool)
    for layer in range(cfg["num_hidden_layers"]):
        v = layer_values(cfg, seed, layer)
        dense = lambda kind: tuple(  # noqa: E731
            _dense(jnp.asarray(v[kind][n][0]), jnp.asarray(v[kind][n][1]),
                   jnp.asarray(v[kind][n][2]), b, *dims(cfg, kind, n))
            for n in MATS)
        if "router" not in v:
            x = _dense_layer(x, tuple(w[0] for w in dense("dense")),
                             precision, eps)
        else:
            x, tie = _moe_layer(
                x, v["router"], dense("expert"),
                tuple(w[0] for w in dense("shared")), precision, eps,
                cfg["num_experts_per_tok"], bool(cfg["norm_topk_prob"]),
                float(cfg["routed_scaling_factor"]), float(delta))
            ties = ties | tie
        del v
    out = np.asarray(x, np.float64).reshape(s, t, -1)
    return list(out), list(np.asarray(ties).reshape(s, t))
