"""Plain reference of DeepSeek-V2-Lite's FFN and MoE stack.

DeepSeek-V2-Lite (https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite,
``config.json``): 27 pre-norm blocks ``x <- x + mlp(rmsnorm(x))``.  Layer 0
(``first_k_dense_replace`` 1) is a SiLU-gated FFN; every later layer is a
mixture of experts: a router ``x @ W_r`` over ``n_routed_experts``,
softmax over all of them (``scoring_func`` softmax), the greedy top
``num_experts_per_tok`` (``topk_method`` greedy), gates renormalised over
the top k only if ``norm_topk_prob``, then scaled by
``routed_scaling_factor``; each chosen expert is a SiLU-gated FFN, and the
``n_shared_experts`` run as one SiLU-gated FFN on every token.

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, with dense weights, a loop
over the experts with masks, no kernels and no plans.  Departures from the
published model, all of them cuts of what is not the FFN or MoE block:

- the attention (MLA) of each block is not run, so the block is
  ``x + mlp(rmsnorm(x))`` alone;
- the RMSNorm weights are one;
- the embeddings, the final norm and the head are not run.

Parameters (dense, ``(d_in, d_out)``): ``{"dense": ffn, "moe": [layer,
...]}`` with ``ffn = {"gate", "up", "down"}`` and ``layer = {"router":
(d, E), "experts": {"gate": (E, d, f), "up": (E, d, f), "down": (E, f,
d)}, "shared": ffn}``.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

__all__ = ["rmsnorm", "ffn", "moe", "forward"]


def rmsnorm(x, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def ffn(p: Dict[str, Any], x):
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def moe(p: Dict[str, Any], x, *, k: int, norm_topk_prob: bool,
        routed_scaling_factor: float):
    """One MoE block's output for the ``(T, d)`` tokens ``x``."""
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    if norm_topk_prob:
        gate = gate / gate.sum(-1, keepdims=True)
    gate = gate * routed_scaling_factor
    ex = p["experts"]
    y = ffn(p["shared"], x)
    for e in range(p["router"].shape[1]):
        w = jnp.where(idx == e, gate, 0.0).sum(-1)
        y = y + w[:, None] * ffn({n: ex[n][e] for n in ex}, x)
    return y


def forward(params: Dict[str, Any], x, *, k: int, norm_topk_prob: bool,
            routed_scaling_factor: float, eps: float):
    """The stack on the ``(T, d)`` tokens ``x``: layer 0's FFN, then each
    MoE layer, every block pre-norm with a residual."""
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(x, jnp.float32)
        x = x + ffn(params["dense"], rmsnorm(x, eps))
        for p in params["moe"]:
            x = x + moe(p, rmsnorm(x, eps), k=k,
                        norm_topk_prob=norm_topk_prob,
                        routed_scaling_factor=routed_scaling_factor)
        return x
