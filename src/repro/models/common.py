"""Model substrate: config schema, parameter initialization, dtype policy.

The zoo is functional: a config describes an architecture; ``init_params``
builds a pytree of arrays; pure ``apply`` functions in layers/ssm/model
consume (params, inputs). Layer parameters are *stacked* along a leading
layer axis so the whole stack runs under ``lax.scan`` (one compiled block
body regardless of depth — essential for the 80-94 layer dry-run configs).

Blocks with heterogeneous mixers (xLSTM's sLSTM/mLSTM alternation) share a
union parameter structure selected per-layer by a static type vector, so
the scan body stays uniform.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ModelConfig", "DTYPES", "param_dtype", "compute_dtype", "dense_init", "Initializer"]

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One schema for all ten assigned architectures (+ paper workloads)."""

    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int

    head_dim: Optional[int] = None          # explicit (qwen3 uses 128 != D/H)
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    act: str = "silu"
    tie_embeddings: bool = False

    # per-layer mixer types: "attn" | "swa" | "mamba" | "mlstm" | "slstm" | "hymba"
    # None -> all "attn".
    layer_types: Optional[Tuple[str, ...]] = None
    sliding_window: int = 1024

    # MoE
    num_experts: int = 0
    experts_per_token: int = 0
    moe_capacity_factor: float = 1.25
    shared_expert: bool = False
    shared_expert_ff: int = 0
    # serving router (SparseMoE use_plan=True): renormalise the top-k gates,
    # then scale them (DeepSeek's norm_topk_prob, routed_scaling_factor)
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0

    # SSM / recurrent
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_dt_rank: int = 0           # 0 -> ceil(d_model/16)

    # encoder-decoder
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0

    # modality frontend (STUB per assignment: precomputed embeddings)
    frontend: str = "none"         # none | vision_stub | audio_stub
    frontend_dim: int = 0          # dim of precomputed patch/frame embeddings
    frontend_len: int = 0          # number of patch/frame positions

    # sparsity feature (the paper's technique as a model layer)
    sparse_ffn_density: float = 1.0
    sparse_block: int = 128

    # numerics
    param_dtype: str = "float32"
    compute_dtype: str = "float32"

    # attention memory management
    attn_chunk_q: int = 512
    attn_chunk_k: int = 1024

    # perf levers (hillclimb knobs; defaults = paper-faithful baseline)
    attn_skip_masked_blocks: bool = False   # causal: iterate (qi,ki<=qi) pairs
    remat_policy: str = "full"              # full | dots
    moe_group_size: int = 512
    mlstm_chunk: int = 64                   # chunkwise-parallel block length
    sp_attention: bool = False              # shard_map sequence-parallel attn
    attn_probs_bf16: bool = False           # store probabilities in bf16

    def __post_init__(self):
        if self.layer_types is not None and len(self.layer_types) != self.num_layers:
            raise ValueError("layer_types length must equal num_layers")

    # -- derived -------------------------------------------------------------

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.hd

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.hd

    @property
    def types(self) -> Tuple[str, ...]:
        return self.layer_types or ("attn",) * self.num_layers

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return self.ssm_dt_rank or -(-self.d_model // 16)

    @property
    def vocab_padded(self) -> int:
        """Vocab padded to a multiple of 256 so the embedding shards over a
        16-wide model axis on any assigned vocab (32001, 256206, ...)."""
        return -(-self.vocab_size // 256) * 256

    @property
    def is_recurrent(self) -> bool:
        return any(t in ("mamba", "mlstm", "slstm", "hymba") for t in self.types)

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic sequence mixing on every layer (SSM/hybrid/sliding)."""
        return all(t != "attn" for t in self.types)

    def param_count(self) -> int:
        """Analytic parameter count (total, incl. MoE experts)."""
        d, ff, v = self.d_model, self.d_ff, self.vocab_padded
        per_layer = 0
        for t in self.types:
            if t in ("attn", "swa", "hymba"):
                per_layer += d * (self.q_dim + 2 * self.kv_dim) + self.q_dim * d
            if t == "hymba":
                di = self.d_inner
                per_layer += d * 2 * di + di * d + di * (self.dt_rank + 2 * self.ssm_state) + di * self.ssm_conv
            if t == "mamba":
                di = self.d_inner
                per_layer += d * 2 * di + di * d + di * (self.dt_rank + 2 * self.ssm_state) + di * self.ssm_conv
            if t == "mlstm":
                di = self.d_inner
                per_layer += d * 2 * di + di * d + 3 * di * di // 1  # qkv in inner dim
            if t == "slstm":
                per_layer += 4 * d * d + d * d
            if t in ("attn", "swa", "hymba") or t in ("mamba",):
                if self.num_experts:
                    per_layer += self.num_experts * 3 * d * ff + d * self.num_experts
                    if self.shared_expert:
                        per_layer += 3 * d * (self.shared_expert_ff or ff)
                elif self.d_ff:
                    per_layer += 3 * d * ff
            per_layer += 2 * d  # norms
        total = per_layer + v * d * (1 if self.tie_embeddings else 2) + d
        if self.is_encoder_decoder:
            enc = self.num_encoder_layers * (d * (self.q_dim + 2 * self.kv_dim) + self.q_dim * d + 3 * d * ff + 2 * d)
            xattn = self.num_layers * (d * (self.q_dim + 2 * self.kv_dim) + self.q_dim * d + d)
            total += enc + xattn
        return int(total)

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed experts)."""
        if not self.num_experts:
            return self.param_count()
        d, ff = self.d_model, self.d_ff
        dense_experts = self.param_count() - len(self.types) * self.num_experts * 3 * d * ff
        active = len(self.types) * self.experts_per_token * 3 * d * ff
        return int(dense_experts + active)


def param_dtype(cfg: ModelConfig):
    return DTYPES[cfg.param_dtype]


def compute_dtype(cfg: ModelConfig):
    return DTYPES[cfg.compute_dtype]


class Initializer:
    """Counter-based deterministic init — avoids threading a PRNG through
    the whole tree construction (cheap + reproducible)."""

    def __init__(self, seed: int, dtype):
        self.key = jax.random.PRNGKey(seed)
        self.count = 0
        self.dtype = dtype

    def _next(self):
        self.count += 1
        return jax.random.fold_in(self.key, self.count)

    def dense(self, *shape: int, scale: Optional[float] = None) -> jax.Array:
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(self._next(), shape, jnp.float32) * std).astype(self.dtype)

    def zeros(self, *shape: int) -> jax.Array:
        return jnp.zeros(shape, self.dtype)

    def ones(self, *shape: int) -> jax.Array:
        return jnp.ones(shape, self.dtype)

    def embed(self, *shape: int) -> jax.Array:
        return (jax.random.normal(self._next(), shape, jnp.float32) * 0.02).astype(self.dtype)


def dense_init(rng_init: Initializer, din: int, dout: int, bias: bool) -> Dict[str, Any]:
    p = {"w": rng_init.dense(din, dout)}
    if bias:
        p["b"] = rng_init.zeros(dout)
    return p
