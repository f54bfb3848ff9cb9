"""Seeded block-sparse FFN weights and the plain FFN reference.

One layer of the served part is Olmo's SiLU-gated FFN with its
post-norm residual:

    x <- x + rmsnorm(down(silu(gate(x)) * up(x)))

``gate``/``up`` map ``hidden -> intermediate`` and ``down`` maps back; each
weight keeps ``keep`` of its ``(block x block)`` tiles, drawn uniformly at
random from the seed.  Tile positions are drawn on the host (they are
small); the kept values are drawn on the device in one jitted call,
``normal / sqrt(fan-in kept)``, in float32.

``reference_forward`` rebuilds each weight densely from the same seed and
runs the layers in ``jax.numpy`` at a stated matmul precision; it imports
nothing of the system under test.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MATS = ("gate", "up", "down")


def dims(cfg: Dict) -> Dict[str, Tuple[int, int]]:
    """``(d_in, d_out)`` of each matrix."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    return {"gate": (h, f), "up": (h, f), "down": (f, h)}


def layers(cfg: Dict) -> int:
    return cfg["num_hidden_layers"]


def kept_blocks(cfg: Dict) -> Dict[str, int]:
    """Tiles each matrix keeps: ``round((1 - sparsity) * tiles)``."""
    b = cfg["sparsity"]["block"]
    out = {}
    for name, (d_in, d_out) in dims(cfg).items():
        tiles = (d_in // b) * (d_out // b)
        out[name] = int(round((1.0 - cfg["sparsity"]["block_sparsity"])
                              * tiles))
    return out


def pattern(cfg: Dict, seed: int, layer: int, name: str):
    """``(brow, bcol)`` of the kept tiles, sorted by output tile then input
    tile (the order a column-pointer walk visits them)."""
    b = cfg["sparsity"]["block"]
    d_in, d_out = dims(cfg)[name]
    nk, nf = d_in // b, d_out // b
    rng = np.random.default_rng([seed, layer, MATS.index(name)])
    idx = rng.choice(nk * nf, size=kept_blocks(cfg)[name], replace=False)
    brow, bcol = idx // nf, idx % nf
    order = np.lexsort((brow, bcol))
    return brow[order].astype(np.int32), bcol[order].astype(np.int32)


def _value_key(seed: int):
    return jax.random.key(np.random.default_rng(seed).integers(2 ** 31))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, n_layers: int, mats: Tuple[Tuple[int, int, float], ...]):
    """One normal draw per matrix kind, ``(layers, keep, b, b) * scale``,
    split into a layer-major list of per-layer arrays."""
    keys = jax.random.split(key, len(mats))
    bigs = [jax.random.normal(k, (n_layers, keep, b, b), jnp.float32) * sc
            for k, (keep, b, sc) in zip(keys, mats)]
    return [big[i] for i in range(n_layers) for big in bigs]


def make_values(cfg: Dict, seed: int) -> List[Dict[str, jax.Array]]:
    """Per layer ``{name: (keep, block, block) float32}`` on the device, all
    drawn in one jitted call."""
    b = cfg["sparsity"]["block"]
    kept = kept_blocks(cfg)
    n_layers = layers(cfg)
    mats = tuple((kept[n], b, float(1.0 / np.sqrt(kept[n] * b * b
                                                  / dims(cfg)[n][1])))
                 for n in MATS)
    flat = _draw(_value_key(seed), n_layers, mats)
    return [{n: flat[i * len(MATS) + j] for j, n in enumerate(MATS)}
            for i in range(n_layers)]


def rmsnorm(x, eps):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)


def layer_glue(eps: float):
    """The non-sparse parts of one layer, as two jitted functions:
    ``act(gu) = silu(gu[0]) * gu[1]`` and ``resid(x, y) = x + rmsnorm(y)``."""
    act = jax.jit(lambda gu: jax.nn.silu(gu[0]) * gu[1])
    resid = jax.jit(lambda x, y: x + rmsnorm(y, eps))
    return act, resid


def _dense(values, brow, bcol, d_in, d_out, b):
    w = jnp.zeros((d_in // b, d_out // b, b, b), jnp.float32)
    w = w.at[brow, bcol].set(values)
    return w.transpose(0, 2, 1, 3).reshape(d_in, d_out)


def _split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _dot(a, w, precision):
    """``a @ w`` at ``precision``; ``"high"`` is formed explicitly as the
    MXU forms it (three bfloat16 passes, float32 sums), so that it means
    the same on any platform."""
    hp = jax.lax.Precision.HIGHEST
    if precision != "high":
        return jnp.dot(a, w, precision=hp)
    (a_hi, a_lo), (w_hi, w_lo) = _split(a), _split(w)
    return (jnp.dot(a_hi, w_hi, precision=hp) + jnp.dot(a_hi, w_lo, precision=hp)
            + jnp.dot(a_lo, w_hi, precision=hp))


def reference_forward(cfg: Dict, seed: int, xs,
                      precision: str = "highest") -> List[np.ndarray]:
    """The FFN stack on each input of ``xs`` (host arrays), with weights
    rebuilt from ``seed``, layer by layer, in float32 with matmuls at
    ``precision`` (``"highest"`` or ``"high"``)."""
    b = cfg["sparsity"]["block"]
    eps = cfg["rms_norm_eps"]
    dm = dims(cfg)
    vals = make_values(cfg, seed)
    dense = jax.jit(_dense, static_argnums=(3, 4, 5))

    @jax.jit
    def layer(x, wg, wu, wd):
        mm = lambda a, w: _dot(a, w, precision)  # noqa: E731
        h = jax.nn.silu(mm(x, wg)) * mm(x, wu)
        return x + rmsnorm(mm(h, wd), eps)

    x = jnp.asarray(np.stack(xs), jnp.float32)       # (S, tokens, hidden)
    x = x.reshape(-1, x.shape[-1])
    for i in range(layers(cfg)):
        ws = [dense(vals[i][n], *pattern(cfg, seed, i, n), *dm[n], b)
              for n in MATS]
        x = layer(x, *ws)
        vals[i] = None
        del ws
    out = np.asarray(x, np.float64).reshape(len(xs), -1, x.shape[-1])
    return list(out)


def row_rel_error(got, ref) -> float:
    """Largest ``||got - ref|| / ||ref||`` over rows (tokens)."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        return float("inf")
    num = np.linalg.norm(got - ref, axis=-1)
    den = np.maximum(np.linalg.norm(ref, axis=-1), 1e-30)
    return float((num / den).max())
