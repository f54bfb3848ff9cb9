"""Closed-loop prefill chunks through DeepSeek-V2-Lite's FFN and MoE stack.

Traffic keys: ``tokens`` (rows of a chunk), ``inputs`` (distinct chunks,
drawn on the device from the seed and used in turn), ``documents`` (a
chunk packs this many equal documents), ``topic_weight`` (each token is
``sqrt(w) * t_d * sqrt(hidden) + sqrt(1 - w) * z``, ``t_d`` its
document's seeded unit direction, ``z`` standard normal), ``near_tie``
(the relative gap between a token's k-th and (k+1)-th router scores,
in the reference, under which the check leaves the token out) and
``limits``.

Each block runs as the model code serves it (``repro.models.layers``):
layer 0 a ``SparseFFN`` (gate and up one ``SparseLinearGroup`` dispatch,
down one ``SparseLinear``), every later layer a ``SparseMoE`` with
``use_plan=True`` (dropless routing, the routed experts on the ragged
grouped BSR lane, the shared experts as a ``SparseFFN``); the pre-norm
residual is two small jitted functions here.  The check runs the kept
chunks through the plain reference (``bench.moe``: dense weights rebuilt
from the seed, float32 at ``HIGHEST``) and compares the final hidden
states token by token, leaving out the tokens the reference finds at a
near tie.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import ffn, moe
from bench.loop import ClosedLoop, span

# ``SparseFFN`` and the serving path of ``SparseMoE`` came together: a
# program without them fails here, at once, rather than running the
# capacity router as this model.
from repro.models.layers import SparseFFN, SparseLinear, SparseMoE


def build(cfg: Dict, traffic: Dict, rt: Dict):
    return MoePrefill(cfg, traffic, rt)


def _tensor(vals, brow, bcol, d_in, d_out, b, cells):
    """The skeleton ``W^T`` (d_out, d_in) of one block-sparse weight or,
    with a leading member axis on ``vals``, ``brow`` and ``bcol``, of a
    stack of them; its kept tiles sit at ``(brow, bcol)`` and ``vals`` are
    its blocks, kept as they are."""
    from repro.sparse_api import BsrWeight, Format, SparseTensor

    nk, nf = -(-d_in // b), -(-d_out // b)
    indptr = np.zeros((*bcol.shape[:-1], nf + 1), np.int32)
    for i in np.ndindex(bcol.shape[:-1]):
        np.cumsum(np.bincount(bcol[i], minlength=nf), out=indptr[i][1:])
    w = BsrWeight(blocks=vals, brow=jnp.asarray(brow),
                  indptr=jnp.asarray(indptr), k=nk * b, f=nf * b, tk=b, tf=b)
    return SparseTensor(data=w, format=Format.BSR, shape=(d_out, d_in),
                        nse=cells)


def _ffn(cfg, kind, v):
    """A ``SparseFFN`` and its params from one layer's values."""
    b = cfg["sparsity"]["block"]
    lin, params = {}, {}
    for n in moe.MATS:
        vals, brow, bcol = v[kind][n]
        vals, brow, bcol = vals[0], brow[0], bcol[0]
        lin[n] = SparseLinear(_tensor(
            vals, brow, bcol, *moe.dims(cfg, kind, n), b,
            moe.real_cells(cfg, kind, n, brow, bcol)))
        params[n] = {"w": vals}
    return SparseFFN(lin["gate"], lin["up"], lin["down"]), params


def _chunks(seed: int, traffic: Dict, hidden: int) -> List[jax.Array]:
    """``inputs`` chunks of ``documents`` equal documents each, a token
    ``sqrt(w) * t_d * sqrt(hidden) + sqrt(1 - w) * z``."""
    n, t, docs = traffic["inputs"], traffic["tokens"], traffic["documents"]
    w = float(traffic["topic_weight"])
    key = jax.random.key(np.random.default_rng([seed, 3]).integers(2 ** 31))

    @jax.jit
    def draw(k):
        kt, kz = jax.random.split(k)
        topic = jax.random.normal(kt, (n, docs, hidden), jnp.float32)
        topic = topic / jnp.linalg.norm(topic, axis=-1, keepdims=True)
        topic = jnp.repeat(topic, t // docs, axis=1)
        z = jax.random.normal(kz, (n, t, hidden), jnp.float32)
        return list(np.sqrt(w) * np.sqrt(hidden) * topic
                    + np.sqrt(1.0 - w) * z)

    return draw(key)


class MoePrefill(ClosedLoop):

    sample_count = 3
    sample_of = 8

    def __init__(self, cfg: Dict, traffic: Dict, rt: Dict):
        super().__init__(rt)
        from repro.models.common import ModelConfig

        if (cfg["scoring_func"], cfg["topk_method"], cfg["n_group"]) != (
                "softmax", "greedy", 1):
            raise ValueError("the served router is softmax, greedy, one "
                             "group")
        self.cfg, self.traffic = cfg, traffic
        seed = rt["seed"]
        self.control = rt.get("control", False)
        h, e = cfg["hidden_size"], cfg["n_routed_experts"]
        b = cfg["sparsity"]["block"]
        self.mcfg = ModelConfig(
            name=cfg["name"], family="moe",
            num_layers=cfg["num_hidden_layers"], d_model=h,
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
            act=cfg["hidden_act"], norm_eps=cfg["rms_norm_eps"],
            num_experts=e, experts_per_token=cfg["num_experts_per_tok"],
            shared_expert=cfg["n_shared_experts"] > 0,
            shared_expert_ff=moe.widths(cfg)["shared"],
            norm_topk_prob=bool(cfg["norm_topk_prob"]),
            routed_scaling_factor=float(cfg["routed_scaling_factor"]))
        with span("bench.generate"):
            self.xs = _chunks(seed, traffic, h)
            values = [moe.layer_values(cfg, seed, i)
                      for i in range(cfg["num_hidden_layers"])]
        self.blocks = []
        with span("bench.pack"):
            for v in values:
                if "router" not in v:
                    self.blocks.append(_ffn(cfg, "dense", v))
                    continue
                stacked, p = {}, {"router": v["router"]}
                for n, slot in zip(moe.MATS, ("wg", "wi", "wo")):
                    vals, brow, bcol = v["expert"][n]
                    stacked[slot] = _tensor(
                        vals, brow, bcol, *moe.dims(cfg, "expert", n), b,
                        moe.real_cells(cfg, "expert", n, brow, bcol))
                    p[slot] = vals
                shared, p["shared"] = _ffn(cfg, "shared", v)
                self.blocks.append((SparseMoE(shared=shared, **stacked), p))
        del values
        eps = cfg["rms_norm_eps"]
        self.norm = jax.jit(lambda x: ffn.rmsnorm(x, eps))
        self.add = jax.jit(lambda x, y: x + y)
        whole, self.routed = moe.step_work(cfg, seed, traffic["tokens"])
        self.step_work = whole
        if rt.get("peak"):
            self.step_roofline_s = whole.roofline_s(rt["peak"])

    def _moes(self):
        return [m for m, _ in self.blocks if isinstance(m, SparseMoE)]

    def call(self, i: int):
        x = self.xs[i % len(self.xs)]
        if self.control:
            return moe.reference_forward(self.cfg, self.rt["seed"],
                                         [np.asarray(x)], "high")[0][0]
        with span("bench.dispatch"):
            for layer, p in self.blocks:
                if isinstance(layer, SparseMoE):
                    y = layer(p, self.mcfg, self.norm(x), use_plan=True)
                else:
                    y = layer(p, self.norm(x), act=self.mcfg.act,
                              use_plan=True)
                x = self.add(x, y)
        with span("bench.sync"):
            x.block_until_ready()
        return x

    def warm(self, repeats: int = 2):
        super().warm(repeats)
        for m in self._moes():
            m.reset_stats()

    def window(self, seconds: float) -> Dict:
        res = super().window(seconds)
        c = res["counters"]
        stats = np.stack([np.asarray(m.expert_stats) for m in self._moes()])
        loads = stats[:, 0].astype(np.float64)           # (layers, experts)
        if not loads.sum():           # the control: no serving call ran
            return res
        c["moe_routed_rows"] = int(loads.sum())
        c["moe_computed_rows"] = int(stats[:, 1].sum()) * 128
        c["moe_load_max_over_mean"] = float(
            (loads.max(1) / loads.mean(1)).mean())
        if self.rt.get("peak"):
            c["moe_expert_roofline_s"] = (
                self.routed.roofline_s(self.rt["peak"]) * c["steps"])
        return res

    def keep(self, i: int, out):
        return (out, self.xs[i % len(self.xs)], i % len(self.xs))

    def release(self):
        self.blocks = self.xs = None

    def reference_check(self, kept) -> List[Dict]:
        by_input = {}
        for out, x, j in kept.values():
            by_input.setdefault(int(j), (out, x))
        idx = sorted(by_input)
        refs, ties = moe.reference_forward(
            self.cfg, self.rt["seed"], [by_input[j][1] for j in idx],
            delta=float(self.traffic["near_tie"]))
        errs, left_out, tokens = [], 0, 0
        for j, ref, tie in zip(idx, refs, ties):
            keep = ~np.asarray(tie)
            errs.append(ffn.row_rel_error(by_input[j][0][keep], ref[keep]))
            left_out += int((~keep).sum())
            tokens += keep.size
        lim = self.traffic["limits"]
        return [{"name": "row_rel_err",
                 "value": max(errs) if errs else float("inf"),
                 "limit": lim["row_rel_err"]},
                {"name": "near_tie_share",
                 "value": left_out / tokens if tokens else float("inf"),
                 "limit": lim["near_tie_share"]}]
