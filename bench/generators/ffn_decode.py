"""Closed-loop decode steps through a stack of block-sparse FFN layers.

Traffic keys: ``tokens`` (rows of the activation a decode step carries),
``inputs`` (distinct step inputs, drawn on the device from the seed and
used in turn), ``limits``.

Each layer runs as the model code serves pruned weights
(``repro.models.layers``): ``gate`` and ``up`` as one
``SparseLinearGroup`` dispatch, ``down`` as a ``SparseLinear`` dispatch,
both with ``use_plan=True``; the activation and the post-norm residual
are two small jitted functions of this file's configuration
(``bench.ffn.layer_glue``).  The check runs the kept steps' inputs through
the plain reference (dense weights rebuilt from the seed, float32 at
``HIGHEST``) and compares the final activations row by row.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import ffn
from bench import work as W
from bench.loop import ClosedLoop, draw_normal, span


def build(cfg: Dict, traffic: Dict, rt: Dict):
    return FfnDecode(cfg, traffic, rt)


def _skeleton(values, brow, bcol, d_in, d_out, b):
    """The ``SparseLinear`` skeleton ``W^T`` (d_out, d_in) of a weight
    whose kept ``(b, b)`` tiles sit at ``(brow, bcol)``."""
    from repro.sparse_api import BsrWeight, Format, SparseTensor

    indptr = np.zeros(d_out // b + 1, np.int32)
    np.cumsum(np.bincount(bcol, minlength=d_out // b), out=indptr[1:])
    w = BsrWeight(blocks=values, brow=brow, indptr=indptr, k=d_in, f=d_out,
                  tk=b, tf=b)
    return SparseTensor(data=w, format=Format.BSR, shape=(d_out, d_in),
                        nse=int(brow.shape[0]) * b * b)


class FfnDecode(ClosedLoop):

    sample_count = 3
    sample_of = 32

    def __init__(self, cfg: Dict, traffic: Dict, rt: Dict):
        super().__init__(rt)
        from repro.models.layers import SparseLinear, SparseLinearGroup

        self.cfg, self.traffic = cfg, traffic
        seed = rt["seed"]
        self.control = rt.get("control", False)
        b = cfg["sparsity"]["block"]
        dm = ffn.dims(cfg)
        with span("bench.generate"):
            vals = ffn.make_values(cfg, seed)
            self.xs = draw_normal(seed, 3, traffic["inputs"],
                                  (traffic["tokens"], cfg["hidden_size"]))
        self.layers = []
        with span("bench.pack"):
            for i, v in enumerate(vals):
                lin = {n: SparseLinear(_skeleton(
                    v[n], *ffn.pattern(cfg, seed, i, n), *dm[n], b))
                    for n in ffn.MATS}
                self.layers.append((
                    SparseLinearGroup([lin["gate"], lin["up"]]),
                    [{"w": v["gate"]}, {"w": v["up"]}],
                    lin["down"], {"w": v["down"]}))
        self.act, self.resid = ffn.layer_glue(cfg["rms_norm_eps"])
        tokens = traffic["tokens"]
        kept = ffn.kept_blocks(cfg)
        calls = [W.bsr_spmm(dm[n][1], dm[n][0], kept[n], b, b, tokens)
                 for n in ffn.MATS]
        n_layers = ffn.layers(cfg)
        self.step_work = sum(calls, W.ZERO) * n_layers
        if rt.get("peak"):
            self.step_roofline_s = n_layers * sum(
                c.roofline_s(rt["peak"]) for c in calls)

    def call(self, i: int):
        x = self.xs[i % len(self.xs)]
        if self.control:
            return ffn.reference_forward(self.cfg, self.rt["seed"],
                                         [np.asarray(x)], "high")[0]
        with span("bench.dispatch"):
            for group, gu_p, down, down_p in self.layers:
                gu = group(gu_p, x, use_plan=True)
                y = down(down_p, self.act(gu), use_plan=True)
                x = self.resid(x, y)
        with span("bench.sync"):
            x.block_until_ready()
        return x

    def keep(self, i: int, out):
        return (out, self.xs[i % len(self.xs)])

    def release(self):
        self.layers = self.xs = None

    def reference_check(self, kept) -> List[Dict]:
        idx = sorted(kept)
        refs = ffn.reference_forward(self.cfg, self.rt["seed"],
                                     [kept[i][1] for i in idx])
        errs = [ffn.row_rel_error(kept[i][0], r) for i, r in zip(idx, refs)]
        return [{"name": "row_rel_err", "value": max(errs) if errs
                 else float("inf"),
                 "limit": self.traffic["limits"]["row_rel_err"]}]
