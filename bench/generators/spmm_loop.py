"""Closed-loop SpMM on a resident graph matrix: ``Y = alpha * A @ H + beta * C``.

Traffic keys: ``matrix`` (a kind of ``bench.graph.MATRICES``), ``n`` (dense
width), ``alpha``, ``beta``, ``inputs`` (distinct dense operands, drawn on
the device from the seed and used in turn), ``limits``.

The window drives ``SextansEngine().spmm`` on the packed matrix: the
engine's plan cache, ``SpmmPlan.run``, the kernel ``auto`` resolves to,
and the epilogue.  The check compares kept outputs with the float64
reference, elementwise against the size of each element's terms.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import graph, reference
from bench import work as W
from bench.loop import ClosedLoop, draw_normal, span


def build(cfg: Dict, traffic: Dict, rt: Dict):
    return SpmmLoop(cfg, traffic, rt)


class SpmmLoop(ClosedLoop):

    def __init__(self, cfg: Dict, traffic: Dict, rt: Dict):
        super().__init__(rt)
        from repro.core.engine import SextansEngine
        from repro.core.sparse import SparseMatrix

        self.traffic = traffic
        seed = rt["seed"]
        with span("bench.generate"):
            self.coo = graph.matrix(cfg, traffic["matrix"], seed)
        shape = self.coo[0]
        n = traffic["n"]
        self.alpha, self.beta = traffic["alpha"], traffic["beta"]
        self.control = rt.get("control", False)
        self.eng = SextansEngine()
        with span("bench.pack"):
            self.a = self.eng.pack(SparseMatrix(*self.coo))
        with span("bench.generate"):
            self.h = draw_normal(seed, 1, traffic["inputs"], (shape[1], n))
            self.c = (draw_normal(seed, 2, traffic["inputs"], (shape[0], n))
                      if self.beta != 0.0 else None)
        nnz = self.coo[1].shape[0]
        self.step_work = W.csr_spmm(*shape, nnz, n, beta=self.beta)
        if rt.get("peak"):
            self.step_roofline_s = self.step_work.roofline_s(rt["peak"])

    def _operands(self, i: int):
        j = i % len(self.h)
        return self.h[j], (None if self.c is None else self.c[j])

    def call(self, i: int):
        h, c = self._operands(i)
        if self.control:
            return reference.spmm_high(
                self.coo, np.asarray(h), None if c is None else np.asarray(c),
                self.alpha, self.beta)
        with span("bench.dispatch"):
            y = self.eng.spmm(self.a, h, c, self.alpha, self.beta)
        with span("bench.sync"):
            y.block_until_ready()
        return y

    def keep(self, i: int, out):
        h, c = self._operands(i)
        return (out, h, c)

    def counters(self) -> Dict:
        vals = self.a.data.vals
        return {"nnz": int(self.a.nnz),
                "slab_slots": int(np.prod(vals.shape)),
                "dispatches": self.eng.stats.dispatches}

    def release(self):
        self.eng = self.a = self.h = self.c = None

    def reference_check(self, kept) -> List[Dict]:
        errs = []
        for i in sorted(kept):
            y, h, c = kept[i]
            ref = reference.spmm(self.coo, h, c, self.alpha, self.beta)
            scale = reference.term_scale(self.coo, h, c, self.alpha,
                                         self.beta)
            errs.append(reference.scaled_error(y, ref, scale))
        return [{"name": "scaled_err", "value": max(errs) if errs
                 else float("inf"),
                 "limit": self.traffic["limits"]["scaled_err"]}]
