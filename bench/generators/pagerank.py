"""PageRank power iteration on a resident transition matrix.

    x <- damping * P @ x + (1 - damping) / n

Traffic keys: ``matrix`` (a kind of ``bench.graph.MATRICES``), ``damping``,
``limits``.  ``x`` stays on the device; each iteration is one
``SextansEngine().spmm`` with ``N = 1`` and the teleport term as the
``beta = 1`` epilogue, then reads its L1 residual on the host, as a solver
checking convergence does.  The window starts from the uniform vector.
The check recomputes kept iterations from the program's own input iterate
with the float64 reference.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import graph, reference
from bench import work as W
from bench.loop import ClosedLoop, span


def build(cfg: Dict, traffic: Dict, rt: Dict):
    return PageRank(cfg, traffic, rt)


class PageRank(ClosedLoop):

    sample_count = 16
    sample_of = 64

    def __init__(self, cfg: Dict, traffic: Dict, rt: Dict):
        super().__init__(rt)
        from repro.core.engine import SextansEngine
        from repro.core.sparse import SparseMatrix

        self.traffic = traffic
        with span("bench.generate"):
            self.coo = graph.matrix(cfg, traffic["matrix"], rt["seed"])
        n = self.coo[0][0]
        self.damping = traffic["damping"]
        self.control = rt.get("control", False)
        self.eng = SextansEngine()
        with span("bench.pack"):
            self.a = self.eng.pack(SparseMatrix(*self.coo))
        self.x0 = jnp.full((n, 1), 1.0 / n, jnp.float32)
        self.tele = jnp.full((n, 1), (1.0 - self.damping) / n, jnp.float32)
        self.resid = jax.jit(lambda y, x: jnp.abs(y - x).sum())
        self.x = self.x0
        self.residuals: List[float] = []
        nnz = self.coo[1].shape[0]
        self.step_work = W.csr_spmm(n, n, nnz, 1, beta=1.0)
        if rt.get("peak"):
            self.step_roofline_s = self.step_work.roofline_s(rt["peak"])

    def warm(self, repeats: int = 2):
        super().warm(repeats)
        self.x = self.x0
        self.residuals = []

    def call(self, i: int):
        x = self.x
        if self.control:
            y = jnp.asarray(reference.spmm_high(
                self.coo, np.asarray(x), np.asarray(self.tele),
                self.damping, 1.0))
        else:
            with span("bench.dispatch"):
                y = self.eng.spmm(self.a, x, self.tele, self.damping, 1.0)
        with span("bench.sync"):
            self.residuals.append(float(self.resid(y, x)))
        self.x = y
        return (x, y)

    def counters(self) -> Dict:
        vals = self.a.data.vals
        return {"nnz": int(self.a.nnz),
                "slab_slots": int(np.prod(vals.shape)),
                "dispatches": self.eng.stats.dispatches,
                "last_residual": self.residuals[-1]}

    def release(self):
        self.tele_h = np.asarray(self.tele)
        self.eng = self.a = self.x = self.x0 = self.tele = None

    def reference_check(self, kept) -> List[Dict]:
        errs = []
        for i in sorted(kept):
            x, y = kept[i]
            args = (self.coo, x, self.tele_h, self.damping, 1.0)
            errs.append(reference.scaled_error(
                y, reference.spmm(*args), reference.term_scale(*args)))
        return [{"name": "scaled_err", "value": max(errs) if errs
                 else float("inf"),
                 "limit": self.traffic["limits"]["scaled_err"]}]
