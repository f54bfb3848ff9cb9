"""The closed loop the batch cells share.

Each step follows the last, the way a layer pass or a solver iteration
does: the generator's ``step(i)`` issues the program's calls and waits until
the result is on the device's side of ``block_until_ready``.  The window
runs steps until ``seconds`` have passed; the last step is counted whole,
and the rates are taken over all the steps and all the time it took.

A few steps' results are kept for the check: the positions are drawn from
the seed among the first ``sample_of`` steps, plus the window's last step.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import time
from typing import Dict, List

import jax
import numpy as np

from bench import work as W

#: host seconds spent in each span name, in this process
SPAN_SECONDS: Dict[str, float] = collections.defaultdict(float)


@contextlib.contextmanager
def span(name: str):
    """A host span: a ``jax.profiler.TraceAnnotation`` (read from a
    ``--trace 1`` run's trace) whose seconds also add up in
    ``SPAN_SECONDS``."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    SPAN_SECONDS[name] += time.perf_counter() - t0


def draw_normal(seed: int, salt: int, count: int, shape) -> List[jax.Array]:
    """``count`` float32 standard-normal arrays of ``shape`` on the device,
    drawn in one jitted call from ``(seed, salt)``."""
    key = jax.random.key(np.random.default_rng([seed, salt]).integers(2 ** 31))

    @jax.jit
    def draw(k):
        return list(jax.random.normal(k, (count, *shape), jax.numpy.float32))

    return draw(key)


class ClosedLoop:
    """Base of the batch generators.  A subclass sets ``step_work`` and
    ``step_roofline_s`` (per step, from the generated sizes) and
    implements ``call(i)`` (the program's work for step ``i``, returning
    the result to keep) and ``reference_check(kept)``."""

    sample_count = 2
    sample_of = 16

    def __init__(self, rt: Dict):
        self.rt = rt
        rng = np.random.default_rng([rt["seed"], 7])
        self.sample = set(int(i) for i in rng.choice(
            self.sample_of, self.sample_count, replace=False))
        self.kept: Dict[int, object] = {}
        self.step_work = W.ZERO
        self.step_roofline_s = 0.0

    # -- subclass hooks -----------------------------------------------------

    def call(self, i: int):
        raise NotImplementedError

    def keep(self, i: int, out):
        """What of step ``i`` the check needs (default: its output)."""
        return out

    def release(self):
        """Drop the program's state before the reference runs."""

    def reference_check(self, kept: Dict[int, object]) -> List[Dict]:
        raise NotImplementedError

    def counters(self) -> Dict:
        return {}

    # -- driven by the harness ----------------------------------------------

    def warm(self, repeats: int = 2):
        for _ in range(repeats):
            self.call(0)

    def window(self, seconds: float) -> Dict:
        i = 0
        last = None
        t0 = time.perf_counter()
        while True:
            out = self.call(i)
            if i in self.sample:
                self.kept[i] = self.keep(i, out)
            last = (i, out)
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        self.kept.setdefault(last[0], self.keep(*last))
        work = self.step_work * i
        return {"elapsed_s": elapsed, "attempted": i, "failed": 0,
                "metrics": {"useful_gflops": work.flops / elapsed / 1e9},
                "counters": dict(self.counters(), steps=i,
                                 useful_flops=work.flops,
                                 roofline_s=self.step_roofline_s * i)}

    def check(self) -> List[Dict]:
        kept = {i: jax.tree_util.tree_map(np.asarray, v)
                for i, v in self.kept.items()}
        self.kept = {}
        self.release()
        gc.collect()
        return self.reference_check(kept)
