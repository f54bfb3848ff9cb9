"""Named host spans: one profiler annotation and one timing per span.

    from repro.tracing import span, totals

    with span("sextans.plan.build") as s:
        ...
    s.wall_s                      # this span's seconds
    totals()["sextans.plan.build"]  # {"count", "wall_s", "self_s"}

Each ``span`` opens a ``jax.profiler.TraceAnnotation`` under its name, so
a profiled run shows it on the host plane, on the same clock as the
device's ops; and it adds to in-memory totals per name: how many spans
closed, their wall seconds, and their self seconds (wall less the spans
opened inside it on the same thread).  The recorder is always on; with
no profiler session open a span costs the annotation object, two clock
reads and a dict update: about two microseconds on a server CPU.

The program's spans are named ``sextans.<layer>.<what>`` (``sextans.pack``,
``sextans.plan.build``, ``sextans.plan.run``, ...), so the prefix picks
them out of a trace.  The count of a name is the counter at that
boundary; no other table counts it.
"""

from __future__ import annotations

import threading
from time import perf_counter
from typing import Dict, List

from jax.profiler import TraceAnnotation

__all__ = ["span", "totals"]

_lock = threading.Lock()            # guards _per_thread
_per_thread: List[Dict[str, list]] = []   # each thread's totals
_local = threading.local()          # .top: innermost open span; .tot


def _start_thread():
    tot: Dict[str, list] = {}
    with _lock:
        _per_thread.append(tot)
    _local.top = None
    _local.tot = tot


class span:
    """A context manager around one named span; ``wall_s`` holds its
    seconds once it closes.  One instance is entered once."""

    __slots__ = ("name", "wall_s", "_ann", "_t0", "_child_s", "_parent")

    def __init__(self, name: str):
        self.name = name
        self.wall_s = 0.0

    def __enter__(self) -> "span":
        loc = _local
        try:
            self._parent = loc.top
        except AttributeError:          # the thread's first span
            _start_thread()
            self._parent = None
        loc.top = self
        self._child_s = 0.0
        self._ann = ann = TraceAnnotation(self.name)
        ann.__enter__()
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        wall = self.wall_s = perf_counter() - self._t0
        self._ann.__exit__(*exc)
        loc = _local
        parent = loc.top = self._parent
        if parent is not None:
            parent._child_s += wall
        t = loc.tot.get(self.name)
        if t is None:
            t = loc.tot[self.name] = [0, 0.0, 0.0]
        t[0] += 1
        t[1] += wall
        t[2] += wall - self._child_s
        return False


def totals() -> Dict[str, Dict[str, float]]:
    """A copy of the totals per span name, summed over threads:
    ``count``, ``wall_s`` and ``self_s`` since the process started."""
    out: Dict[str, Dict[str, float]] = {}
    with _lock:
        tables = list(_per_thread)
    for tot in tables:
        for name, (c, w, s) in dict(tot).items():
            o = out.setdefault(name, {"count": 0, "wall_s": 0.0,
                                      "self_s": 0.0})
            o["count"] += c
            o["wall_s"] += w
            o["self_s"] += s
    return out
