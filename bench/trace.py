"""From a profiler trace to device busy time, op times and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
only what the reduction needs, in a plain dict that can be stored as JSON
(the tests check it on a trace they record and on one written by hand):

* ``devices``: for each device plane (``/device:TPU:<i>``), the events of
  its ``XLA Ops`` line as ``[name, start_ns, duration_ns]``, the name
  shortened by ``op_name``;
* ``spans``: the benchmark's own host spans (``jax.profiler.
  TraceAnnotation`` names that start with ``bench.``), same layout.

``reduce`` turns that into the numbers of one traced window, the span
``bench.window``:

* ``busy_s``: the union of the device-op intervals inside the window,
  averaged over the devices;
* ``window_s``: the window's length;
* ``device_ops``: seconds per op name, summed over devices and ops, most
  first;
* ``idle_gaps``: device idle seconds (device 0) by the innermost host span
  open at the middle of each gap, most first (``none`` where no span
  was open).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
TOP = 10


def op_name(hlo: str) -> str:
    """``%name type[dims]`` of an op's HLO text (layouts and operands
    dropped), e.g. ``%bsr_spmm.1 f32[2,128,11008]``."""
    head, _, rest = hlo.partition(" = ")
    m = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return f"{head} {m.group(1)}" if m else head


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, List] = {}
    spans: List = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [op_name(e.name), int(e.start_ns),
                         int(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, int(e.start_ns),
                                      int(e.duration_ns)])
    return {"devices": devices, "spans": spans}


def _union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def window(trace: Dict) -> Tuple[int, int]:
    wins = [(s, s + d) for n, s, d in trace["spans"] if n == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    return wins[0]


def _innermost(spans, t: float) -> str:
    best, best_start = "none", None
    for name, s, d in spans:
        if name != WINDOW_SPAN and s <= t < s + d:
            if best_start is None or s >= best_start:
                best, best_start = name[len(SPAN_PREFIX):], s
    return best


def reduce(trace: Dict) -> Dict:
    """Busy, op and idle numbers of the traced window; ``busy_s`` is None
    when no device op ran in it."""
    t0, t1 = window(trace)
    ops: Dict[str, float] = {}
    busy: List[float] = []
    first_union = None
    for plane in sorted(trace["devices"]):
        clipped = []
        for name, s, d in trace["devices"][plane]:
            s0, e0 = max(s, t0), min(s + d, t1)
            if e0 > s0:
                clipped.append((s0, e0))
                ops[name] = ops.get(name, 0.0) + (e0 - s0) / 1e9
        u = _union(clipped)
        busy.append(sum(e - s for s, e in u) / 1e9)
        if first_union is None:
            first_union = u
    gaps: Dict[str, float] = {}
    if first_union:
        edges = [t0] + [x for iv in first_union for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                key = _innermost(trace["spans"], (a + b) / 2)
                gaps[key] = gaps.get(key, 0.0) + (b - a) / 1e9
    busy_s = sum(busy) / len(busy) if busy and sum(busy) > 0 else None
    top = lambda d: sorted(([k, v] for k, v in d.items()),  # noqa: E731
                           key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": busy_s, "window_s": (t1 - t0) / 1e9,
            "device_ops": top(ops),
            "idle_gaps": top(gaps) if busy_s else []}
