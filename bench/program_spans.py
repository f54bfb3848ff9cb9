"""The seconds of one of the program's own spans in a run, from its span
totals (``repro.tracing.totals()``), for the per-layer metrics that read
them.

A ``SpanSeconds`` takes the totals when it is made, which is when the
harness loads the metric's reader at the start of ``run()``, and reads the
change in the span's wall seconds after the window.  It is silent where no
such span closed in between, and for a program without ``repro.tracing``.
"""

from typing import Dict, Optional


def _totals() -> Optional[Dict[str, Dict[str, float]]]:
    try:
        from repro.tracing import totals
    except ImportError:      # a program from before the span recorder
        return None
    return totals()


class SpanSeconds:
    """Wall seconds of the spans named ``name`` since this was made."""

    def __init__(self, name: str):
        self.name = name
        self.start = _totals()

    def read(self, record) -> Optional[float]:
        now = _totals()
        if now is None or self.start is None or self.name not in now:
            return None
        before = self.start.get(self.name, {"count": 0, "wall_s": 0.0})
        if now[self.name]["count"] == before["count"]:
            return None
        return now[self.name]["wall_s"] - before["wall_s"]
