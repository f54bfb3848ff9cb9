"""JAX's persistent compilation cache, placed from outside the program.

``enable()`` is called once by each entry point (``chip_smoke.py``,
``benchmarks/run.py``) before it compiles anything:

* with ``$JAX_COMPILATION_CACHE_DIR`` set, JAX already reads that
  directory itself and nothing is configured here;
* otherwise the cache goes to the fixed ``<checkout>/.jax_cache``
  (ignored by git).  The path is part of what a later process looks up,
  so it never depends on a temporary name, a pid or the time.

``STATS`` counts the cache's hits and writes in this process (from JAX's
own monitoring events), so a second run of the same program can show
that it compiled nothing new.  JAX writes only programs that took at
least ``jax_persistent_cache_min_compile_time_secs`` (1 s by default) to
compile, so small programs show neither.
"""

from __future__ import annotations

import os
import threading
from typing import Dict

import jax

__all__ = ["ENV", "STATS", "checkout_dir", "enable"]

ENV = "JAX_COMPILATION_CACHE_DIR"

STATS: Dict[str, int] = {"hits": 0, "writes": 0}

# JAX records its "cache_misses" event when it writes a new entry
_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "writes"}
_LOCK = threading.Lock()
_listening = False


def checkout_dir() -> str:
    """``<checkout>/.jax_cache``: the cache directory when none is set."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".jax_cache")


def _count(event: str, **_kw) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        with _LOCK:
            STATS[name] += 1


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    global _listening
    with _LOCK:
        if not _listening:
            jax.monitoring.register_event_listener(_count)
            _listening = True
    path = os.environ.get(ENV)
    if path:
        return path
    path = checkout_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
