"""Platform default for the Pallas ``interpret`` flag."""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Platform-aware default for the Pallas ``interpret`` flag.

    ``None`` means "interpret only off-TPU": on a real TPU the kernels
    compile through Mosaic; everywhere else (CPU CI, local dev) they run in
    the interpreter.  Explicit ``True``/``False`` is honored as-is."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
