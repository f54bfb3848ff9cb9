"""repro.sparse_api — the unified sparse front-end.

One differentiable, format-agnostic SpMM:

    >>> import repro.sparse_api as sp
    >>> A = sp.from_dense(a_np)                   # or from_coo / from_sparse_matrix
    >>> y = sp.spmm(A, b, c, alpha=1.0, beta=0.5) # traced alpha/beta
    >>> y = A @ b                                 # operator sugar
    >>> g = jax.grad(lambda v: sp.spmm(A.with_values(v), b).sum())(A.values)

Formats (``Format.HFLEX`` slabs, ``Format.BSR`` tiles) and execution
backends are orthogonal: ``pallas`` runs the format's chip kernel (at a
tile it takes from N: :func:`hflex_lane` on HFLEX, :func:`bsr_row_tile`
on BSR), ``jnp`` is the XLA reference, and ``auto`` picks one of them;
new ones plug in through :func:`register_backend`.

Serving hot loops should prepare a :func:`plan` (an :class:`SpmmPlan`):
backend resolution, index precompute and executable compilation happen
once, ``plan.run(b, c, alpha, beta)`` is a bare compiled call with results
bit-identical to ``spmm``.

Bucket-mates (same slab geometry) batch into ONE dispatch:
:func:`stack_hflex` (HFLEX) / :func:`stack_bsr` (pruned BSR weights)
stack G matrices behind a leading group axis (``A.batch``), ``spmm`` then
takes ``b`` of shape ``(G, K, N)``, and :func:`plan_group` prepares a
single group executable; ``plan(..., mesh=)`` carries multi-chip
shardings on the same abstraction.

Matrices larger than device memory stream: ``plan(..., device_bytes=)``
returns a :class:`StreamingPlan` that pipelines K0-window chunks through a
persistent C accumulator (bit-identical to the resident path), and
:func:`spmm_streaming` is its differentiable twin (per-chunk cotangent
accumulation).
"""

from .autotune import (
    AUTOTUNE_MODES,
    TUNE_SCHEMA,
    TUNE_STATS,
    TuningDB,
    apply_skinny_from_db,
    get_db,
    tune_key,
    tune_plan,
    tune_skinny_threshold,
)
from .backends import (
    BACKEND_STATS,
    SKINNY_N_MAX,
    Backend,
    StreamOps,
    bsr_row_tile,
    get_backend,
    hflex_lane,
    is_kernel,
    is_skinny,
    list_backends,
    register_backend,
    resolve_backend,
    set_auto_policy,
    set_skinny_n_max,
    skinny_n_max,
)
from .ops import spmm, spmm_raw, spmm_streaming
from .plan import (
    PLAN_STATS,
    RaggedPlan,
    SpmmPlan,
    StreamingPlan,
    clear_plan_cache,
    device_memory_budget,
    plan,
    plan_group,
    plan_ragged,
)
from .tensor import (
    BsrWeight,
    Format,
    PackedSpMM,
    SparseTensor,
    bucket_block_count,
    from_bsr_weight,
    from_coo,
    from_dense,
    from_sparse_matrix,
    pack_bsr_weight,
    pack_hflex,
    repad_lw,
    stack_bsr,
    stack_hflex,
)

__all__ = [
    "Format",
    "SparseTensor",
    "PackedSpMM",
    "BsrWeight",
    "spmm",
    "spmm_raw",
    "spmm_streaming",
    "plan",
    "plan_group",
    "plan_ragged",
    "SpmmPlan",
    "RaggedPlan",
    "StreamingPlan",
    "StreamOps",
    "PLAN_STATS",
    "clear_plan_cache",
    "device_memory_budget",
    "from_coo",
    "from_dense",
    "from_sparse_matrix",
    "from_bsr_weight",
    "pack_hflex",
    "pack_bsr_weight",
    "stack_hflex",
    "stack_bsr",
    "bucket_block_count",
    "repad_lw",
    "Backend",
    "register_backend",
    "get_backend",
    "list_backends",
    "resolve_backend",
    "set_auto_policy",
    "BACKEND_STATS",
    "SKINNY_N_MAX",
    "skinny_n_max",
    "set_skinny_n_max",
    "hflex_lane",
    "bsr_row_tile",
    "is_skinny",
    "is_kernel",
    "AUTOTUNE_MODES",
    "TUNE_SCHEMA",
    "TUNE_STATS",
    "TuningDB",
    "get_db",
    "tune_key",
    "tune_plan",
    "tune_skinny_threshold",
    "apply_skinny_from_db",
]
