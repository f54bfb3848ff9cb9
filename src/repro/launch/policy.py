"""Cost-model grouping policy for the SpMM serving scheduler.

The exact-key scheduler (:class:`repro.launch.serve.SpmmScheduler`) only
batches requests whose packed tensors land in the *same* geometry bucket
with the *same* epilogue scalars.  Mixed traffic therefore fragments into
many small dispatches even when the buckets are near-misses — adjacent
power-of-two LW slabs, adjacent padded-N widths, adjacent BSR block-count
buckets, or identical geometry with different ``(alpha, beta)``.  This
module decides, from the analytic cost model
(:func:`repro.core.perfmodel.packed_event_cycles`), when fragmenting is
the wrong call:

* **Near-miss merging** (:meth:`MergePolicy.plan_merges`): groups whose
  keys differ only in the LW bucket (HFLEX slab width / BSR block-count
  bucket) and/or the padded dense width N are *mergeable*: re-padding the
  narrow members up to the widest bucket is inert
  (:func:`repro.sparse_api.repad_lw` — ``q``/``nse`` untouched, padded
  slots exact zeros) and ragged N already zero-pads, so a merged dispatch
  is bit-identical per member to the split dispatches.  Whether it is
  *cheaper* is a padding-waste vs per-dispatch-overhead trade the cost
  model prices: merge exactly when

      cycles(merged union) < sum_i cycles(split group i),

  each side including ``dispatch_overhead_cycles`` per dispatch, and the
  padded-slot walk of the flat ``jnp`` backend charged via
  ``packed_event_cycles(..., lw=bucket)``.  No ad-hoc thresholds: a
  near-miss pair merges when overhead dominates and splits when padding
  waste dominates, and the contract tests pin both directions.

  Only the LW/N axes are merge-legal.  MB/NW (row-block / K-window
  counts) are *structural*: slab row ids interleave as ``rows * MB + bi``
  and window ids offset columns, so changing either re-addresses every
  non-zero — never merged, enforced by :func:`family_key`.

* **Epilogue folding** (:meth:`MergePolicy.fold_epilogue`): the batched
  execution paths apply ``(alpha, beta)`` as a per-member ``(G,)`` vector
  with the same FMA shape as the scalar epilogue
  (``repro.sparse_api.spmm``'s vector form), so members with different
  epilogues can share a group bit-identically.  The gate is explicit:
  only backends on the known vector-epilogue list fold; anything else
  (a custom registered backend) conservatively keeps ``(alpha, beta)``
  in the group key.

* **Admission** (:meth:`MergePolicy.full_enough`): the deadline-driven
  background flusher admits a forming group once its modeled work
  amortizes the per-dispatch overhead below ``fill_ratio`` (or the group
  hits ``max_group``); the deadline backstop lives in the scheduler.

The policy is pure host-side arithmetic over :class:`GroupSketch`
summaries — no engine, no device — so its merge/split contract is unit
testable in isolation.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.perfmodel import Platform, packed_event_cycles

__all__ = ["ABVEC_BACKENDS", "GroupSketch", "MergeCluster", "MergePolicy",
           "family_key"]

#: Backends whose batched (group) execution path applies ``(alpha, beta)``
#: as a per-member ``(G,)`` vector, bit-identically to the member's scalar
#: epilogue (same FMA, same operand order — see the SMEM ``(G, 2)`` block
#: of the Pallas kernels and ``_ab_expand`` on the jnp paths).  The fold
#: gate: only these may drop the epilogue scalars from the group key.
ABVEC_BACKENDS = frozenset({"pallas", "jnp"})


def family_key(key: Tuple) -> Tuple:
    """The merge-family identity of a scheduler group key: the key with
    its two merge-legal axes (LW/block-count bucket and padded N) scrubbed.

    Two groups may merge **only** when their family keys are equal —
    same format, same structural geometry (MB, NW, TM, K0, chunk,
    interleave / BSR tiling + logical shape), same dtype and same
    epilogue component (scalars, or the folded ``(None, None)``).
    """
    from repro.sparse_api import Format

    fmt, geo = key[0], key[1]
    if fmt is Format.BSR:
        # geo = (nb_bucket, K', F', TK, TF): the block-count bucket is the
        # LW analogue (stack_bsr pads members up to the shared bucket)
        fam_geo = (None,) + tuple(geo[1:])
    else:
        # geo = (mb, nw, lw, tm, k0, chunk, interleaved): only lw merges
        fam_geo = tuple(geo[:2]) + (None,) + tuple(geo[3:])
    return (fmt, fam_geo, key[2], None) + tuple(key[4:])


@dataclasses.dataclass(frozen=True)
class GroupSketch:
    """What the cost model needs to price one candidate dispatch group.

    ``q`` is the stacked per-member pointer matrix ``(G, MB, NW)`` (for
    BSR, the pseudo-``q`` ``(G, 1, 1)`` of true block counts — the
    pointer walk is the block walk); ``lw`` is the group's padded bucket
    width (slab LW / BSR block-count bucket) and ``flat`` says whether
    the resolved backend walks every padded slot: the flat segment-sum
    path (``jnp``) is charged at the full bucket width
    (``packed_event_cycles(..., lw=bucket)``), while the kernel walks
    exactly ``q`` chunk trips and prices at the true ``q``.
    """

    key: Tuple
    q: np.ndarray
    n: int
    k0: int
    lw: int
    flat: bool

    @property
    def g(self) -> int:
        return int(self.q.shape[0])


@dataclasses.dataclass(frozen=True)
class MergeCluster:
    """One policy decision: merge these groups into one padded dispatch."""

    keys: List[Tuple]     # original group keys, len >= 2
    lw: int               # target bucket width (max over members)
    n: int                # target padded dense width (max over members)
    saved_cycles: float   # sum(split costs) - merged cost, > 0


class MergePolicy:
    """Cost-model merge/fold/admission policy.

    ``dispatch_overhead_cycles`` is the modeled fixed cost of one compiled
    call (host launch + plan lookup + operand staging), in the same cycle
    units as :func:`packed_event_cycles`; it is what merging amortizes.
    ``fill_ratio`` bounds the admitted overhead share for the background
    flusher: a group is *full enough* once
    ``dispatch_overhead_cycles <= fill_ratio * work_cycles``.
    """

    def __init__(self, params: Optional[Platform] = None,
                 dispatch_overhead_cycles: float = 200_000.0,
                 fill_ratio: float = 0.5):
        if dispatch_overhead_cycles < 0:
            raise ValueError("dispatch_overhead_cycles must be >= 0")
        if fill_ratio <= 0:
            raise ValueError("fill_ratio must be > 0")
        self.params = params
        self.dispatch_overhead_cycles = float(dispatch_overhead_cycles)
        self.fill_ratio = float(fill_ratio)

    # -- epilogue folding ----------------------------------------------------

    def fold_epilogue(self, backend: str) -> bool:
        """True when ``backend``'s group path applies per-member
        ``(alpha, beta)`` vectors bit-identically — the scheduler may then
        lift the epilogue scalars out of the group key and dispatch the
        member coefficients as a ``(G,)`` vector."""
        return backend in ABVEC_BACKENDS

    # -- pricing -------------------------------------------------------------

    def group_cycles(self, sk: GroupSketch, *, lw: Optional[int] = None,
                     n: Optional[int] = None) -> float:
        """Modeled cycles of dispatching ``sk`` as one group, optionally
        re-priced at a wider target bucket (``lw``) / padded width (``n``)
        — how a merge candidate's members are priced inside the union."""
        lw_t = sk.lw if lw is None else max(lw, sk.lw)
        n_t = sk.n if n is None else max(n, sk.n)
        return float(packed_event_cycles(
            sk.q, n_t, self.params, k0=sk.k0,
            dispatch_overhead_cycles=self.dispatch_overhead_cycles,
            lw=(lw_t if sk.flat else None)))

    def merged_cycles(self, sks: Sequence[GroupSketch]) -> float:
        """Cycles of the union dispatched as ONE group at the widest
        member bucket/width.  One dispatch overhead total; every member's
        slab walk priced at the union's LW bucket on flat backends."""
        lw_t = max(sk.lw for sk in sks)
        n_t = max(sk.n for sk in sks)
        per_member = sum(
            self.group_cycles(sk, lw=lw_t, n=n_t) for sk in sks)
        # group_cycles charged one dispatch per sketch; the union pays one
        return per_member - self.dispatch_overhead_cycles * (len(sks) - 1)

    def should_merge(self, sks: Sequence[GroupSketch]) -> bool:
        """Merge exactly when the union beats the split dispatches."""
        split = sum(self.group_cycles(sk) for sk in sks)
        return self.merged_cycles(sks) < split

    # -- merge planning ------------------------------------------------------

    def plan_merges(self, sketches: Sequence[GroupSketch],
                    max_group: Optional[int] = None) -> List[MergeCluster]:
        """Greedy cost-model merge plan over one flush's groups.

        Within each merge family (:func:`family_key`), clusters start as
        the original groups and the pair with the largest positive
        ``split - merged`` saving merges first, repeating until no pair
        saves cycles (or would exceed ``max_group`` members).  Greedy
        best-pair is exact for two groups — the contract case — and a
        sound heuristic beyond (every applied merge is individually
        cost-positive, so the plan never loses to the split baseline).
        """
        families: Dict[Tuple, List[List[GroupSketch]]] = {}
        for sk in sketches:
            families.setdefault(family_key(sk.key), []).append([sk])
        out: List[MergeCluster] = []
        for clusters in families.values():
            while len(clusters) > 1:
                best = None
                for i in range(len(clusters)):
                    for j in range(i + 1, len(clusters)):
                        cand = clusters[i] + clusters[j]
                        if max_group is not None and sum(
                                sk.g for sk in cand) > max_group:
                            continue
                        saving = (sum(self.merged_cycles(c) if len(c) > 1
                                      else self.group_cycles(c[0])
                                      for c in (clusters[i], clusters[j]))
                                  - self.merged_cycles(cand))
                        if saving > 0 and (best is None or saving > best[0]):
                            best = (saving, i, j)
                if best is None:
                    break
                _, i, j = best
                merged = clusters[i] + clusters[j]
                clusters[:] = [c for k, c in enumerate(clusters)
                               if k not in (i, j)] + [merged]
            for c in clusters:
                if len(c) > 1:
                    split = sum(self.group_cycles(sk) for sk in c)
                    out.append(MergeCluster(
                        keys=[sk.key for sk in c],
                        lw=max(sk.lw for sk in c),
                        n=max(sk.n for sk in c),
                        saved_cycles=split - self.merged_cycles(c)))
        return out

    # -- admission (background flusher) --------------------------------------

    def full_enough(self, sk: GroupSketch,
                    max_group: Optional[int] = None) -> bool:
        """True when the forming group's modeled work amortizes the
        per-dispatch overhead below ``fill_ratio`` (or the group is at
        ``max_group``) — the background flusher's non-deadline admission
        signal.  More members monotonically add work, so a full-enough
        group stays full enough."""
        if max_group is not None and sk.g >= max_group:
            return True
        work = self.group_cycles(sk) - self.dispatch_overhead_cycles
        return self.dispatch_overhead_cycles <= self.fill_ratio * work
