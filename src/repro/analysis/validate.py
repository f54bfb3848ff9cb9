"""Runtime invariant validator for packed Sextans artifacts.

The packing/scheduling pipeline rests on invariants no type ever states:
slab ``cols`` are window-local, ``q`` is the chunk-ceiled twin of the
true count ``nse``, padding slots carry zero values (the flat kernels
rely on it), schedules keep same-row non-zeros >= D cycles apart (II=1
legality, paper Sec. 3.3).  ``validate(obj)`` checks them exhaustively
and raises :class:`InvariantViolation` with the first offending
coordinate; it understands

* :class:`repro.sparse_api.SparseTensor` (HFLEX or BSR, batched or not,
  including ``stack_hflex`` / ``stack_bsr`` groups and ``windows()``
  slices),
* bare :class:`PackedSpMM` / :class:`BsrWeight` payloads,
* :class:`repro.core.hflex.PEStreams` (paper-form per-PE streams), and
* :class:`repro.core.schedule.Schedule` (pass ``rows=`` of the scheduled
  non-zeros).

Three entry points:

* explicit — ``from repro.analysis.validate import validate``;
* plan time — exporting ``SEXTANS_CHECK=1`` makes ``pack``/``plan``/
  ``spmm`` entry points run :func:`maybe_validate` on their packed
  operands (hooks live in ``sparse_api/tensor.py``/``ops.py``/
  ``plan.py``);
* tests — the ``sextans_check`` conftest fixture sets the env var for
  one test and hands back :func:`validate`.

Traced (jax ``Tracer``) payloads are skipped silently: inside
``jit``/``grad`` there is nothing concrete to check, and hooks must not
add trace-time data-dependent control flow.

Caveat: the PE-stream same-row distance check asserts the paper's strict
II=1 invariant; streams built with ``hub_split > 0`` deliberately relax
it for virtual sub-rows (merged in the CompC pass) and should be
validated with ``check_ii=False``.
"""
from __future__ import annotations

import os
from typing import Any, Optional

import numpy as np

from repro.core.hflex import slab_lanes

__all__ = ["InvariantViolation", "validate", "maybe_validate", "enabled",
           "ENV_VAR"]

ENV_VAR = "SEXTANS_CHECK"


class InvariantViolation(AssertionError):
    """A packed artifact broke a structural invariant."""


def enabled() -> bool:
    """True when ``SEXTANS_CHECK`` requests validation at plan time."""
    return os.environ.get(ENV_VAR, "").strip().lower() not in (
        "", "0", "false", "off", "no")


def _fail(msg: str) -> None:
    raise InvariantViolation(msg)


def _first(mask: np.ndarray) -> str:
    """Coordinate string of the first True entry of a boolean mask."""
    idx = np.argwhere(mask)
    return "[" + ", ".join(str(int(i)) for i in idx[0]) + "]"


def _is_traced(tree: Any) -> bool:
    import jax

    return any(isinstance(x, jax.core.Tracer)
               for x in jax.tree_util.tree_leaves(tree))


# ---------------------------------------------------------------------------
# Dispatch

def validate(obj: Any, *, rows: Optional[np.ndarray] = None,
             check_ii: bool = True) -> Any:
    """Validate a packed artifact; return it unchanged on success.

    Raises :class:`InvariantViolation` (an ``AssertionError`` subclass,
    so plain ``pytest.raises(AssertionError)`` works too) naming the
    violated invariant and the first offending coordinate.  Traced
    payloads pass through unexamined.
    """
    from repro.core.hflex import PEStreams
    from repro.core.schedule import Schedule
    from repro.sparse_api.tensor import BsrWeight, PackedSpMM, SparseTensor

    if isinstance(obj, SparseTensor):
        _validate_tensor(obj)
    elif isinstance(obj, PackedSpMM):
        _validate_packed(obj, where="PackedSpMM")
    elif isinstance(obj, BsrWeight):
        _validate_bsr(obj, where="BsrWeight")
    elif isinstance(obj, PEStreams):
        _validate_pe_streams(obj, check_ii=check_ii)
    elif isinstance(obj, Schedule):
        if rows is None:
            raise TypeError("validate(Schedule) needs rows= (the row index "
                            "of each scheduled non-zero)")
        _validate_schedule(obj, rows)
    else:
        raise TypeError(f"validate() does not understand "
                        f"{type(obj).__name__}")
    return obj


def maybe_validate(obj: Any, **kw: Any) -> Any:
    """``validate(obj)`` when ``SEXTANS_CHECK`` is on; identity otherwise.

    This is the hook form used by pack/plan/spmm entry points — zero cost
    (one env lookup) when the flag is off.
    """
    if enabled():
        validate(obj, **kw)
    return obj


# ---------------------------------------------------------------------------
# HFlex slabs

def _validate_packed(d: Any, where: str, m: Optional[int] = None,
                     k: Optional[int] = None) -> None:
    if _is_traced(d):
        return
    vals = np.asarray(d.vals)
    cols = np.asarray(d.cols)
    rows = np.asarray(d.rows)
    q = np.asarray(d.q)
    nse = np.asarray(d.nse)
    m = d.m if m is None else m
    k = d.k if k is None else k

    if vals.ndim not in (4, 5):
        _fail(f"{where}: vals must be (MB, NW, R, L) or (G, MB, NW, R, L), "
              f"got ndim={vals.ndim}")
    for name, arr in (("cols", cols), ("rows", rows)):
        if arr.shape != vals.shape:
            _fail(f"{where}: {name} shape {arr.shape} != vals shape "
                  f"{vals.shape}")
    lanes = vals.shape[-1]
    if slab_lanes(vals.shape[-2] * lanes) != lanes:
        _fail(f"{where}: slab rows of {lanes} lanes do not match the lane "
              f"layout of LW={vals.shape[-2] * lanes}")
    # checks below read the flat slot axis: ([G,] MB, NW, LW)
    vals, cols, rows = (x.reshape(*x.shape[:-2], -1)
                        for x in (vals, cols, rows))
    for name, arr in (("q", q), ("nse", nse)):
        if arr.shape != vals.shape[:-1]:
            _fail(f"{where}: {name} shape {arr.shape} != slab prefix "
                  f"{vals.shape[:-1]}")
    if not np.issubdtype(vals.dtype, np.floating):
        _fail(f"{where}: vals must be floating, got {vals.dtype}")
    for name, arr in (("cols", cols), ("rows", rows), ("q", q),
                      ("nse", nse)):
        if not np.issubdtype(arr.dtype, np.integer):
            _fail(f"{where}: {name} must be integral, got {arr.dtype}")

    mb, nw, lw = vals.shape[-3], vals.shape[-2], vals.shape[-1]
    tm, k0, chunk = d.tm, d.k0, d.chunk
    if min(tm, k0, chunk) <= 0:
        _fail(f"{where}: non-positive tiling (tm={tm}, k0={k0}, "
              f"chunk={chunk})")
    if not (mb - 1) * tm < m <= mb * tm:
        _fail(f"{where}: M={m} inconsistent with MB={mb} row blocks of "
              f"TM={tm}")
    if not (nw - 1) * k0 < k <= nw * k0:
        _fail(f"{where}: K={k} inconsistent with NW={nw} windows of "
              f"K0={k0}")

    # pointer matrix: 0 <= nse <= q <= LW, q chunk-ceiled from nse
    if (nse < 0).any():
        _fail(f"{where}: negative nse at {_first(nse < 0)}")
    if (nse > q).any():
        i = _first(nse > q)
        _fail(f"{where}: nse overflows q (true count > scheduled slots) "
              f"at block {i}")
    if (q > lw).any():
        i = _first(q > lw)
        _fail(f"{where}: q exceeds slab width LW={lw} at block {i}")
    expect_q = -(-nse // chunk) * chunk  # cdiv * chunk
    if (q != expect_q).any():
        i = _first(q != expect_q)
        _fail(f"{where}: q is not the chunk-ceiled count "
              f"(chunk={chunk}) at block {i}")
    total = int(nse.sum())
    if total != d.nnz:
        _fail(f"{where}: nse sums to {total} but nnz={d.nnz}")

    # coordinates: window-local cols, block-local rows, and the valid
    # prefix must land inside the logical (M, K)
    slot = np.arange(lw)
    valid = slot < nse[..., None]
    if (cols < 0).any() or (cols >= k0).any():
        bad = (cols < 0) | (cols >= k0)
        _fail(f"{where}: column {int(cols[bad][0])} at {_first(bad)} "
              f"outside the window-local range [0, K0={k0})")
    wi = np.arange(nw, dtype=np.int64)[:, None]
    gcol = cols.astype(np.int64) + wi * k0
    bad = valid & (gcol >= k)
    if bad.any():
        _fail(f"{where}: global column {int(gcol[bad][0])} at "
              f"{_first(bad)} outside K={k} (out-of-window col)")
    if (rows < 0).any() or (rows >= tm).any():
        bad = (rows < 0) | (rows >= tm)
        _fail(f"{where}: row {int(rows[bad][0])} at {_first(bad)} outside "
              f"the block-local range [0, TM={tm})")
    bi = np.arange(mb, dtype=np.int64)[:, None, None]
    if d.interleaved:
        grow = rows.astype(np.int64) * mb + bi
    else:
        grow = bi * tm + rows.astype(np.int64)
    bad = valid & (grow >= m)
    if bad.any():
        _fail(f"{where}: global row {int(grow[bad][0])} at {_first(bad)} "
              f"outside M={m}")

    # padding slots must be exact zeros — the flat kernels add their
    # (index-0-targeted) contributions unconditionally
    bad = (~valid) & (vals != 0)
    if bad.any():
        _fail(f"{where}: non-zero value {float(vals[bad][0])} in a "
              f"padding slot at {_first(bad)} (slots >= nse must be 0)")


def _validate_tensor(t: Any) -> None:
    from repro.sparse_api.tensor import Format

    if _is_traced(t.data):
        return
    if t.format is Format.HFLEX:
        g = t.data.batch
        where = (f"SparseTensor[HFLEX, G={g}]" if g is not None
                 else "SparseTensor[HFLEX]")
        if t.shape != (t.data.m, t.data.k):
            _fail(f"{where}: logical shape {t.shape} != payload "
                  f"(M, K)=({t.data.m}, {t.data.k}) — geometry-"
                  f"inconsistent member or corrupted slice")
        _validate_packed(t.data, where=where)
    else:
        w = t.data
        g = w.batch
        where = (f"SparseTensor[BSR, G={g}]" if g is not None
                 else "SparseTensor[BSR]")
        _validate_bsr(w, where=where)
        # payload stores A^T padded up to tile multiples
        if not (t.m <= w.f and t.k <= w.k):
            _fail(f"{where}: logical shape {t.shape} exceeds "
                  f"padded weight ({w.f}, {w.k})")


# ---------------------------------------------------------------------------
# BSR weights

def _validate_bsr(w: Any, where: str) -> None:
    if _is_traced(w):
        return
    blocks = np.asarray(w.blocks)
    brow = np.asarray(w.brow)
    indptr = np.asarray(w.indptr)
    if w.k % w.tk or w.f % w.tf:
        _fail(f"{where}: (K={w.k}, F={w.f}) not multiples of tile "
              f"({w.tk}, {w.tf})")
    nbf = w.f // w.tf
    if blocks.ndim == 4:
        # stacked group: per-member arrays behind a leading G axis; NB is
        # the shared padded bucket, member g truly stores indptr[g, -1]
        g, nb = blocks.shape[0], blocks.shape[1]
        if blocks.shape[2:] != (w.tk, w.tf):
            _fail(f"{where}: blocks must be (G, NB, {w.tk}, {w.tf}), got "
                  f"{blocks.shape}")
        if indptr.shape != (g, nbf + 1):
            _fail(f"{where}: indptr must be (G={g}, F/TF+1={nbf + 1}), "
                  f"got {indptr.shape}")
        if brow.shape != (g, nb):
            _fail(f"{where}: brow must be (G={g}, NB={nb}), got "
                  f"{brow.shape}")
        for gi in range(g):
            nb_true = int(indptr[gi, -1])
            if nb_true > nb:
                _fail(f"{where}: member {gi} claims {nb_true} blocks but "
                      f"the padded bucket holds NB={nb}")
            _validate_bsr_member(blocks[gi, :nb_true], brow[gi, :nb_true],
                                 indptr[gi], nb_true, nbf, w,
                                 f"{where} member {gi}")
            pad = blocks[gi, nb_true:]
            if pad.size and (pad != 0).any():
                _fail(f"{where}: member {gi} has a non-zero padded block "
                      f"slot at {_first(pad != 0)} (slots >= "
                      f"indptr[g, -1]={nb_true} must be zero)")
            pad_brow = brow[gi, nb_true:]
            if pad_brow.size and ((pad_brow < 0)
                                  | (pad_brow >= w.k // w.tk)).any():
                _fail(f"{where}: member {gi} padded brow outside "
                      f"[0, K/TK={w.k // w.tk})")
        return
    if blocks.ndim != 3 or blocks.shape[1:] != (w.tk, w.tf):
        _fail(f"{where}: blocks must be (NB, {w.tk}, {w.tf}), got "
              f"{blocks.shape}")
    nb = blocks.shape[0]
    if indptr.shape != (nbf + 1,):
        _fail(f"{where}: indptr must have F/TF+1={nbf + 1} entries, got "
              f"{indptr.shape}")
    if brow.shape != (nb,):
        _fail(f"{where}: brow must have NB={nb} entries, got {brow.shape}")
    _validate_bsr_member(blocks, brow, indptr, nb, nbf, w, where)


def _validate_bsr_member(blocks: np.ndarray, brow: np.ndarray,
                         indptr: np.ndarray, nb: int, nbf: int,
                         w: Any, where: str) -> None:
    """Invariants of one BSR pointer walk (a single weight, or one member
    of a stacked group with its padding stripped)."""
    if indptr[0] != 0 or indptr[-1] != nb:
        _fail(f"{where}: indptr must run 0..NB={nb}, got "
              f"[{int(indptr[0])}..{int(indptr[-1])}]")
    if (np.diff(indptr) < 0).any():
        _fail(f"{where}: indptr not monotone at "
              f"{_first(np.diff(indptr) < 0)}")
    if nb and ((brow < 0) | (brow >= w.k // w.tk)).any():
        bad = (brow < 0) | (brow >= w.k // w.tk)
        _fail(f"{where}: block row {int(brow[bad][0])} outside "
              f"[0, K/TK={w.k // w.tk})")
    if nb > 1:
        bcol = np.searchsorted(indptr, np.arange(nb), side="right") - 1
        same = bcol[1:] == bcol[:-1]
        if (same & (np.diff(brow) <= 0)).any():
            _fail(f"{where}: block rows not strictly increasing within a "
                  f"column segment (kernel pointer walk assumes sorted)")


# ---------------------------------------------------------------------------
# PE streams (paper form)

def _validate_pe_streams(s: Any, check_ii: bool = True) -> None:
    from repro.core.hflex import decode_a64
    from repro.core.partition import cdiv

    P, K0, D = s.params.P, s.params.K0, s.params.D
    m, k = s.shape
    nw = cdiv(k, K0) if k else 0
    if len(s.streams) != P or len(s.q) != P:
        _fail(f"PEStreams: expected {P} streams/q arrays, got "
              f"{len(s.streams)}/{len(s.q)}")
    total_real = 0
    for p in range(P):
        stream = np.asarray(s.streams[p])
        q = np.asarray(s.q[p])
        if q.shape != (nw + 1,):
            _fail(f"PEStreams: q[{p}] must have NW+1={nw + 1} window "
                  f"offsets, got {q.shape}")
        if nw == 0:
            continue
        if q[0] != 0:
            _fail(f"PEStreams: q[{p}][0] = {int(q[0])} != 0")
        if (np.diff(q) < 0).any():
            j = int(np.argwhere(np.diff(q) < 0)[0][0])
            _fail(f"PEStreams: q[{p}] not monotone at window {j} "
                  f"({int(q[j])} -> {int(q[j + 1])})")
        if q[-1] != len(stream):
            _fail(f"PEStreams: q[{p}][-1] = {int(q[-1])} != stream length "
                  f"{len(stream)}")
        real = stream != s.BUBBLE_WORD
        total_real += int(real.sum())
        if not real.any():
            continue
        pos = np.nonzero(real)[0]
        row, col, _ = decode_a64(stream[pos])
        if ((col < 0) | (col >= K0)).any():
            bad = int(col[(col < 0) | (col >= K0)][0])
            _fail(f"PEStreams: stream {p} column {bad} outside the "
                  f"window-local range [0, K0={K0})")
        grow = row.astype(np.int64) * P + p
        if (grow >= m).any():
            _fail(f"PEStreams: stream {p} decodes global row "
                  f"{int(grow[grow >= m][0])} outside M={m}")
        if not check_ii:
            continue
        # II=1 legality per (window, row): same-row spacing >= D
        wid = np.searchsorted(q, pos, side="right") - 1
        order = np.lexsort((pos, row, wid))
        wo, ro, po = wid[order], row[order], pos[order]
        same = (wo[1:] == wo[:-1]) & (ro[1:] == ro[:-1])
        gap = np.diff(po)
        bad = same & (gap < D)
        if bad.any():
            i = int(np.argwhere(bad)[0][0])
            _fail(f"PEStreams: II=1 violation on stream {p}, window "
                  f"{int(wo[i])}: row {int(ro[i])} at cycles "
                  f"{int(po[i])} and {int(po[i + 1])} (distance "
                  f"{int(gap[i])} < D={D})")
    if total_real != s.nnz:
        _fail(f"PEStreams: streams carry {total_real} non-bubble words "
              f"but nnz={s.nnz}")


# ---------------------------------------------------------------------------
# Schedules

def _validate_schedule(sched: Any, rows: np.ndarray) -> None:
    from repro.core.schedule import min_dependency_distance, verify_schedule

    try:
        verify_schedule(sched, rows)
    except AssertionError as e:
        raise InvariantViolation(f"Schedule: {e}") from None
    dist = min_dependency_distance(sched, rows)
    if dist is not None and dist < sched.d:
        _fail(f"Schedule: dependency distance {dist} < D={sched.d} "
              f"(II=1 illegal)")
