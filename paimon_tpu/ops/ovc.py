"""Offset-value coding over normalized-key lanes.

Graefe et al., "Robust and Efficient Sorting with Offset-Value Coding"
(arXiv 2209.08420): when merging SORTED runs, each row carries a single
integer code — the offset of its first difference from its run
predecessor plus the value at that offset — and merge comparisons
collapse to one integer compare, falling through to lane compares only
on code ties.  Each output row's final code ends up relative to the
previous output row, so key-equality (the segment boundaries the
dedup/agg winner selection needs) falls out of the merge for free.

This module drives the native merge in native/radix_sort.c, which
computes the initial per-run codes in ONE sequential C pass
(ovc_codes_u64/ovc_codes_lanes — the pass also verifies the runs
actually honor their (key, seq) sort contract; a violated contract
silently falls back to the sort paths instead of producing a wrong
merge) and then runs the single-int-compare merge.  ops/merge.py
routes eligible host merges here: the O(n log n) radix/lexsort of a
merge window becomes an O(n log k) merge, and the separate
neighbor-equality pass disappears.

Code layout for an L-lane u32 key row r relative to base row z:
    offset = first lane where r differs from z   (L = all equal)
    code   = (L - offset) << 32 | r[offset]      (0 when equal)
Larger code = larger row.  The first row of each run is coded relative
to an imaginary -infinity row (offset 0), which every first-tournament
comparison shares as its base.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import numpy as np

__all__ = ["ovc_enabled", "ovc_sorted_winners", "run_ovc_offsets",
           "OVC_OFF_SENTINEL", "OVC_PATH_ROWS"]

# run-start rows carry no usable code (their predecessor is the -inf
# sentinel, not a real row): the device winner-select must fall through
# to full lane compares exactly there
OVC_OFF_SENTINEL = np.uint32(0xFFFFFFFF)

# rows merged through the OVC path this process (observability: bench)
OVC_PATH_ROWS = {"rows": 0, "merges": 0}


def ovc_enabled() -> bool:
    """OVC merge on unless explicitly disabled (kill switch mirrors
    PAIMON_DISABLE_NATIVE): tests take the sort-based paths it
    replaces as their reference."""
    return os.environ.get("PAIMON_DISABLE_OVC") != "1"


def run_ovc_offsets(lanes, run_starts: np.ndarray) -> np.ndarray:
    """uint32[n] per-row OVC OFFSETS vs the run predecessor: the first
    lane index where the row differs (num_lanes = all lanes equal),
    OVC_OFF_SENTINEL at run starts.  This is the single-int code the
    device winner-select consumes: a sorted-adjacent pair that is also
    run-consecutive resolves key-(in)equality from the offset alone —
    offset >= num_key_lanes means same key — and only the remaining
    pairs fall through to the full lane-compare chain
    (ops/merge.py _eq_next)."""
    mat = np.asarray(lanes)
    n, num_lanes = mat.shape
    out = np.full(n, np.uint32(num_lanes), dtype=np.uint32)
    if n:
        diff = mat[1:] != mat[:-1]
        any_diff = diff.any(axis=1)
        off = np.argmax(diff, axis=1).astype(np.uint32)
        out[1:] = np.where(any_diff, off, np.uint32(num_lanes))
        starts = np.asarray(run_starts)[:-1]
        out[starts[starts < n]] = OVC_OFF_SENTINEL
    return out


def ovc_sorted_winners(lanes, seq: np.ndarray,
                       run_starts: np.ndarray, num_key_lanes: int,
                       packed: Optional[np.ndarray] = None
                       ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """(perm, eq) — the merged order over the unpadded rows and whether
    sorted rows i and i+1 share a key, which ops/merge.py's
    `_winner_epilogue` turns into the host paths' (perm, winner, prev) —
    via the native OVC merge, or None when ineligible (native runtime
    unavailable, empty input, or a run that is not actually (key,
    seq)-sorted; the caller falls back to the sort paths)."""
    from paimon_tpu import native

    n = len(seq)
    if n == 0 or not ovc_enabled() or not native.predicted_available():
        return None
    seq = np.ascontiguousarray(seq, dtype=np.int64)
    starts = np.ascontiguousarray(run_starts, dtype=np.int64)
    if packed is not None and num_key_lanes == 2:
        res = native.ovc_merge_u64(
            np.ascontiguousarray(packed, dtype=np.uint64), seq, starts)
        num_lanes = 2
    else:
        mat = np.ascontiguousarray(np.asarray(lanes), dtype=np.uint32)
        if mat.shape[1] == 0:
            return None
        res = native.ovc_merge_lanes(mat, seq, starts)
        num_lanes = mat.shape[1]
    if res is None:
        return None
    perm, out_codes = res
    OVC_PATH_ROWS["rows"] += n
    OVC_PATH_ROWS["merges"] += 1
    # output code i is relative to output row i-1: neighbor rows share
    # a KEY iff the first difference sits past the key lanes
    eq = (out_codes[1:] >> np.uint64(32)) \
        <= np.uint64(num_lanes - num_key_lanes)
    return perm, eq
