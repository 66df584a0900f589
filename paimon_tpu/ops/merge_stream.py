"""Streamed k-way merge in bounded key windows.

Kills the whole-bucket memory cliff (SURVEY hard part (d)): instead of
concatenating every run of a bucket in RAM and padding to a power of two,
runs stream in as bounded Arrow chunks, and the device kernel merges one
key WINDOW at a time:

1. every run keeps a small buffer of decoded chunks
2. the window bound = MIN over non-exhausted runs of their last buffered
   key — every key strictly below it is fully present in the buffers
3. rows below the bound are cut from all buffers (run order preserved),
   merged with the normal segmented-sort kernel, and emitted
4. buffers refill; repeat until all runs drain, then flush the remainder

Windows partition the keyspace, so per-key semantics (dedup last-by-seq,
partial-update, aggregation) are EXACTLY those of the one-shot merge:
a key's rows never straddle windows (the cut compares normalized-key
lanes, and prefix-equal truncated keys stay in one window together).

Peak memory ~ k_runs x chunk_rows + window, independent of bucket size.
This replaces the reference's record-at-a-time spillable MergeSorter
(mergetree/MergeSorter.java:112) with a columnar pipeline.
"""

from __future__ import annotations

from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

from paimon_tpu.obs.trace import span
from paimon_tpu.ops.merge import merge_runs
from paimon_tpu.ops.normkey import NormalizedKeyEncoder

__all__ = ["merge_runs_streamed", "iter_merge_windows"]


def _cut_point(lanes: np.ndarray, bound: Tuple) -> int:
    """Rows with key lanes lexicographically < bound form a PREFIX of a
    key-sorted buffer, so the cut is a binary search (O(L log n)), not a
    full vectorized compare over the chunk."""
    lo, hi = 0, lanes.shape[0]
    num_lanes = lanes.shape[1]
    while lo < hi:
        mid = (lo + hi) // 2
        row = lanes[mid]
        lt = False
        for i in range(num_lanes):
            ri = int(row[i])
            bi = int(bound[i])
            if ri != bi:
                lt = ri < bi
                break
        if lt:
            lo = mid + 1
        else:
            hi = mid
    return lo


class _RunState:
    def __init__(self, chunks: Iterator, key_cols: Sequence[str],
                 encoder: NormalizedKeyEncoder):
        self._chunks = chunks
        self.key_cols = list(key_cols)
        self.encoder = encoder
        # single-fixed-key tables: bounds and cuts compare the packed
        # u64 (one searchsorted) instead of lane tuples — the window
        # comparator consuming the single-int code (ops/ovc.py is the
        # same idea inside the merge itself)
        self.packed_mode = getattr(encoder, "packs_single_key", False)
        # (table, lanes, truncated, packed-u64-or-None) quads
        self.buffer: List[Tuple] = []
        self.exhausted = False

    @property
    def buffered_rows(self) -> int:
        return sum(item[0].num_rows for item in self.buffer)

    def fill_one(self) -> bool:
        if self.exhausted:
            return False
        try:
            item = next(self._chunks)
        except StopIteration:
            self.exhausted = True
            return False
        if isinstance(item, tuple):
            # pre-encoded upstream (e.g. inside a prefetch thread, so
            # the lane encode overlaps the merge):
            # (table, lanes, trunc[, packed])
            t, lanes, trunc = item[:3]
            packed = item[3] if len(item) > 3 else None
        else:
            t, lanes, trunc, packed = item, None, None, None
        if t.num_rows == 0:
            return self.fill_one()
        if lanes is None:
            lanes, trunc, packed = self.encoder.encode_table_ex(
                t, self.key_cols)
        elif packed is None and self.packed_mode:
            # upstream handed raw lanes: derive the packed key so every
            # buffered chunk cuts through the same u64 comparator
            mat = np.asarray(lanes)
            packed = (mat[:, 0].astype(np.uint64) << np.uint64(32)) \
                | mat[:, 1].astype(np.uint64)
        self.buffer.append((t, lanes, trunc, packed))
        return True

    def last_key(self) -> Optional[Tuple]:
        if not self.buffer:
            return None
        if self.packed_mode:
            return int(self.buffer[-1][3][-1])
        lanes = self.buffer[-1][1]
        return tuple(lanes[-1])

    def key_at(self, idx: int):
        """Key of the idx-th buffered row (run order), or None when
        fewer rows are buffered — the per-run window-size cap probe."""
        for t, lanes, _trunc, packed in self.buffer:
            n = t.num_rows
            if idx < n:
                if self.packed_mode:
                    return int(packed[idx])
                return tuple(lanes[idx])
            idx -= n
        return None

    def cut_lt(self, bound: Tuple) -> List[Tuple]:
        """Remove and return rows with key lanes < bound (a prefix of the
        buffer, since runs are key-sorted)."""
        head: List[Tuple] = []
        new_buffer: List[Tuple] = []
        for t, lanes, trunc, packed in self.buffer:
            if new_buffer:
                new_buffer.append((t, lanes, trunc, packed))  # past bound
                continue
            if self.packed_mode:
                k = int(np.searchsorted(packed, np.uint64(bound),
                                        side="left"))
            else:
                k = _cut_point(lanes, bound)
            if k == t.num_rows:
                head.append((t, lanes, trunc, packed))
            else:
                if k:
                    head.append((t.slice(0, k), lanes[:k], trunc[:k],
                                 packed[:k] if packed is not None
                                 else None))
                new_buffer.append((t.slice(k), lanes[k:], trunc[k:],
                                   packed[k:] if packed is not None
                                   else None))
        self.buffer = new_buffer
        return head

    def take_all(self) -> List[Tuple]:
        out = self.buffer
        self.buffer = []
        return out


def iter_merge_windows(
    run_chunk_iters: Sequence[Iterator],
    key_cols: Sequence[str],
    key_encoder: NormalizedKeyEncoder,
    stats: Optional[Dict[str, int]] = None,
    window_rows: Optional[int] = None,
) -> Iterator[List[Tuple]]:
    """Pull-based window stream: yields one run-ordered item list per key
    window, in ascending key order.  Each item is a (table, lanes,
    truncated, packed-u64-or-None) quad; the concatenation of a window's
    items holds every buffered row whose key is strictly below the
    window bound, so per-key merge semantics applied window-by-window
    equal the one-shot merge (keys never straddle windows).

    This is the generator form of ``merge_runs_streamed`` — the mesh
    compaction engine (parallel/mesh_engine.py) pulls one window per
    bucket lane per mesh step to build its [B, window] device batches,
    while the single-chip streamed rewrite keeps the push (emit) shape.

    `stats`, when given, records "peak_buffered_rows": the max total
    rows buffered across runs at any point — the observable that the
    bounded-host-RAM contract is tested against.

    `window_rows` caps each run's contribution per window: the bound is
    lowered to the smallest buffered key at row `window_rows` of any
    run, so a window holds ~k x window_rows rows instead of everything
    below the natural bound (whole-file chunks otherwise degenerate to
    ONE window holding nearly the entire bucket, serializing the
    downstream merge pipeline behind a single giant sort).  The lowered
    bound is an existing key, so the key-window invariant — a key's
    rows never straddle windows — is unchanged; windows where the cap
    makes no progress (one key group wider than the cap) fall back to
    the natural bound."""
    runs = [_RunState(it, key_cols, key_encoder)
            for it in run_chunk_iters]
    for r in runs:
        r.fill_one()

    while True:
        for r in runs:
            if not r.exhausted and not r.buffer:
                r.fill_one()
        if stats is not None:
            buffered = sum(r.buffered_rows for r in runs)
            if buffered > stats.get("peak_buffered_rows", 0):
                stats["peak_buffered_rows"] = buffered
        non_exhausted = [r for r in runs if not r.exhausted]
        if not non_exhausted:
            tail = []
            for r in runs:
                tail.extend(r.take_all())
            if tail:
                yield tail
            return
        # the cut alone, not the fill above (the chunk source's time);
        # the span closes before the yield: a suspended generator holds
        # none
        with span("merge.cut", cat="merge"):
            bound = min(r.last_key() for r in non_exhausted)
            heads: List = []
            if window_rows:
                caps = [c for c in (r.key_at(window_rows) for r in runs)
                        if c is not None]
                if caps:
                    cap = min(caps)
                    if cap < bound:
                        for r in runs:      # run order = merge stability
                            heads.extend(r.cut_lt(cap))
                        # no rows below the cap: a single key group
                        # wider than it — fall back to the natural
                        # bound below so the stream advances
            if not heads:
                for r in runs:              # run order = merge stability
                    heads.extend(r.cut_lt(bound))
        if heads:
            yield heads
        else:
            # every buffered row >= bound: a key group spans entire
            # buffers; extend the runs sitting exactly at the bound
            progressed = False
            for r in non_exhausted:
                if r.last_key() == bound:
                    progressed |= r.fill_one()
                    if r.exhausted:
                        progressed = True
            if not progressed:              # defensive: cannot happen
                tail = []
                for r in runs:
                    tail.extend(r.take_all())
                if tail:
                    yield tail
                return


def merge_runs_streamed(
    run_chunk_iters: Sequence[Iterator],
    key_cols: Sequence[str],
    key_encoder: NormalizedKeyEncoder,
    emit: Callable[[pa.Table], None],
    merge_window: Callable[[List], pa.Table],
    pass_encoded: bool = False,
    window_rows: Optional[int] = None,
) -> None:
    """Stream-merge k runs (oldest first) and emit merged key windows in
    ascending key order.

    run_chunk_iters: one iterator of key-sorted KV chunks per run; each
    item is a pa.Table or a pre-encoded (table, lanes, truncated[,
    packed]) tuple.  merge_window: merges a window's run-ordered chunk
    list into the final rows (e.g. a merge_runs(...).take() or
    merge_runs_agg closure).  With pass_encoded=True it receives the
    (table, lanes, truncated, packed) tuples so the kernel can skip
    re-encoding (and re-packing) the window's keys."""
    for items in iter_merge_windows(run_chunk_iters, key_cols,
                                    key_encoder,
                                    window_rows=window_rows):
        emit(merge_window(items if pass_encoded
                          else [item[0] for item in items]))
