"""K-way sorted-run merge on device.

Replaces the reference's per-record loser tree
(mergetree/compact/SortMergeReaderWithLoserTree.java:34, LoserTree.java:45)
and merge functions with one data-parallel plan:

1. concatenate the k runs oldest-first (keeps input order for stable ties),
2. stable device sort by (validity, key lanes..., seq_hi, seq_lo)
   -- jax.lax.sort lexicographic keys; O(N log N) on the VPU but with
   ~10^3-way parallelism it beats a scalar tournament tree by orders of
   magnitude,
3. segmented winner selection: neighbor-equality mask over sorted lanes
   gives per-key segments; deduplicate keeps the last row of each segment
   (max sequence; stability resolves equal sequences by arrival order),
   first-row keeps the first,
4. return take-indices into the concatenated input; the host applies them
   to the Arrow table (variable-length values never touch the device).

Static shapes: inputs are padded to the next power of two; padding rows
carry validity=1 which sorts after all real rows and never joins a segment.
The operands are planes, one contiguous uint32[m] array each
(`_new_planes`).  `merge_runs` decides the route before it encodes
(`sorted_winners`), so a device merge of fixed-width keys writes each
operand once, straight from the Arrow chunks (`device_planes`); callers
that hold a lane matrix reach the planes through `_padded_operands`.

The router (`device_sorted_winners`) sends each merge one of two ways:
to the host (offset-value coded merge of sorted runs, C radix sort of a
packed key, or the general lexsort) or to the device, from one cost
model over the measured link (`_device_path_pays`).  The device returns
one of two formats: one packed word a row (perm | winner << 31) where
the caller promised `winners_only`, else the full perm / winner / prev
triple, with offset-value codes riding the sort where the input is
sorted runs.  One winner-select body (`segmented_merge_body`) serves
both, the mesh engines and the fused decode.  Two environment pins take
the decision away from the model: PAIMON_FORCE_HOST_SORT (the
benchmark's table build; the tests' reference) and
PAIMON_FORCE_DEVICE_SORT (tests of the device programs on the cpu
backend).
"""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager
from dataclasses import dataclass
from functools import lru_cache
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from paimon_tpu.metrics import (
    MERGE_DEVICE_INFLIGHT_SUM, MERGE_DEVICE_MS, MERGE_DEVICE_ROWS,
    MERGE_DEVICE_TRIPS, MERGE_GATHER_BYTES, MERGE_GATHER_MS, MERGE_HOST_MS,
    MERGE_PREP_MS, MERGE_PREP_PLANAR_ROWS, MERGE_RETURN_BYTES,
    MERGE_TIEBREAK_MS, MERGE_TIEBREAK_RESORTED_ROWS, MERGE_TIEBREAK_ROWS,
    MERGE_WINNERS_MS, global_registry,
)
from paimon_tpu.obs.trace import metrics_enabled, span
from paimon_tpu.ops.normkey import (
    LazyPackedLanes, NormalizedKeyEncoder, words64, write_int64_column,
    write_words,
)
from paimon_tpu.ops.ovc import (
    OVC_OFF_SENTINEL, ovc_sorted_winners, run_ovc_offsets,
)
from paimon_tpu.types import RowKind

__all__ = ["merge_runs", "MergeResult", "MergeOperands", "merge_operands",
           "device_sorted_winners", "sorted_winners", "route_to_host",
           "host_sorted_winners", "tiebreak_cut_keys",
           "take_link_reading", "user_seq_order_lanes", "SEQ_COL",
           "KIND_COL"]

SEQ_COL = "_SEQUENCE_NUMBER"
KIND_COL = "_VALUE_KIND"


@dataclass
class MergeResult:
    """Indices into the concatenated input table, in key order."""
    table: pa.Table          # concatenated input (runs oldest-first)
    indices: np.ndarray      # winners, sorted by key
    # per-winner previous-version indices (for changelog), -1 if none
    prev_indices: Optional[np.ndarray] = None

    def take(self, columns: Optional[List[str]] = None) -> pa.Table:
        t = self.table.select(columns) if columns else self.table
        return gather(t, self.indices)


def _pad_size(n: int) -> int:
    if n <= 1024:
        return 1024
    return 1 << (n - 1).bit_length()


def _gathered(rows: int, columns: int, take):
    """`merge.gather`: rows taken out of a merge's input in an order the
    merge chose — `take()`, whose result's bytes are counted (`merge` /
    `gather_bytes`); the span learns them at its end, which the ring
    records and the profiler's annotation cannot."""
    with span("merge.gather", cat="merge", group="merge",
              metric=MERGE_GATHER_MS, rows=rows, columns=columns) as sp:
        taken = take()
        sp.set(bytes=taken.nbytes)
        if metrics_enabled():
            global_registry().group("merge").counter(MERGE_GATHER_BYTES) \
                .inc(taken.nbytes)
        return taken


def gather(table: pa.Table, indices) -> pa.Table:
    """`merge.gather` of an Arrow table: its rows at `indices`, a numpy
    array of positions or an Arrow array of them, whose null entries give
    null rows.  The bytes counted are the buffers of the table taken."""
    if not isinstance(indices, pa.Array):
        indices = pa.array(indices)
    return _gathered(len(indices), table.num_columns,
                     lambda: table.take(indices))


def gather_columns(parts: Sequence[Tuple[pa.Table, np.ndarray]]
                   ) -> pa.Table:
    """`merge.gather` of tables laid side by side: each part's rows at
    its own positions (one length for all), its columns in turn, as one
    table — rows whose key columns and value columns live in different
    tables.  The bytes counted are the buffers of the table taken."""
    def take():
        cols = {}
        for table, indices in parts:
            taken = table.take(pa.array(indices))
            cols.update(zip(taken.column_names, taken.columns))
        return pa.table(cols)
    return _gathered(len(parts[0][1]),
                     sum(table.num_columns for table, _ in parts), take)


def gather_values(values: np.ndarray, indices: np.ndarray) -> np.ndarray:
    """`merge.gather` of one column held as a numpy array (a column's
    values or its validity): the view of it that a reduction over the
    merge's order reads, where no Arrow column is wanted."""
    return _gathered(len(indices), 1, lambda: values[indices])


def prep_span(rows: int, form: Optional[str] = None):
    """`merge.prep`: host work that readies a merge's operands —
    concat, key-lane encode, sequence split, pad to the program's
    size.  Shared by every caller of `device_sorted_winners`.  A span
    that writes the device's operands says in `form` how: `planes`
    (key and sequence words straight from the Arrow chunks) or `matrix`
    (an encoded lane matrix transposed into them)."""
    attrs = {} if form is None else {"form": form}
    return span("merge.prep", cat="merge", group="merge",
                metric=MERGE_PREP_MS, rows=rows, **attrs)


def _new_planes(num_lanes: int, n: int) -> List[np.ndarray]:
    """The device programs' operands for `n` rows of `num_lanes` lanes,
    still empty: num_lanes + 3 planes of uint32[m], m the program's
    size, each contiguous, so it uploads as it lies: the lanes, the
    sequence's high and low words, and the validity word that sorts
    padding last (zero in a real row, one in the pad).  An array each,
    not one block: a plane of a window under 8Mi rows is small enough
    for the allocator to hand back a block it has kept, where one block
    of them all would be mapped and faulted in afresh every merge."""
    planes = [np.zeros(_pad_size(n), dtype=np.uint32)
              for _ in range(num_lanes + 3)]
    planes[-1][n:] = 1
    return planes


def _write_lanes(planes: List[np.ndarray], row: int, lanes: np.ndarray
                 ) -> None:
    """A lane matrix uint32[n, L] transposed into planes row..row+L."""
    for j in range(lanes.shape[1]):
        planes[row + j][:len(lanes)] = lanes[:, j]


def _padded_operands(lanes, order_lanes: Optional[np.ndarray],
                     seq: np.ndarray) -> List[np.ndarray]:
    """The operand planes (`_new_planes`) of a caller that hands lane
    matrices — key lanes, then any user order lanes — and the sequence
    as an array: one transposing copy, under `merge.prep`."""
    n = len(seq)
    with prep_span(n, form="matrix"):
        parts = [lanes] if _no_user_order(order_lanes) \
            else [lanes, order_lanes]
        planes = _new_planes(sum(p.shape[1] for p in parts), n)
        row = 0
        for part in parts:
            if isinstance(part, LazyPackedLanes):
                # a packed u64 key: its words, without the [n, 2] matrix
                write_words(words64(part.packed), planes[row][:n],
                            planes[row + 1][:n])
            else:
                _write_lanes(planes, row, np.asarray(part))
            row += part.shape[1]
        write_words(words64(np.ascontiguousarray(seq, dtype=np.int64)),
                    planes[row][:n], planes[row + 1][:n])
    return planes


# round trips to the chip open now, process-wide: every `merge.device`
# and `agg.device` span, whichever thread opened it
_TRIPS_OPEN = 0
_TRIPS_LOCK = threading.Lock()


@contextmanager
def device_trip(name: str, merge_rows: Optional[int] = None, **span_args):
    """One round trip to the chip as the span `name`, counted among
    those open: `merge` / `device_trips` takes one and `device_inflight_sum`
    the number open as this one opens, itself included, which the span
    carries as `inflight`; their ratio is how many round trips shared the
    chip and its link.  `merge_rows`: the real rows of a merge's round
    trip, for `merge` / `device_rows`.  The count is taken before the
    span opens and given back after it closes, whatever it raised: the
    span times the calls as they are, and nothing waits for the device."""
    global _TRIPS_OPEN
    with _TRIPS_LOCK:
        _TRIPS_OPEN += 1
        inflight = _TRIPS_OPEN
    try:
        if metrics_enabled():
            group = global_registry().group("merge")
            group.counter(MERGE_DEVICE_TRIPS).inc()
            group.counter(MERGE_DEVICE_INFLIGHT_SUM).inc(inflight)
            if merge_rows is not None:
                group.counter(MERGE_DEVICE_ROWS).inc(merge_rows)
        with span(name, cat="merge", inflight=inflight, **span_args) as sp:
            yield sp
    finally:
        with _TRIPS_LOCK:
            _TRIPS_OPEN -= 1


def device_span(route: str, rows: int, padded_rows: int,
                h2d_bytes: int, d2h_bytes: int, **attrs):
    """`merge.device`: one round trip to the chip (or, route `mesh`, to
    the chips of a mesh step), from the first operand's upload to the
    host holding the result.  It times the calls as they are — dispatch
    is asynchronous, the download blocks — and adds none.  Bytes are
    the operands' and the results' sizes.  Counted as `device_trip`
    says, its `rows` in `merge` / `device_rows`."""
    return device_trip("merge.device", rows, group="merge",
                       metric=MERGE_DEVICE_MS, rows=rows,
                       padded_rows=padded_rows, h2d_bytes=h2d_bytes,
                       d2h_bytes=d2h_bytes, route=route, **attrs)


def _host_span(route: str, rows: int):
    """`merge.host`: a merge sorted on the host, as far as the sorted
    order; its winners are `merge.winners`."""
    return span("merge.host", cat="merge", group="merge",
                metric=MERGE_HOST_MS, rows=rows, route=route)


def winners_span(rows: int, route: str):
    """`merge.winners`: a merge's return side — the (perm, winner) words
    turned into row indices: the unpack of the device's packed words, the
    host routes' winner epilogue, `flatnonzero` and the index cast, the
    repair of truncated keys, the delete check.  `route`: `device`,
    `host`, `ovc`, `mesh`, `agg` (the segment ids of an aggregation) or
    `sort` (`sort_table`'s order, which has no winners).
    The winners' count is set at its end (ring only)."""
    return span("merge.winners", cat="merge", group="merge",
                metric=MERGE_WINNERS_MS, rows=rows, route=route)


def _eq_next(lane_list, invalid, ovc_off, perm):
    """bool[N]: sorted position i continues the same (validity, key
    lanes...) segment at i+1.  The validity guard keeps a real row whose
    key encodes like padding out of the padding segment.  With
    `ovc_off` (sorted-order offset-value-code offsets, else None) and
    `perm` (the sort permutation) a pair that is also run-consecutive resolves key
    equality from the next row's code alone (offset past the key lanes =
    same key); only the remaining pairs use the lane-compare chain
    (ops/ovc.run_ovc_offsets documents the code)."""
    lanes_mat = jnp.stack(lane_list)
    eq = jnp.all(lanes_mat[:, :-1] == lanes_mat[:, 1:], axis=0)
    if ovc_off is not None:
        consec = perm[1:] == perm[:-1] + 1
        known = ovc_off[1:] != jnp.uint32(OVC_OFF_SENTINEL)
        eq_code = ovc_off[1:] >= jnp.uint32(len(lane_list))
        eq = jnp.where(consec & known, eq_code, eq)
    eq = eq & (invalid[:-1] == invalid[1:])
    return jnp.concatenate([eq, jnp.array([False])])


def segmented_merge_body(lane_list, seq_hi, seq_lo, invalid, keep: str,
                         num_key_lanes: Optional[int] = None,
                         ovc_off=None):
    """Traceable kernel body shared by the single-chip path, the mesh
    paths (parallel/mesh_engine.py, sharded_merge.py, sharded_compact.py),
    the fused decode (ops/decode.py) and the driver entry.

    lane_list: list of uint32[N] arrays (most-significant lane first).
    The first `num_key_lanes` define SEGMENT identity; any further lanes
    are user-defined sequence order (reference
    utils/UserDefinedSeqComparator: rows within a key order by the
    sequence field first, internal sequence breaks ties).
    `ovc_off`: optional uint32[N] per-row offset-value-code offsets vs
    the run predecessor (ops/ovc.run_ovc_offsets) — rides the sort as a
    payload so the winner-select resolves run-consecutive neighbor
    pairs from the single-int code and only lane-compares the rest.
    Returns (perm, winner, prev_in_seg)."""
    num_lanes = len(lane_list)
    if num_key_lanes is None:
        num_key_lanes = num_lanes
    n = invalid.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    operands = [invalid] + list(lane_list) + [seq_hi, seq_lo, iota]
    if ovc_off is not None:
        operands.append(ovc_off)          # payload, not a sort key
    sorted_ops = jax.lax.sort(operands, num_keys=num_lanes + 3,
                              is_stable=True)
    s_invalid = sorted_ops[0]
    s_lanes = sorted_ops[1:1 + num_key_lanes]
    perm = sorted_ops[num_lanes + 3]
    s_off = sorted_ops[-1] if ovc_off is not None else None

    eq_next = _eq_next(s_lanes, s_invalid, s_off, perm)
    eq_prev = jnp.concatenate([jnp.array([False]), eq_next[:-1]])
    valid = s_invalid == 0
    if keep == "last":
        winner = (~eq_next) & valid
    else:  # "first"
        winner = (~eq_prev) & valid
    # previous version of each winner: its predecessor within the same
    # segment (highest-seq non-winner), for changelog derivation
    prev_in_seg = jnp.where(eq_prev, jnp.roll(perm, 1), -1)
    return perm, winner, prev_in_seg


@lru_cache(maxsize=64)
def _merge_fn(num_lanes: int, keep: str, num_key_lanes: int,
              with_ovc: bool = False):
    """Build the jitted merge kernel for a lane count: the full
    (perm, winner, prev) return."""

    if with_ovc:
        @jax.jit
        def fn_ovc(lanes, seq_hi, seq_lo, invalid, ovc_off):
            return segmented_merge_body(
                [lanes[i] for i in range(num_lanes)], seq_hi, seq_lo,
                invalid, keep, num_key_lanes=num_key_lanes,
                ovc_off=ovc_off)

        return fn_ovc

    @jax.jit
    def fn(lanes, seq_hi, seq_lo, invalid):
        return segmented_merge_body(
            [lanes[i] for i in range(num_lanes)], seq_hi, seq_lo, invalid,
            keep, num_key_lanes=num_key_lanes)

    return fn


@lru_cache(maxsize=64)
def _merge_fn_packed(num_lanes: int, keep: str, num_key_lanes: int):
    """Winners-only variant: ONE uint32[N] output, perm in the low 31
    bits and the winner flag in bit 31.  Callers that never read `prev`
    or intra-segment order pull 4 bytes/row off the device instead of
    13."""

    @jax.jit
    def fn(lanes, seq_hi, seq_lo, invalid):
        perm, winner, _ = segmented_merge_body(
            [lanes[i] for i in range(num_lanes)], seq_hi, seq_lo, invalid,
            keep, num_key_lanes=num_key_lanes)
        return perm.astype(jnp.uint32) | (
            winner.astype(jnp.uint32) << 31)

    return fn


# (host->device bytes/s, device->host bytes/s), measured once per
# process on the live accelerator link (first caller measures under
# _LINK_LOCK, everyone else waits for its reading): the merge path
# choice hinges on exactly this number
_LINK_BW: Optional[Tuple[float, float]] = None
_LINK_LOCK = threading.Lock()

# merges taken per path this process (observability: bench + metrics)
PATH_COUNTS = {"host": 0, "device": 0, "ovc": 0}

# inputs and outcome of the first _ROUTE_LOG_CAP routing decisions since
# the list was last cleared (observability, like PATH_COUNTS: the chip
# smoke prints the first decision of each of its steps)
ROUTE_LOG: List[dict] = []
_ROUTE_LOG_CAP = 64

# cost-model constants (rows/s).  NOT MEASURED ON THE CURRENT MACHINE:
# the values predate it and are kept until the router is re-measured on
# an attached chip (ROADMAP S2).  Device: sorted rows/s with data
# resident, derated for dispatch and padding; host packed-key path via
# numpy argsort vs the native C radix sort (derated for pipeline
# contention); the general lexsort.
_DEVICE_SORT_ROWS_PER_SEC = 50e6
_HOST_FAST_NUMPY_ROWS_PER_SEC = 1.5e6
_HOST_FAST_NATIVE_ROWS_PER_SEC = 10e6
_HOST_GENERAL_ROWS_PER_SEC = 0.7e6


def _host_fast_rate() -> float:
    # predict WITHOUT triggering the native build: forcing a gcc
    # compile inside the routing decision would stall first merges on
    # processes that always route to the device
    from paimon_tpu import native
    return (_HOST_FAST_NATIVE_ROWS_PER_SEC
            if native.predicted_available()
            else _HOST_FAST_NUMPY_ROWS_PER_SEC)


def _measure_link_bandwidth() -> Tuple[float, float]:
    """One reading per process, taken under a lock: the scan pipeline
    routes merges from up to eight workers at once, and concurrent
    first calls would each time a contended link and pin whichever
    reading landed last for the life of the process."""
    global _LINK_BW
    with _LINK_LOCK:
        if _LINK_BW is None:
            _LINK_BW = _time_link()
        return _LINK_BW


def take_link_reading() -> None:
    """Take the process's one link reading now, on the calling thread,
    if the router is going to want one (an accelerator backend, no pin).
    For a caller about to route merges from several threads at once:
    the first merge otherwise times the link while the other threads'
    decode and prep contend for the host, reads it several times too
    narrow (0.19–0.28 GB/s up where a quiet host reads 0.9–4.4; PERF.md
    §6, PR 31) and sends every merge of the process to the host."""
    if os.environ.get("PAIMON_FORCE_HOST_SORT") == "1" or \
            os.environ.get("PAIMON_FORCE_DEVICE_SORT") == "1":
        return
    if jax.default_backend() != "cpu":
        _measure_link_bandwidth()


def _time_link() -> Tuple[float, float]:
    import time as _time
    size = 8 << 20
    # one unmeasured warm-up round: the very first transfers absorb
    # buffer-pool/backend warm-up and would read far below the true
    # bandwidth, permanently misrouting merges to the host path
    warm = jax.device_put(np.zeros(size, np.uint8))
    warm.block_until_ready()
    np.asarray(warm)
    h2d_best = d2h_best = 0.0
    for _ in range(2):                         # best-of-2 measured
        buf = np.zeros(size, np.uint8)
        t0 = _time.perf_counter()
        d = jax.device_put(buf)
        d.block_until_ready()
        h2d_best = max(h2d_best,
                       size / max(_time.perf_counter() - t0, 1e-9))
        t0 = _time.perf_counter()
        np.asarray(d)
        d2h_best = max(d2h_best,
                       size / max(_time.perf_counter() - t0, 1e-9))
    return (h2d_best, d2h_best)


def _device_path_pays(n: int, num_lanes: int, winners_only: bool,
                      host_fast: bool, epilogue_h2d_bytes: int = 0,
                      d2h_bytes: Optional[int] = None) -> bool:
    """Cost model: offload the sort only when transfer+compute beats
    the host sort.  The accelerator wins on wide links; a narrow link
    loses on device->host alone and the merge stays host-side.  A merge
    with an epilogue on the device (ops/scan_agg.py) says what its
    value lanes add on the way up and what really comes back."""
    m = _pad_size(n)
    h2d, d2h = _measure_link_bandwidth()
    bytes_in = m * (4 * num_lanes + 12) + epilogue_h2d_bytes
    bytes_out = d2h_bytes if d2h_bytes is not None \
        else m * (4 if winners_only else 9)      # packed vs perm+win+prev
    t_dev = bytes_in / h2d + bytes_out / d2h + m / _DEVICE_SORT_ROWS_PER_SEC
    host_rate = _host_fast_rate() if host_fast \
        else _HOST_GENERAL_ROWS_PER_SEC
    return t_dev < n / host_rate


def _host_sorted_winners_fast(lanes: np.ndarray, seq: np.ndarray,
                              keep: str,
                              packed: Optional[np.ndarray] = None
                              ) -> Tuple[np.ndarray, np.ndarray,
                                         np.ndarray]:
    """Packed-key fast path for the hottest shape (exactly two key
    lanes — a fixed-width 64-bit key, so lanes are never
    prefix-truncated — and no changelog predecessor needed): ONE stable
    argsort on a u64 key instead of a 4-key lexsort, then the winner
    per segment via segmented max/min of (seq, arrival) with reduceat.
    Semantics identical to the full sort: winner = max seq (ties -> the
    later arrival) for keep=last, min seq (ties -> earlier arrival) for
    keep=first.  ~1.6x faster than the lexsort path at 8M rows.

    When the native C library is available the whole thing runs as one
    fused radix sort + segment scan (paimon_tpu/native/radix_sort.c):
    ~3.5x faster again than the numpy pipeline at 8M rows."""
    n = lanes.shape[0]
    # the encoder hands back its pre-packed u64 for single fixed-width
    # keys; repack from the lanes only when it couldn't
    if packed is not None:
        key = packed
    else:
        lanes = np.asarray(lanes)    # materialize if lazily concatenated
        key = (lanes[:, 0].astype(np.uint64) << np.uint64(32)) \
            | lanes[:, 1].astype(np.uint64)
    from paimon_tpu import native
    fused = native.merge_winners(key, seq, keep == "last")
    if fused is not None:
        perm, winner = fused
        return perm, winner, np.broadcast_to(np.int64(-1), n)
    perm = np.argsort(key, kind="stable").astype(np.int32)
    k_sorted = key[perm]
    starts_mask = np.empty(n, dtype=bool)
    starts_mask[0] = True
    starts_mask[1:] = k_sorted[1:] != k_sorted[:-1]
    seg_starts = np.flatnonzero(starts_mask)
    seg_id = np.cumsum(starts_mask) - 1
    seq_sorted = seq[perm]
    if keep == "last":
        best_seq = np.maximum.reduceat(seq_sorted, seg_starts)
        tie = seq_sorted == best_seq[seg_id]
        cand = np.where(tie, perm, -1)
        best_arrival = np.maximum.reduceat(cand, seg_starts)
    else:
        best_seq = np.minimum.reduceat(seq_sorted, seg_starts)
        tie = seq_sorted == best_seq[seg_id]
        cand = np.where(tie, perm, n)
        best_arrival = np.minimum.reduceat(cand, seg_starts)
    winner = tie & (perm == best_arrival[seg_id])
    # winners_only contract: prev is never read — O(1) placeholder
    prev = np.broadcast_to(np.int64(-1), n)
    return perm, winner, prev


def _winner_epilogue(perm: np.ndarray, eq_neighbors: np.ndarray,
                     keep: str) -> Tuple[np.ndarray, np.ndarray,
                                         np.ndarray]:
    """Shared tail of every sorted-winner host path: `eq_neighbors[i]`
    says sorted rows i and i+1 share a key.  Winner = segment end
    (keep=last) or start (keep=first); prev = in-segment predecessor."""
    eq_next = np.concatenate([eq_neighbors, [False]])
    eq_prev = np.concatenate([[False], eq_neighbors])
    winner = ~eq_next if keep == "last" else ~eq_prev
    prev = np.where(eq_prev, np.roll(perm, 1), -1)
    return perm, winner, prev


def _host_sorted_winners(lanes: np.ndarray, seq: np.ndarray, keep: str,
                         num_key_lanes: int,
                         need_prev: bool = True,
                         packed: Optional[np.ndarray] = None
                         ) -> Tuple[np.ndarray, ...]:
    """CPU-backend fallback with EXACTLY the kernel's semantics: when no
    accelerator is attached, np.lexsort beats a single-threaded XLA
    host sort ~2x and skips the device round-trip + power-of-two
    padding entirely.  Accelerator runs never take this path.

    Returns the fused fast path's (perm, winner, prev), or the sorted
    order and its neighbours' key equality (perm, eq) for the caller's
    `_winner_epilogue` (`_host_winners`)."""
    n, num_lanes = lanes.shape
    if num_lanes == 2 and num_key_lanes == 2 and not need_prev \
            and n > 0:
        return _host_sorted_winners_fast(lanes, seq, keep, packed=packed)
    if num_lanes == 2 and num_key_lanes == 2 and n > 0 \
            and packed is not None:
        # full-order variant of the packed fast path (agg/partial-update
        # need every row's position, not just winners): two STABLE C
        # radix passes — by seq, then by key — compose to the exact
        # (key, seq, arrival) order of the lexsort, ~3x faster
        from paimon_tpu import native
        if native.load() is not None and int(seq.min()) >= 0:
            useq = seq.astype(np.int64, copy=False).view(np.uint64)
            p1 = native.radix_argsort(useq)
            p2 = native.radix_argsort(
                np.ascontiguousarray(packed[p1])) \
                if p1 is not None else None
            if p2 is not None:
                perm = p1[p2].astype(np.int32, copy=False)
                k_sorted = packed[perm]
                return perm, k_sorted[1:] == k_sorted[:-1]
    lanes = np.asarray(lanes)        # materialize if lazily concatenated
    useq = seq.astype(np.int64, copy=False).view(np.uint64)
    keys = ((useq & np.uint64(0xFFFFFFFF)).astype(np.uint32),
            (useq >> np.uint64(32)).astype(np.uint32),
            *(lanes[:, i] for i in range(num_lanes - 1, -1, -1)))
    perm = np.lexsort(keys).astype(np.int32)
    s_lanes = lanes[:, :num_key_lanes][perm]
    return perm, np.all(s_lanes[:-1] == s_lanes[1:], axis=1)


def count_returned(nbytes: int) -> None:
    """`merge` / `return_bytes`: the bytes a merge handed back to its
    caller — the permutation and winner mask (the device's packed words
    or full triple, the host route's arrays), or, where an epilogue ran
    with the merge (ops/scan_agg.py), the partials alone."""
    if metrics_enabled():
        global_registry().group("merge").counter(MERGE_RETURN_BYTES) \
            .inc(int(nbytes))


def _no_user_order(order_lanes: Optional[np.ndarray]) -> bool:
    return _num_order_lanes(order_lanes) == 0


def _num_order_lanes(order_lanes: Optional[np.ndarray]) -> int:
    return 0 if order_lanes is None else order_lanes.shape[1]


def route_to_host(n: int, num_key_lanes: int, num_order_lanes: int,
                  winners_only: bool, epilogue_h2d_bytes: int = 0,
                  d2h_bytes: Optional[int] = None) -> bool:
    """The router's decision for one merge of `n` rows, logged in
    ROUTE_LOG: True = sort on the host.  The two pins first, then the
    cpu backend (always the host), then the cost model.  It reads the
    merge's shape alone, so it is taken before the encode, which then
    writes the form the route reads."""
    force_device = os.environ.get("PAIMON_FORCE_DEVICE_SORT") == "1"
    force_host = os.environ.get("PAIMON_FORCE_HOST_SORT") == "1"
    host_fast = num_key_lanes == 2 and winners_only \
        and num_order_lanes == 0
    nl_total = num_key_lanes + num_order_lanes
    use_host = force_host
    pinned = force_host or force_device
    if not pinned and n > 0:
        use_host = jax.default_backend() == "cpu" \
            or not _device_path_pays(n, nl_total, winners_only, host_fast,
                                     epilogue_h2d_bytes, d2h_bytes)
    if len(ROUTE_LOG) < _ROUTE_LOG_CAP:
        ROUTE_LOG.append({
            "rows": n, "lanes": nl_total, "winners_only": winners_only,
            "host_fast": host_fast, "pinned": pinned,
            "route": "host" if use_host else "device"})
    return use_host


def host_sorted_winners(lanes: np.ndarray, seq: np.ndarray, keep: str,
                        order_lanes: Optional[np.ndarray],
                        winners_only: bool,
                        packed: Optional[np.ndarray],
                        run_starts: Optional[np.ndarray]
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The host route of `device_sorted_winners`: the offset-value
    coded merge where the input is sorted runs, else the sort."""
    n, num_key_lanes = lanes.shape
    no_user_order = _no_user_order(order_lanes)
    if run_starts is not None and no_user_order and len(run_starts) > 1:
        # sorted-run inputs: offset-value coded merge replaces the
        # sort (single-int compares, segment boundaries for free)
        with _host_span("ovc", n):
            res = ovc_sorted_winners(lanes, seq, run_starts,
                                     num_key_lanes, packed=packed)
        if res is not None:
            PATH_COUNTS["ovc"] += 1
            return _host_winners(res, keep, n, "ovc")
    PATH_COUNTS["host"] += 1
    with _host_span("host", n):
        full = lanes if no_user_order \
            else np.concatenate([lanes, order_lanes], axis=1)
        res = _host_sorted_winners(full, seq, keep, num_key_lanes,
                                   need_prev=not winners_only,
                                   packed=packed if no_user_order
                                   else None)
    return _host_winners(res, keep, n, "host")


def _host_winners(res: Tuple[np.ndarray, ...], keep: str, n: int,
                  route: str) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """A host sort's result as (perm, winner, prev): the fused fast
    path's as it is, a sorted order with its neighbours' key equality
    through `_winner_epilogue`, after `merge.host` has closed."""
    if len(res) == 3:
        return res
    with winners_span(n, route):
        return _winner_epilogue(*res, keep)


def device_sorted_winners(lanes: np.ndarray, seq: np.ndarray,
                          keep: str = "last",
                          order_lanes: Optional[np.ndarray] = None,
                          winners_only: bool = False,
                          packed: Optional[np.ndarray] = None,
                          run_starts: Optional[np.ndarray] = None
                          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Sort one merge's rows and select each key's winner, on the host
    or on the device.

    lanes: uint32[N, L] (segment identity); seq: int64[N] (non-negative);
    order_lanes: optional uint32[N, O] user-defined sequence lanes that
    rank within a key BEFORE the internal sequence.
    `winners_only=True` promises the caller uses ONLY the winner rows
    (never full perm ordering within segments nor prev): the device
    returns one packed word a row, the host takes the packed-key fast
    path for fixed-width two-lane keys (`packed`: their u64 form).
    `run_starts`: optional int64[k+1] boundaries marking the input as k
    concatenated (key, seq)-SORTED runs — on the host the offset-value
    coded O(n log k) tree-of-losers merge (ops/ovc.py) replaces the full
    sort (it verifies the sort contract and falls back when violated);
    on the device the full return ships the codes to the winner-select.
    Returns (perm, winner_mask, prev_in_segment) as numpy arrays — of
    the power-of-two padded size on the device route, UNPADDED (length
    N, all rows valid) on the host route.  Callers must select via the
    winner mask / `perm < n`, never assume a padded length.

    Two routes.  On the cpu backend every merge stays on the host; on an
    accelerator the first call measures h2d/d2h bandwidth and each merge
    offloads only when the modeled transfer+sort time beats the host
    sort (_device_path_pays).  Two pins override the model:
    PAIMON_FORCE_HOST_SORT=1 (the benchmark builds its tables under it:
    the build is not under test and its flush sorts stay off the device;
    tests take the host route as their reference) and
    PAIMON_FORCE_DEVICE_SORT=1 (tests run the device programs on the cpu
    backend, for padding and validity).
    """
    n, num_key_lanes = lanes.shape
    if route_to_host(n, num_key_lanes, _num_order_lanes(order_lanes),
                     winners_only):
        res = host_sorted_winners(lanes, seq, keep, order_lanes,
                                  winners_only, packed, run_starts)
        count_returned(res[0].nbytes + res[1].nbytes)
        return res
    ovc = None
    if run_starts is not None and not winners_only:
        ovc = (lanes if _no_user_order(order_lanes) else np.concatenate(
            [np.asarray(lanes), order_lanes], axis=1), run_starts)
    return _device_winners(_padded_operands(lanes, order_lanes, seq), n,
                           num_key_lanes, keep, winners_only, ovc)


def _device_winners(planes: List[np.ndarray], n: int, num_key_lanes: int,
                    keep: str, winners_only: bool,
                    ovc: Optional[Tuple[np.ndarray, np.ndarray]] = None
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The device route: the operand planes (`_new_planes`) of `n` real
    rows up, the program, the result down.  `ovc`: the unpadded lane
    matrix and the bounds of the sorted runs it holds, for the full
    return's offset-value codes."""
    PATH_COUNTS["device"] += 1
    num_lanes, m = len(planes) - 3, len(planes[0])
    with_ovc = ovc is not None
    with device_span("packed" if winners_only
                     else "full_ovc" if with_ovc else "full", n, m,
                     4 * m * (num_lanes + 3 + with_ovc),
                     4 * m if winners_only else 9 * m):
        operands = [jnp.asarray(plane) for plane in planes]
        # sorted-run inputs ship their offset-value codes to the device:
        # the winner-select consumes the single-int offsets first and
        # only lane-compares pairs the codes cannot decide (full variant
        # only — the packed return already collapses keys to one u64).
        # The codes are computed here, after the lanes' upload was
        # started, as before the span was put around it.
        ovc_args = ()
        if with_ovc:
            off = np.full(m, OVC_OFF_SENTINEL, dtype=np.uint32)
            off[:n] = run_ovc_offsets(np.asarray(ovc[0]), ovc[1])
            ovc_args = (jnp.asarray(off),)
        # a kernel the compiler refuses raises here: there is no second,
        # quieter program to fall back to
        fn = _merge_fn_packed(num_lanes, keep, num_key_lanes) \
            if winners_only \
            else _merge_fn(num_lanes, keep, num_key_lanes, with_ovc)
        out = fn(tuple(operands[:num_lanes]), *operands[num_lanes:],
                 *ovc_args)
        if winners_only:
            # one 4-byte word/row off the device: perm | (winner << 31)
            packed = np.asarray(out)
        else:
            perm, winner, prev = (np.asarray(a) for a in out)
    count_returned(4 * m if winners_only else 9 * m)
    if winners_only:
        with winners_span(n, "device"):
            perm = (packed & np.uint32(0x7FFFFFFF)).astype(np.int32)
            winner = (packed >> np.uint32(31)).astype(bool)
        prev = np.broadcast_to(np.int64(-1), m)
    return perm, winner, prev


def _user_seq_encoder(schema: pa.Schema, seq_fields: Sequence[str]
                      ) -> NormalizedKeyEncoder:
    """The encoder of the user-defined sequence columns' order lanes."""
    for f in seq_fields:
        t = schema.field(f).type
        if pa.types.is_string(t) or pa.types.is_large_string(t) or \
                pa.types.is_binary(t) or pa.types.is_large_binary(t):
            raise ValueError(
                f"sequence.field {f!r} must be numeric/temporal; string "
                f"sequences would compare only by a fixed-width prefix")
    return NormalizedKeyEncoder(
        [schema.field(f).type for f in seq_fields],
        nullable=[True] * len(seq_fields))


def user_seq_order_lanes(table: pa.Table,
                         seq_fields: Sequence[str],
                         descending: bool = False) -> np.ndarray:
    """uint32[N, O] order lanes for user-defined sequence columns
    (reference utils/UserDefinedSeqComparator). Nulls rank FIRST — a row
    with a null sequence always loses to any non-null one (in either
    sort order).  `descending` implements
    sequence.field.sort-order=descending: the SMALLER user sequence
    wins, via bitwise inversion of the value lanes."""
    enc = _user_seq_encoder(table.schema, seq_fields)
    lanes, _ = enc.encode_table(table, seq_fields)
    pos = 0
    for nl in enc.lanes_per_col:
        # encoder presence lane sorts nulls last; sequences need the
        # opposite (null = smallest, so null always loses)
        lanes[:, pos] = 1 - lanes[:, pos]
        if descending:
            for p in range(pos + 1, pos + nl):
                lanes[:, p] = np.uint32(0xFFFFFFFF) - lanes[:, p]
        pos += nl
    return lanes


def sort_table(table: pa.Table, key_names: Sequence[str],
               key_encoder: Optional[NormalizedKeyEncoder] = None
               ) -> np.ndarray:
    """Full sort permutation by (key, seq) -- used to lay out write-buffer
    flushes when the merge engine defers merging to read time. Returns
    indices into `table` in sorted order (stable: arrival order for ties)."""
    n = table.num_rows
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    if key_encoder is None:
        key_encoder = NormalizedKeyEncoder(
            [table.schema.field(k).type for k in key_names],
            nullable=[table.schema.field(k).nullable for k in key_names])
    with prep_span(n):
        lanes, truncated = key_encoder.encode_table(table, key_names)
        seq = np.asarray(
            table.column(SEQ_COL).combine_chunks().cast(pa.int64()))
    perm, _, _ = device_sorted_winners(lanes, seq, "last")
    with winners_span(n, "sort"):
        if truncated.any():
            return tiebreak_cut_keys(table, key_names, key_encoder, lanes,
                                     truncated, perm, seq)[0]
        return perm[perm < n].astype(np.int64)


def _adjacent_equal(lanes: np.ndarray, order: np.ndarray, lo: int,
                    hi: int) -> np.ndarray:
    """bool[n-1]: rows order[i] and order[i+1] agree in lanes lo..hi-1
    (all true where the range is empty)."""
    eq = np.ones(max(len(order) - 1, 0), dtype=bool)
    for j in range(lo, hi):
        col = lanes[:, j][order]
        eq &= col[1:] == col[:-1]
    return eq


def _pairs_same_bytes(columns: Sequence[pa.ChunkedArray], a: np.ndarray,
                      b: np.ndarray) -> np.ndarray:
    """bool[k]: rows a[i] and b[i] hold the same bytes in each of the
    string / binary `columns` (a null equals a null alone) — one Arrow
    take of each side and one compare a column."""
    same = np.ones(len(a), dtype=bool)
    if not len(a):
        return same
    ia, ib = pa.array(a), pa.array(b)
    for col in columns:
        x, y = col.take(ia), col.take(ib)
        eq = pc.equal(x, y)
        if eq.null_count:
            eq = pc.or_(pc.fill_null(eq, False),
                        pc.and_(pc.is_null(x), pc.is_null(y)))
        same &= eq.to_numpy()
    return same


def tiebreak_cut_keys(table: pa.Table, key_names: Sequence[str],
                      key_encoder: NormalizedKeyEncoder, lanes, truncated,
                      perm: np.ndarray, seq: np.ndarray,
                      order_lanes: Optional[np.ndarray] = None
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """`merge.tiebreak`: a sort by key lanes turned into the exact order
    of keys whose lanes hold a cut prefix (the encoder's `truncated`).

    `perm` is the (lanes, order lanes, seq, arrival) order of a host or
    device route, padding included.  Rows that share the head lanes
    (`cut_head_lanes`, through the last string column) and of which one
    is cut are compared by their full bytes, one vectorised pass over
    the neighbour pairs.  A group of equal head lanes that holds one key
    is already exact; only groups that hold several are sorted again,
    by (group, full key, order lanes, seq, arrival) in one Arrow sort.
    Returns (order, same): int64[n] rows in exact order, and bool[n-1],
    True where sorted rows i and i+1 share their full key.  Counted in
    `merge` / `tiebreak_rows` (rows of the compared pairs) and
    `tiebreak_resorted_rows` (rows of the groups sorted again)."""
    n = len(seq)
    with span("merge.tiebreak", cat="merge", group="merge",
              metric=MERGE_TIEBREAK_MS, rows=n) as sp:
        order = perm[perm < n].astype(np.int64)
        lanes = np.asarray(lanes)
        head, num_lanes = key_encoder.cut_head_lanes, lanes.shape[1]
        eq_head = _adjacent_equal(lanes, order, 0, head)
        same = eq_head & _adjacent_equal(lanes, order, head, num_lanes)
        cut = truncated[order]
        pairs = np.flatnonzero(eq_head & (cut[1:] | cut[:-1]))
        byte_cols = [table.column(key_names[i])
                     for i in key_encoder.bytes_columns]
        differ = pairs[~_pairs_same_bytes(byte_cols, order[pairs],
                                          order[pairs + 1])]
        same[differ] = False
        compared = np.zeros(n, dtype=bool)
        compared[pairs] = compared[pairs + 1] = True
        resorted = 0
        if len(differ):
            group = np.zeros(n, dtype=np.int64)
            np.cumsum(~eq_head, out=group[1:])
            several = np.zeros(int(group[-1]) + 1, dtype=bool)
            several[group[differ]] = True
            rows = np.flatnonzero(several[group])
            resorted = len(rows)
            sub = order[rows]
            order[rows] = sub[_exact_sort(
                table, key_names, key_encoder, lanes, group[rows], sub,
                seq, order_lanes)]
            inner = rows[rows < n - 1]
            inner = inner[eq_head[inner]]
            a, b = order[inner], order[inner + 1]
            same[inner] = _pairs_same_bytes(byte_cols, a, b) & np.all(
                lanes[a, head:] == lanes[b, head:], axis=1)
        if metrics_enabled():
            group_m = global_registry().group("merge")
            group_m.counter(MERGE_TIEBREAK_ROWS).inc(
                int(np.count_nonzero(compared)))
            group_m.counter(MERGE_TIEBREAK_RESORTED_ROWS).inc(resorted)
        sp.set(compared=int(np.count_nonzero(compared)), resorted=resorted)
    return order, same


def _exact_sort(table: pa.Table, key_names: Sequence[str],
                key_encoder: NormalizedKeyEncoder, lanes: np.ndarray,
                group: np.ndarray, rows: np.ndarray, seq: np.ndarray,
                order_lanes: Optional[np.ndarray]) -> np.ndarray:
    """Positions of `rows` in (group, key, order lanes, seq, arrival)
    order, a key compared as the encoder orders it but with each string
    column's full bytes in place of its prefix lanes."""
    keys = {"group": group}
    bytes_cols = set(key_encoder.bytes_columns)
    lane = 0
    for i, (nl, nullable) in enumerate(zip(key_encoder.lanes_per_col,
                                           key_encoder.nullable)):
        if i in bytes_cols:
            if nullable:                # the presence lane orders a null
                keys[f"lane{lane}"] = lanes[rows, lane]
            col = table.column(key_names[i])
            if pa.types.is_string(col.type):
                col = col.cast(pa.binary())
            elif pa.types.is_large_string(col.type):
                col = col.cast(pa.large_binary())
            keys[f"key{i}"] = col.take(pa.array(rows))
        else:
            for j in range(lane, lane + nl):
                keys[f"lane{j}"] = lanes[rows, j]
        lane += nl
    for j in range(0 if order_lanes is None else order_lanes.shape[1]):
        keys[f"order{j}"] = np.asarray(order_lanes)[rows, j]
    keys["seq"] = seq[rows]
    keys["arrival"] = rows
    sort_keys = pa.table(keys)
    return pc.sort_indices(
        sort_keys, sort_keys=[(k, "ascending")
                              for k in sort_keys.column_names]).to_numpy()


class _LazyLanes:
    """Deferred np.concatenate of per-run lane matrices.  The packed-key
    host fast path sorts the pre-packed u64 and never reads the lane
    matrix; this defers (and usually skips) an 8N-byte copy per window.
    Exposes .shape; np.asarray(...) materializes with a one-shot cache."""

    def __init__(self, parts: List[np.ndarray]):
        self._parts = parts
        n = sum(p.shape[0] for p in parts)
        self.shape = (n, parts[0].shape[1] if parts else 0)
        self._mat: Optional[np.ndarray] = None

    def __array__(self, dtype=None, copy=None):
        if self._mat is None:
            self._mat = (np.concatenate(self._parts)
                         if len(self._parts) > 1 else self._parts[0])
        out = self._mat if dtype is None else self._mat.astype(dtype)
        if copy and out is self._mat:
            out = out.copy()         # honor the NumPy 2 copy request —
            # the cache (and parts[0]) stay owned by the streamed buffer
        return out


@dataclass
class MergeOperands:
    """One merge's operands.  `merge_operands` concatenates the runs
    (oldest first) and says what the router reads — the rows, the lane
    counts, whether any key was cut to a prefix; the route taken, the
    caller asks for the form it reads: `encode_host` fills the host
    form (lanes, packed key, sequence numbers, user order lanes),
    `device_planes` gives the device programs' operands.
    A key that can be cut and pre-encoded runs (`encoded=`) are in the
    host form from the start."""
    table: pa.Table
    keep: str = "last"
    key_names: Sequence[str] = ()
    key_encoder: Optional[NormalizedKeyEncoder] = None
    seq_fields: Optional[Sequence[str]] = None
    seq_desc: bool = False
    num_order_lanes: int = 0
    run_starts: Optional[np.ndarray] = None
    lanes: Optional[np.ndarray] = None
    truncated: Optional[np.ndarray] = None
    packed: Optional[np.ndarray] = None
    seq: Optional[np.ndarray] = None
    order_lanes: Optional[np.ndarray] = None
    route: Optional[str] = None     # `sorted_winners`' decision

    @property
    def n(self) -> int:
        return self.table.num_rows

    @property
    def num_key_lanes(self) -> int:
        return self.key_encoder.num_lanes

    @property
    def any_truncated(self) -> bool:
        return self.truncated is not None and bool(self.truncated.any())

    def _order_lanes(self) -> Optional[np.ndarray]:
        return user_seq_order_lanes(self.table, self.seq_fields,
                                    self.seq_desc) \
            if self.seq_fields else None

    def _encode_host(self, encoded=None) -> None:
        table, packed = self.table, None
        if encoded is not None:
            # caller already lane-encoded each run (streamed windows
            # encode once for the window cut — don't pay the encode
            # twice); items are (lanes, truncated[, packed-u64])
            truncated = (np.concatenate([e[1] for e in encoded])
                         if len(encoded) > 1
                         else np.asarray(encoded[0][1]))
            packs = [e[2] if len(e) > 2 else None for e in encoded]
            if all(p is not None for p in packs):
                packed = (np.concatenate(packs) if len(packs) > 1
                          else np.asarray(packs[0]))
            if packed is not None:
                # the packed-key host fast path never reads the lane
                # matrix: concatenating it up front would copy 8N bytes
                # per window for nothing, so defer until a path
                # actually wants it
                lane_parts = [e[0] for e in encoded]
                lanes = _LazyLanes(lane_parts)
            else:
                lanes = (np.concatenate([e[0] for e in encoded])
                         if len(encoded) > 1
                         else np.asarray(encoded[0][0]))
        else:
            lanes, truncated, packed = self.key_encoder.encode_table_ex(
                table, self.key_names)
        self.lanes, self.truncated, self.packed = lanes, truncated, packed
        self.seq = np.asarray(
            table.column(SEQ_COL).combine_chunks().cast(pa.int64()))
        self.order_lanes = self._order_lanes()

    def encode_host(self) -> None:
        """The host form, under `merge.prep`, unless it is there."""
        if self.lanes is None:
            with prep_span(self.n):
                self._encode_host()

    def device_planes(self) -> List[np.ndarray]:
        """The device programs' operands (`_new_planes`), under
        `merge.prep`.  From the host form where that is there, through
        one transposing copy (`form` matrix).  Else each is written
        once (`form` planes): the key's and the sequence's words go
        from the Arrow chunks' own buffers to their place in the planes
        and nothing the size of the merge exists in between; only user
        order lanes pass through their matrix."""
        if self.lanes is not None:
            return _padded_operands(self.lanes, self.order_lanes, self.seq)
        n, table = self.n, self.table
        with prep_span(n, form="planes"):
            num_key_lanes = self.num_key_lanes
            planes = _new_planes(num_key_lanes + self.num_order_lanes, n)
            self.key_encoder.encode_planes(
                [table.column(k) for k in self.key_names], planes)
            row = num_key_lanes
            if self.num_order_lanes:
                _write_lanes(planes, row, self._order_lanes())
                row += self.num_order_lanes
            seq = table.column(SEQ_COL)
            if not pa.types.is_int64(seq.type):
                seq = seq.cast(pa.int64())
            write_int64_column(seq, planes[row][:n], planes[row + 1][:n])
        if metrics_enabled():
            global_registry().group("merge") \
                .counter(MERGE_PREP_PLANAR_ROWS).inc(n)
        return planes


def merge_operands(runs: Sequence[pa.Table], key_names: Sequence[str],
                   merge_engine: str = "deduplicate",
                   key_encoder: Optional[NormalizedKeyEncoder] = None,
                   seq_fields: Optional[Sequence[str]] = None,
                   seq_desc: bool = False,
                   encoded: Optional[Sequence[Tuple[np.ndarray,
                                                    np.ndarray]]] = None
                   ) -> MergeOperands:
    """The concat of one merge's runs and what its router reads: the
    host work ahead of the route decision, shared by `merge_runs` and
    the scan's pushed aggregate (ops/scan_agg.py).  The encode waits
    for the decision where nothing needs it sooner (`MergeOperands`)."""
    if not runs:
        raise ValueError("No runs to merge")
    keep = "first" if merge_engine == "first-row" else "last"
    with prep_span(sum(r.num_rows for r in runs)):
        table = pa.concat_tables(runs, promote_options="none")
        if table.num_rows == 0:
            return MergeOperands(table, keep)
        if key_encoder is None:
            key_encoder = NormalizedKeyEncoder(
                [table.schema.field(k).type for k in key_names],
                nullable=[table.schema.field(k).nullable
                          for k in key_names])
        if seq_fields and keep == "first":
            # reference forbids the combo: "first by user sequence" would
            # let later commits replace the retained first row
            raise ValueError(
                "sequence.field cannot be used with merge-engine first-row")
        # sorted-run boundaries for the OVC merge path: every input run
        # (or pre-cut window chunk — chunks of one run arrive in run
        # order, so treating each as its own run preserves arrival
        # order) is individually (key, seq)-sorted by the write/compact
        # invariants; the OVC path re-verifies and falls back if a
        # caller violates that.  Not where a user sequence orders a key.
        run_lens = [e[0].shape[0] for e in encoded] \
            if encoded is not None else [r.num_rows for r in runs]
        op = MergeOperands(
            table, keep, key_names, key_encoder, seq_fields, seq_desc,
            _user_seq_encoder(table.schema, seq_fields).num_lanes
            if seq_fields else 0,
            None if seq_fields else np.concatenate(
                [[0], np.cumsum(run_lens)]).astype(np.int64))
        if encoded is not None or not key_encoder.fixed_width:
            op._encode_host(encoded)
    return op


def sorted_winners(op: MergeOperands, winners_only: bool
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """`device_sorted_winners` of a merge's operands, the route decided
    ahead of the encode: one decision, then the one form it reads."""
    n = op.n
    to_host = route_to_host(n, op.num_key_lanes, op.num_order_lanes,
                            winners_only)
    op.route = "host" if to_host else "device"
    if to_host:
        op.encode_host()
        res = host_sorted_winners(op.lanes, op.seq, op.keep,
                                  op.order_lanes, winners_only, op.packed,
                                  op.run_starts)
        count_returned(res[0].nbytes + res[1].nbytes)
        return res
    ovc = None
    if not winners_only and op.run_starts is not None:
        # the full return's offset-value codes read the lane matrix
        op.encode_host()
        ovc = (op.lanes, op.run_starts)
    return _device_winners(op.device_planes(), n, op.num_key_lanes,
                           op.keep, winners_only, ovc)


def merge_runs(runs: Sequence[pa.Table], key_names: Sequence[str],
               merge_engine: str = "deduplicate",
               drop_deletes: bool = True,
               key_encoder: Optional[NormalizedKeyEncoder] = None,
               with_prev: bool = False,
               seq_fields: Optional[Sequence[str]] = None,
               seq_desc: bool = False,
               encoded: Optional[Sequence[Tuple[np.ndarray, np.ndarray]]]
               = None) -> MergeResult:
    """Merge k sorted runs (oldest first) into the latest row per key.

    Equivalent reference path: MergeTreeReaders.readerForMergeTree
    (mergetree/MergeTreeReaders.java:44) + DeduplicateMergeFunction /
    FirstRowMergeFunction + DropDeleteReader.
    """
    op = merge_operands(runs, key_names, merge_engine, key_encoder,
                        seq_fields, seq_desc, encoded)
    table, keep = op.table, op.keep
    n = table.num_rows
    if n == 0:
        return MergeResult(table, np.zeros(0, dtype=np.int64))
    # without changelog derivation the caller consumes only winner
    # rows, so the packed-key fast path is admissible — unless a key's
    # lanes were cut: the tie-break reads the whole sorted order
    truncated = op.any_truncated
    perm, winner, prev = sorted_winners(
        op, winners_only=not with_prev and not truncated)

    with winners_span(n, op.route) as sp:
        if truncated:
            perm, winner, prev = _winner_epilogue(*tiebreak_cut_keys(
                table, key_names, op.key_encoder, op.lanes, op.truncated,
                perm, op.seq, op.order_lanes), keep)
        win_pos = np.flatnonzero(winner)
        indices = perm[win_pos].astype(np.int64)
        prev_idx = prev[win_pos].astype(np.int64) if with_prev else None

        if drop_deletes and KIND_COL in table.column_names:
            # cheap min/max scan beats materializing the kinds array when
            # the batch is uniformly +I or uniformly +U (the common
            # compaction window has only +I): RowKind is +I=0 < -U=1 <
            # +U=2 < -D=3, and only lo==hi in {0,2} proves no -U/-D hides
            # in between
            import pyarrow.compute as pc
            mm = pc.min_max(table.column(KIND_COL))
            lo, hi = mm["min"].as_py(), mm["max"].as_py()
            if not (lo == hi and lo in (RowKind.INSERT,
                                        RowKind.UPDATE_AFTER)):
                kinds = np.asarray(table.column(KIND_COL).combine_chunks()
                                   .cast(pa.int8()))
                keep_mask = (kinds[indices] == RowKind.INSERT) | \
                            (kinds[indices] == RowKind.UPDATE_AFTER)
                indices = indices[keep_mask]
                if prev_idx is not None:
                    prev_idx = prev_idx[keep_mask]
        sp.set(winners=len(indices))

    return MergeResult(table, indices, prev_idx)

