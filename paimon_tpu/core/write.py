"""Write path: buffered per-(partition,bucket) writers producing L0 files.

reference call stack (SURVEY §3.1): TableWriteImpl.write ->
AbstractFileStoreWrite.write (operation/AbstractFileStoreWrite.java:186)
-> MergeTreeWriter.write/flushMemory (mergetree/MergeTreeWriter.java:164,
203) -> sort + merge-dedup -> KeyValueFileWriterFactory rolling write.

TPU deviation: instead of a binary sort buffer with normalized-key
insertion (SortBufferWriteBuffer.java:59), rows accumulate as Arrow
batches; at flush the whole buffer is sorted/deduped by the device kernel
in one shot and written columnar.
"""

from __future__ import annotations

import contextlib
import os
from collections import deque
from dataclasses import dataclass, field as dc_field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

from paimon_tpu.core.bucket import FixedBucketAssigner
from paimon_tpu.core.kv_file import KEY_PREFIX, KeyValueFileWriter
from paimon_tpu.fs import FileIO
from paimon_tpu.manifest import DataFileMeta, SimpleStats
from paimon_tpu.options import CoreOptions, MergeEngine
from paimon_tpu.ops.merge import (
    KIND_COL, SEQ_COL, gather_columns, merge_runs, sort_table,
)
from paimon_tpu.schema.table_schema import TableSchema
from paimon_tpu.types import RowKind
from paimon_tpu.utils.deadline import wait_future
from paimon_tpu.utils.path_factory import FileStorePathFactory

__all__ = ["CommitMessage", "KeyValueFileStoreWrite", "build_kv_table"]

ROW_KIND_COL = "_ROW_KIND"


@dataclass
class CommitMessage:
    """reference: table/sink/CommitMessageImpl.java."""
    partition: Tuple
    bucket: int
    total_buckets: int
    new_files: List[DataFileMeta] = dc_field(default_factory=list)
    compact_before: List[DataFileMeta] = dc_field(default_factory=list)
    compact_after: List[DataFileMeta] = dc_field(default_factory=list)
    changelog_files: List[DataFileMeta] = dc_field(default_factory=list)
    compact_changelog: List[DataFileMeta] = dc_field(default_factory=list)
    # dynamic-bucket hash index updates (reference indexIncrement)
    index_entries: List = dc_field(default_factory=list)

    def is_empty(self) -> bool:
        return not (self.new_files or self.compact_before
                    or self.compact_after or self.changelog_files
                    or self.compact_changelog or self.index_entries)


# a combined (bucket, partition...) group code stays below this, so
# every step of building it stays inside an int64
_MAX_GROUP_CODE = 1 << 62


def group_by_partition_bucket(table: pa.Table, buckets: np.ndarray,
                              partition_keys: Sequence[str]):
    """Split rows into (partition_tuple, bucket) groups.
    Returns [((part, bucket), row_indices)] — shared by the pk and
    append write paths (reference RowKeyExtractor + ChannelComputer).
    Groups ascend by bucket, then by each partition key's order of
    first appearance; a group's indices ascend.  A batch that is one
    group gets `arange(N)`: its rows are the batch, in its order."""
    n = len(buckets)
    if n == 0:
        return []
    lo, hi = int(buckets.min()), int(buckets.max())
    # one integer code a row: the bucket, then each partition key's
    # dictionary index, most significant first
    codes = buckets if lo == 0 else buckets.astype(np.int64) - lo
    card = hi - lo + 1
    parts = []
    for pk in partition_keys:
        enc = table.column(pk).combine_chunks().dictionary_encode()
        if enc.indices.null_count:
            raise ValueError(f"partition key {pk!r} holds a null")
        indices = np.asarray(enc.indices)
        parts.append((enc.dictionary, indices))
        if card * len(enc.dictionary) > _MAX_GROUP_CODE:
            codes = np.unique(codes, return_inverse=True)[1]
            card = int(codes.max()) + 1
        codes = codes.astype(np.int64, copy=False) * len(enc.dictionary) \
            + indices
        card *= len(enc.dictionary)
    if card == 1 or (parts and codes.min() == codes.max()):
        order, starts = np.arange(n), [0, n]
    else:
        # the narrowest unsigned type that holds the codes: numpy's
        # stable argsort of 8- and 16-bit integers is a radix sort
        codes = codes.astype(np.min_scalar_type(card - 1), copy=False)
        order = np.argsort(codes, kind="stable")
        ranked = codes[order]
        starts = [0, *(np.flatnonzero(ranked[1:] != ranked[:-1]) + 1), n]
    out = []
    for start, end in zip(starts[:-1], starts[1:]):
        first = order[start]
        part = tuple(dictionary[int(indices[first])].as_py()
                     for dictionary, indices in parts)
        out.append(((part, int(buckets[first])), order[start:end]))
    return out


def _offset_width(t: pa.DataType) -> int:
    """Bytes of a string or binary type's offset; 0 for any other."""
    if pa.types.is_string(t) or pa.types.is_binary(t):
        return 4
    if pa.types.is_large_string(t) or pa.types.is_large_binary(t):
        return 8
    return 0


def _add_value_ends(ends: np.ndarray, col: pa.ChunkedArray) -> None:
    """Add a string or binary column's bytes before each row to `ends`
    (int64[n + 1]; only its differences are read): the offsets where
    no null holds bytes, else the running sum of the non-null values'
    lengths (a take copies no null's bytes)."""
    dtype = np.int64 if _offset_width(col.type) == 8 else np.int32
    chunks = [chunk for chunk in col.chunks if len(chunk)]
    row, base = 0, 0
    for chunk in chunks:
        n = len(chunk)
        offsets = np.frombuffer(chunk.buffers()[1], dtype=dtype)[
            chunk.offset:chunk.offset + n + 1]
        local = None
        if chunk.null_count:
            lengths = np.diff(offsets)
            bit = chunk.offset % 8
            valid = np.unpackbits(
                np.frombuffer(chunk.buffers()[0], dtype=np.uint8)[
                    chunk.offset // 8:], count=bit + n,
                bitorder="little")[bit:].astype(dtype)
            if int(np.dot(lengths, valid)) != \
                    int(offsets[-1]) - int(offsets[0]):
                local = np.cumsum(lengths * valid, dtype=np.int64)
        if local is None:
            if len(chunks) == 1:
                np.add(ends, offsets, out=ends)
                return
            local = offsets[1:].astype(np.int64) - int(offsets[0])
        seg = ends[row + 1:row + n + 1]
        seg += local
        seg += base
        base += int(local[-1])
        row += n


def taken_nbytes(table: pa.Table,
                 selections: Sequence[np.ndarray]) -> Optional[List[int]]:
    """`table.take(idx).nbytes` of each of `selections`, read off the
    columns' validity, widths and offsets without the take.  A take
    writes a validity bitmap where the column holds a null, and always
    for a string or binary column; a fixed-width value's width a row; a
    string or binary value's offset and its bytes (a null's none).
    None where a column is of another type."""
    rows = np.array([len(idx) for idx in selections], dtype=np.int64)
    bitmap = (rows + 7) // 8
    total = np.zeros(len(selections), dtype=np.int64)
    ends = None
    for col in table.columns:
        t = col.type
        if _offset_width(t):
            total += bitmap + rows * _offset_width(t)
            if ends is None:
                ends = np.zeros(table.num_rows + 1, dtype=np.int64)
            _add_value_ends(ends, col)
        elif (pa.types.is_boolean(t) or pa.types.is_integer(t)
              or pa.types.is_floating(t) or pa.types.is_decimal(t)
              or pa.types.is_timestamp(t) or pa.types.is_date(t)
              or pa.types.is_time(t) or pa.types.is_duration(t)
              or pa.types.is_fixed_size_binary(t)):
            total += (rows * t.bit_width + 7) // 8
            if col.null_count:
                total += bitmap
        else:
            return None
    if ends is not None:
        row_bytes = np.diff(ends)
        total += [int(row_bytes[idx].sum()) for idx in selections]
    return total.tolist()


def _kv_head(keys: pa.Table, schema: TableSchema, seq: np.ndarray,
             kinds: np.ndarray) -> pa.Table:
    """The KV layout's leading columns: _KEY_<pk...>, _SEQUENCE_NUMBER,
    _VALUE_KIND; `keys` holds the trimmed primary key columns."""
    cols = {KEY_PREFIX + k: keys.column(k)
            for k in schema.trimmed_primary_keys()}
    cols[SEQ_COL] = pa.array(seq, pa.int64())
    cols[KIND_COL] = pa.array(kinds, pa.int8())
    return pa.table(cols)


def _joined(*tables: pa.Table) -> pa.Table:
    """The tables' columns side by side, in turn, as one table."""
    cols = {}
    for t in tables:
        cols.update(zip(t.column_names, t.columns))
    return pa.table(cols)


def _build_span(rows: int):
    from paimon_tpu.metrics import WRITE_BUILD_MS
    from paimon_tpu.obs.trace import span
    return span("write.build", cat="write", group="write",
                metric=WRITE_BUILD_MS, rows=rows)


def build_kv_table(raw: pa.Table, schema: TableSchema,
                   seq: np.ndarray, kinds: np.ndarray) -> pa.Table:
    """Flatten rows into the KV file layout:
    _KEY_<pk...>, _SEQUENCE_NUMBER, _VALUE_KIND, <all value columns>.
    A leaf span, `write.build`: callers open none around it alone."""
    with _build_span(raw.num_rows):
        return _joined(_kv_head(raw, schema, seq, kinds),
                       raw.select([f.name for f in schema.fields]))


def _take_span(rows: int):
    """`write.take`: rows taken on the route's side of the write — the
    key columns and kinds of a batch of several groups, or a selection
    taken whole before its flush."""
    from paimon_tpu.metrics import WRITE_TAKE_MS
    from paimon_tpu.obs.trace import span
    return span("write.take", cat="write", group="write",
                metric=WRITE_TAKE_MS, rows=rows)


class _Rows:
    """One batch's rows in a bucket's buffer.  Whole (`idx` None): the
    rows are `table`, as the caller handed them on.  A selection: the
    route took the key columns (`keys`) at `idx`, and the value columns
    stay in the caller's `table` until the flush gathers them by the
    composed index, or the writer takes them early (`_materialise`).
    `nbytes` is what `table.take(idx)` holds; `pin` is the writer's
    record of `table` while the selection is buffered."""
    __slots__ = ("table", "keys", "idx", "nbytes", "pin")

    def __init__(self, table: pa.Table, keys: Optional[pa.Table] = None,
                 idx: Optional[np.ndarray] = None,
                 nbytes: Optional[int] = None):
        self.table, self.keys, self.idx = table, keys, idx
        self.nbytes = table.nbytes if nbytes is None else nbytes
        self.pin = None

    @property
    def num_rows(self) -> int:
        return self.table.num_rows if self.idx is None else len(self.idx)


class _Pin:
    """A caller's batch that buffered selections read: its bytes, and
    those selections (by id, in the order they were routed)."""
    __slots__ = ("table", "nbytes", "rows")

    def __init__(self, table: pa.Table):
        self.table, self.nbytes, self.rows = table, table.nbytes, {}


@dataclass
class _Payload:
    """A bucket's buffer detached for one flush: each batch's
    (table, keys, idx) as `_Rows` holds them, with the kinds and the
    sequence numbers, and the bytes the buffer accounted."""
    parts: List[Tuple[pa.Table, Optional[pa.Table], Optional[np.ndarray]]]
    kinds: np.ndarray
    seq: np.ndarray
    nbytes: int

    @property
    def num_rows(self) -> int:
        return len(self.kinds)

    def keys(self, key_names: Sequence[str]) -> pa.Table:
        """The key columns, in arrival order (zero-copy)."""
        return pa.concat_tables(
            [t.select(key_names) if idx is None else keys
             for t, keys, idx in self.parts], promote_options="none")

    def arrival(self, fields: Sequence[str]) -> pa.Table:
        """The value columns in arrival order: a whole batch as it is,
        a selection taken once, as the route once took it."""
        return pa.concat_tables(
            [t.select(fields) if idx is None
             else t.select(fields).take(pa.array(idx))
             for t, _, idx in self.parts], promote_options="none")

    def sources(self, fields: Sequence[str]
                ) -> Tuple[pa.Table, Optional[np.ndarray], int]:
        """(values, index, deferred): the value columns of the distinct
        batches, each once, in one zero-copy table; the position there
        of each buffered row, None where that is the row's own; and
        the rows that are selections."""
        start, tables, rows = {}, [], 0
        for t, _, _ in self.parts:
            if id(t) not in start:
                start[id(t)] = rows
                tables.append(t.select(fields))
                rows += t.num_rows
        values = pa.concat_tables(tables, promote_options="none")
        deferred = sum(len(idx) for _, _, idx in self.parts
                       if idx is not None)
        if rows == self.num_rows and not deferred:
            return values, None, 0
        return values, np.concatenate(
            [start[id(t)] + (np.arange(t.num_rows) if idx is None else idx)
             for t, _, idx in self.parts]), deferred


def _buffer_span(rows: int):
    """`write.buffer`: the caller thread's own work on a bucket's
    buffer — a batch appended with its reserved sequence numbers, the
    buffer detached as one flush payload (the kinds and the sequence
    numbers concatenated)."""
    from paimon_tpu.obs.trace import span
    return span("write.buffer", cat="write", rows=rows)


class _BucketWriter:
    """One (partition, bucket)'s buffered state.

    Concurrency contract (parallel/write_pipeline.py): `write`,
    `_spill` and the flush *scheduling* run on the caller thread —
    sequence ranges are reserved at write() time, single-threaded, so
    pipelined flushes can never duplicate or reorder them.  The
    sort/encode/upload bodies run as FlushPool tasks; tasks for this
    bucket execute strictly in submission order (per-key actor), so
    `new_files`/`changelog_files`/`spills` are only ever touched by one
    task at a time and publish deterministically."""

    def __init__(self, parent: "KeyValueFileStoreWrite", partition: Tuple,
                 bucket: int):
        self.parent = parent
        self.partition = partition
        self.bucket = bucket
        self.buffers: List[pa.Table] = []
        self.kind_buffers: List[np.ndarray] = []
        self.seq_buffers: List[np.ndarray] = []   # reserved at write()
        self.buffered_bytes = 0
        self.next_seq: Optional[int] = None   # lazily restored
        self.new_files: List[DataFileMeta] = []
        self.changelog_files: List[DataFileMeta] = []
        self.spills: List[str] = []           # key-sorted local runs
        self._spill_dir: Optional[str] = None
        self._spill_bytes = 0                 # on-disk spill footprint
        self._spills_scheduled = 0            # caller-side (see _spill)
        self._spill_seq = 0                   # monotonic name counter:
        # names derived from len(spills)/listdir counts can REPEAT
        # after a fold shrinks both, truncating a live run (actor-
        # serialized, so a plain int is safe)
        self._spill_sched_bytes = 0           # scheduled-not-yet-written
        # spill payload bytes: the disk-budget check must see queued
        # spills too, or async workers let /tmp overshoot the cap
        # changelog-producer=lookup: the runs above level 0, kept across
        # commits (lookup/levels_index.py), built on first use
        self.lookup_index = None

    @property
    def _key(self) -> Tuple:
        return (self.partition, self.bucket)

    def pending_bytes(self) -> int:
        """Flush-cost estimate for LPT scheduling (buffered + spilled)."""
        return self.buffered_bytes + self._spill_bytes

    def write(self, rows: _Rows, kinds: np.ndarray):
        if self.parent.delta_listener is not None and rows.idx is not None:
            # the listener reads the rows themselves
            self.parent._materialise(rows)
        with _buffer_span(rows.num_rows):
            self.buffers.append(rows)
            self.kind_buffers.append(kinds)
            # sequence numbers are reserved HERE, on the single-threaded
            # caller, never inside a pooled flush task
            seqs = self._assign_seq(rows.num_rows)
            self.seq_buffers.append(seqs)
        if self.parent.delta_listener is not None:
            # serving-plane hot delta tier (service/delta.py): the
            # batch becomes point-lookup-visible the moment it is
            # buffered — AFTER sequence reservation, so delta
            # newest-wins order is exactly flush order
            self.parent.delta_listener(self.partition, self.bucket,
                                       rows.table, kinds, seqs)
        self.buffered_bytes += rows.nbytes
        opts = self.parent.options
        if self.parent.spillable:
            # sorted runs spill at sort-spill-buffer-size cadence,
            # bounded overall by write-buffer-size
            threshold = min(opts.write_buffer_size,
                            opts.get(CoreOptions.SORT_SPILL_BUFFER_SIZE))
            if self.buffered_bytes >= threshold:
                # queued-but-unwritten spill payloads count toward the
                # disk budget (their on-disk size is at most the in-RAM
                # estimate), else async workers let /tmp overshoot it
                if self._spill_bytes + self._spill_sched_bytes >= \
                        opts.get(
                            CoreOptions.WRITE_BUFFER_SPILL_MAX_DISK_SIZE):
                    # disk budget exhausted: flush to L0 instead of
                    # spilling further (reference MaxDiskSize cap)
                    self.flush()
                else:
                    self._spill()
        elif self.buffered_bytes >= opts.write_buffer_size:
            self.flush()

    def _restore_seq(self) -> int:
        if self.next_seq is None:
            if not self.parent.options.get(
                    CoreOptions.KV_SEQUENCE_NUMBER_ENABLED):
                # key-value.sequence_number.enabled=false: no per-record
                # sequence restore — all rows carry seq 0 and merge
                # order falls back to run (commit) order
                self.next_seq = 0
                return 0
            self.next_seq = self.parent.restore_max_seq(
                self.partition, self.bucket) + 1
        return self.next_seq

    def _assign_seq(self, n: int) -> np.ndarray:
        start = self._restore_seq()
        if not self.parent.options.get(
                CoreOptions.KV_SEQUENCE_NUMBER_ENABLED):
            return np.zeros(n, dtype=np.int64)
        self.next_seq = start + n
        return np.arange(start, start + n, dtype=np.int64)

    def _snapshot(self) -> Optional[_Payload]:
        """Detach the in-RAM buffer into an immutable flush payload
        (caller thread), or None.  Nothing is copied: the flush task
        that receives it composes the rows (`_Payload`), sorts and
        encodes them; the selections leave the writer's pins here."""
        if not self.buffers:
            return None
        with _buffer_span(sum(len(k) for k in self.kind_buffers)):
            for rows in self.buffers:
                if rows.pin is not None:
                    self.parent._unpin(rows)
            snap = _Payload([(r.table, r.keys, r.idx) for r in self.buffers],
                            np.concatenate(self.kind_buffers),
                            np.concatenate(self.seq_buffers),
                            self.buffered_bytes)
            self.buffers, self.kind_buffers, self.seq_buffers = [], [], []
            self.buffered_bytes = 0
        return snap

    def _sorted_chunk(self, snap: Optional[_Payload]
                      ) -> Tuple[Optional[pa.Table], List[DataFileMeta]]:
        """Sort/merge one flush payload into a key-sorted KV chunk and
        write its changelog-producer=input file (arrival order).
        The sort reads the key columns, the sequence numbers and the
        kinds; then one gather takes those by the sort's order and each
        value column from the caller's batches by the composed index,
        every value byte copied once.
        Worker-side and retry-safe: nothing on `self` is mutated —
        returns (sorted_kv, changelog_metas) for the caller to publish
        after the whole task succeeded."""
        if snap is None:
            return None, []
        schema = self.parent.schema
        key_names = schema.trimmed_primary_keys()
        fields = [f.name for f in schema.fields]
        from paimon_tpu.metrics import (
            WRITE_DEFERRED_GATHER_ROWS, WRITE_SORT_MS, global_registry,
        )
        from paimon_tpu.obs.trace import metrics_enabled, span
        with span("write.sort", cat="write", group="write",
                  metric=WRITE_SORT_MS, partition=self.partition,
                  bucket=self.bucket, rows=snap.num_rows):
            with _build_span(snap.num_rows):
                head = _kv_head(snap.keys(key_names), schema, snap.seq,
                                snap.kinds)
                values, index, deferred = snap.sources(fields)
            key_cols = [KEY_PREFIX + k for k in key_names]
            engine = self.parent.options.merge_engine
            if engine in (MergeEngine.DEDUPLICATE, MergeEngine.FIRST_ROW):
                seq_fields = self.parent.options.sequence_field or None
                sort_in = head
                if seq_fields:
                    ordering = values.select(seq_fields)
                    sort_in = _joined(head, ordering if index is None else
                                      ordering.take(pa.array(index)))
                order = merge_runs([sort_in], key_cols, merge_engine=engine,
                                   drop_deletes=False,
                                   key_encoder=self.parent.key_encoder,
                                   seq_fields=seq_fields,
                                   seq_desc=self.parent.options
                                   .sequence_field_descending).indices
            else:
                order = sort_table(head, key_cols,
                                   key_encoder=self.parent.key_encoder)
            sorted_kv = gather_columns(
                [(head, order),
                 (values, order if index is None else index[order])])
            if deferred and metrics_enabled():
                global_registry().write_metrics().counter(
                    WRITE_DEFERRED_GATHER_ROWS).inc(deferred)

        changelog: List[DataFileMeta] = []
        if self.parent.changelog_input:
            # changelog-producer=input: raw rows in arrival order
            with _build_span(snap.num_rows):
                cl = _joined(head, snap.arrival(fields))
            changelog = self.parent.write_changelog(
                self.partition, self.bucket, cl)
        return sorted_kv, changelog

    def flush(self):
        """Snapshot the buffer (caller thread) and hand the
        sort/encode/upload to the flush pool; bucket k+1's hashing and
        buffering proceed while this bucket encodes and uploads."""
        snap = self._snapshot()
        if snap is None:
            return

        def task(snap=snap):
            sorted_kv, changelog = self._sorted_chunk(snap)
            metas = self.parent.kv_writer.write(
                self.partition, self.bucket, sorted_kv, level=0)
            # publish only after the upload succeeded: a retried
            # attempt rewrites under fresh names, never double-counts
            self.new_files.extend(metas)
            self.changelog_files.extend(changelog)

        self.parent.flush_pool().submit(self._key, snap.nbytes, task)

    # -- spillable buffer (reference SortBufferWriteBuffer:59 spill via
    # MergeSorter/BinaryExternalSortBuffer: full buffers become local
    # sorted runs, merged into L0 once at prepareCommit — fewer, larger
    # L0 files than one flush file per buffer-full) ----------------------

    def _spill_codec(self):
        """IPC compression per spill-compression(+zstd-level)."""
        codec = self.parent.options.get(CoreOptions.SPILL_COMPRESSION)
        if codec in (None, "none"):
            return None
        if codec == "zstd":
            level = self.parent.options.get(
                CoreOptions.SPILL_COMPRESSION_ZSTD_LEVEL)
            return pa.Codec("zstd", compression_level=level)
        return pa.Codec(codec)

    def _write_spill_file(self, sorted_kv: pa.Table) -> str:
        import tempfile
        if self._spill_dir is None:
            self._spill_dir = tempfile.mkdtemp(prefix="paimon-spill-")
        path = os.path.join(self._spill_dir,
                            f"spill-{self._spill_seq}.arrow")
        self._spill_seq += 1
        opts = pa.ipc.IpcWriteOptions(compression=self._spill_codec())
        # batches are BYTE-capped (~24MB): the k-way merge buffers at
        # least one batch per run, so row-capped batches the size of a
        # whole write buffer would recreate the memory cliff spilling
        # exists to avoid
        per_row = max(1, sorted_kv.nbytes // max(1, sorted_kv.num_rows))
        chunk_rows = max(1024, (24 << 20) // per_row)
        with pa.OSFile(path, "wb") as f, \
                pa.ipc.new_file(f, sorted_kv.schema, options=opts) as wr:
            wr.write_table(sorted_kv, max_chunksize=chunk_rows)
        self._spill_bytes += os.path.getsize(path)
        return path

    def _spill(self):
        """Snapshot (caller thread) + pooled sort/IPC-write; spill
        folding rides the same per-bucket actor so `spills` stays
        append-ordered.  The spill write and the fold are SEPARATE
        tasks (= separate retry domains): a transient fold failure must
        not re-run the spill write after it already published — that
        would duplicate the run (and its changelog events)."""
        snap = self._snapshot()
        if snap is None:
            return
        self._spills_scheduled += 1
        payload = snap.nbytes
        self._spill_sched_bytes += payload

        def spill_task(snap=snap):
            sorted_kv, changelog = self._sorted_chunk(snap)
            path = self._write_spill_file(sorted_kv)
            # publish LAST: a retried attempt rewrote under fresh names
            self.spills.append(path)
            self.changelog_files.extend(changelog)
            self._spill_sched_bytes -= payload

        def fold_task():
            max_handles = self.parent.options.get(
                CoreOptions.LOCAL_SORT_MAX_NUM_FILE_HANDLES)
            if len(self.spills) > max_handles:
                self._fold_spills(max_handles)

        pool = self.parent.flush_pool()
        pool.submit(self._key, payload, spill_task)
        pool.submit(self._key, 1, fold_task)

    def _fold_spills(self, max_handles: int):
        """Merge the oldest runs into one so at most `max_handles`
        stay open at once (local-sort.max-num-file-handles; reference
        BinaryExternalSortBuffer's external-merge fan-in bound)."""
        from paimon_tpu.ops.merge_stream import merge_runs_streamed
        fold, rest = self.spills[:max_handles], self.spills[max_handles:]
        schema = self.parent.schema
        key_cols = [KEY_PREFIX + k for k in schema.trimmed_primary_keys()]
        encoder = self.parent.key_encoder

        out_path: List[str] = []
        writer_box: List = [None, None]       # (OSFile, ipc writer)

        def emit(window: pa.Table):
            if window.num_rows == 0:
                return
            if writer_box[0] is None:
                path = os.path.join(self._spill_dir,
                                    f"spill-fold-{self._spill_seq}"
                                    f".arrow")
                self._spill_seq += 1
                out_path.append(path)
                writer_box[0] = pa.OSFile(path, "wb")
                writer_box[1] = pa.ipc.new_file(
                    writer_box[0], window.schema,
                    options=pa.ipc.IpcWriteOptions(
                        compression=self._spill_codec()))
            writer_box[1].write_table(window)

        merge_runs_streamed([self._ipc_iter(p) for p in fold],
                            key_cols, encoder, emit,
                            self._window_merge_fn())
        if writer_box[1] is not None:
            writer_box[1].close()
            writer_box[0].close()
        # publish the new run list BEFORE unlinking the inputs: a
        # retried fold (transient failure) must re-read a consistent
        # `spills`, never paths it already deleted; an unlink that
        # fails leaves a stray file for _drop_spills' rmtree
        import contextlib
        fold_sizes = sum(os.path.getsize(p) for p in fold)
        self.spills = out_path + rest
        self._spill_bytes -= fold_sizes
        if out_path:
            self._spill_bytes += os.path.getsize(out_path[0])
        for p in fold:
            with contextlib.suppress(OSError):
                os.unlink(p)

    @staticmethod
    def _ipc_iter(path):
        def gen():
            with pa.OSFile(path, "rb") as f:
                rd = pa.ipc.open_file(f)
                for i in range(rd.num_record_batches):
                    yield pa.Table.from_batches([rd.get_batch(i)])
        return gen()

    def _window_merge_fn(self):
        """Window merger shared by spill folding and the final L0
        merge: dedup engines keep winners, deferred engines keep every
        row in (key, seq) order."""
        schema = self.parent.schema
        key_cols = [KEY_PREFIX + k for k in schema.trimmed_primary_keys()]
        engine = self.parent.options.merge_engine
        encoder = self.parent.key_encoder

        def merge_window(tables: List[pa.Table]) -> pa.Table:
            if engine in (MergeEngine.DEDUPLICATE, MergeEngine.FIRST_ROW):
                return merge_runs(
                    tables, key_cols, merge_engine=engine,
                    drop_deletes=False, key_encoder=encoder,
                    seq_fields=self.parent.options.sequence_field or None,
                    seq_desc=self.parent.options
                    .sequence_field_descending).take()
            kv = pa.concat_tables(tables, promote_options="none")
            order = sort_table(kv, key_cols, key_encoder=encoder)
            return kv.take(pa.array(order))
        return merge_window

    def _merge_spills(self, snap):
        """Streamed k-way merge of the spilled runs (+ the live-buffer
        tail `snap`) into rolling L0 files — the same bounded-memory
        machinery the compaction rewrite uses (ops/merge_stream.py).
        Worker-side and retry-safe: output metas accumulate locally and
        publish at the end; spills are dropped only on success, so a
        retried attempt still has its inputs (half-written L0 files of
        the failed attempt are orphans for maintenance)."""
        from paimon_tpu.ops.merge_stream import merge_runs_streamed

        tail, changelog = self._sorted_chunk(snap)
        schema = self.parent.schema
        key_cols = [KEY_PREFIX + k for k in schema.trimmed_primary_keys()]
        encoder = self.parent.key_encoder

        iters = [self._ipc_iter(p) for p in self.spills]
        if tail is not None:
            iters.append(iter([tail]))
        merge_window = self._window_merge_fn()

        out_metas: List[DataFileMeta] = []
        acc: List[pa.Table] = []
        acc_bytes = 0
        target = self.parent.kv_writer.target_file_size

        def write_acc():
            nonlocal acc, acc_bytes
            if not acc:
                return
            merged = pa.concat_tables(acc, promote_options="none")
            out_metas.extend(self.parent.kv_writer.write(
                self.partition, self.bucket, merged, level=0))
            acc, acc_bytes = [], 0

        def emit(window: pa.Table):
            nonlocal acc_bytes
            if window.num_rows == 0:
                return
            if acc and acc_bytes + window.nbytes > target:
                # flush BEFORE overshooting so the rolling writer
                # doesn't split every accumulation into full + sliver
                write_acc()
            acc.append(window)
            acc_bytes += window.nbytes
            if acc_bytes >= target:
                write_acc()

        merge_runs_streamed(iters, key_cols, encoder, emit,
                            merge_window)
        write_acc()
        self.new_files.extend(out_metas)
        self.changelog_files.extend(changelog)
        self._drop_spills()

    def _drop_spills(self):
        import shutil
        self.spills = []
        self._spill_bytes = 0
        if self._spill_dir is not None:
            shutil.rmtree(self._spill_dir, ignore_errors=True)
            self._spill_dir = None

    def schedule_final_flush(self):
        """Queue the end-of-batch drain for this bucket: the tail
        buffer is snapshotted NOW (caller thread, sequence numbers
        already reserved), but the spill-vs-flush decision runs inside
        the task — earlier spill tasks for this bucket may still be in
        flight, and the per-key actor guarantees they land first."""
        snap = self._snapshot()
        if snap is None and self._spills_scheduled == 0:
            # nothing buffered and no spill run queued since the last
            # drain: don't churn a no-op task per bucket per checkpoint
            # (it would also inflate the flushes/flushed_bytes metrics)
            return
        self._spills_scheduled = 0

        def task(snap=snap):
            if self.spills:
                self._merge_spills(snap)
            else:
                sorted_kv, changelog = self._sorted_chunk(snap)
                if sorted_kv is not None:
                    metas = self.parent.kv_writer.write(
                        self.partition, self.bucket, sorted_kv, level=0)
                    self.new_files.extend(metas)
                    self.changelog_files.extend(changelog)

        est = (snap.nbytes if snap is not None else 0) + \
            self._spill_bytes
        self.parent.flush_pool().submit(self._key, est, task)

    def take_commit_message(self) -> Optional[CommitMessage]:
        """Assemble this bucket's message AFTER the pool drained (the
        prepare-commit barrier); caller thread only."""
        msg = CommitMessage(self.partition, self.bucket,
                            self.parent.total_buckets,
                            new_files=list(self.new_files),
                            changelog_files=list(self.changelog_files))
        self.new_files = []
        self.changelog_files = []
        return None if msg.is_empty() else msg


def dicts_to_arrow(arrow_schema: pa.Schema, rows: Sequence[dict],
                   row_kinds: Optional[Sequence[int]] = None
                   ) -> Tuple[pa.Table, Optional[np.ndarray]]:
    """Dict rows -> (Arrow table, int8 kinds array or None): the ONE
    conversion behind TableWrite.write_dicts and the distributed
    plane's write_dicts, so coercion/default behavior cannot drift
    between the single-process and multi-host paths."""
    table = pa.Table.from_pylist(list(rows), schema=arrow_schema)
    kinds = np.asarray(row_kinds, dtype=np.int8) \
        if row_kinds is not None else None
    return table, kinds


def extract_row_kinds(table: pa.Table,
                      row_kinds: Optional[np.ndarray]
                      ) -> Tuple[pa.Table, np.ndarray]:
    """Honor an inline `_ROW_KIND` column or an explicit kinds array;
    defaults to all-INSERT."""
    if ROW_KIND_COL in table.column_names:
        row_kinds = np.asarray(table.column(ROW_KIND_COL)
                               .combine_chunks().cast(pa.int8()))
        table = table.drop_columns([ROW_KIND_COL])
    if row_kinds is None:
        row_kinds = np.zeros(table.num_rows, dtype=np.int8)
    return table, np.asarray(row_kinds, dtype=np.int8)


class LocalMerger:
    """Pre-shuffle hot-key dedup (reference mergetree/localmerge/
    HashMapLocalMerger.java): rows buffer BEFORE bucket routing; when
    the buffer reaches `local-merge-buffer-size`, duplicate keys
    collapse to their winning version with the device merge kernel, so
    a hot key reaches the bucket writers once per flush instead of once
    per update.  Row kinds ride along — a DELETE that wins the merge
    still propagates as a DELETE."""

    def __init__(self, store: "KeyValueFileStoreWrite",
                 buffer_bytes: int):
        self.store = store
        self.buffer_bytes = buffer_bytes
        self._tables: List[pa.Table] = []
        self._kinds: List[np.ndarray] = []
        self._buckets: List[Optional[np.ndarray]] = []
        self._nbytes = 0

    def add(self, table: pa.Table, kinds: np.ndarray,
            buckets: Optional[np.ndarray] = None):
        self._tables.append(table)
        self._kinds.append(kinds)
        self._buckets.append(buckets)
        self._nbytes += table.nbytes
        if self._nbytes >= self.buffer_bytes:
            self.flush()

    def flush(self):
        if not self._tables:
            return
        raw = pa.concat_tables(self._tables, promote_options="none")
        kinds = np.concatenate(self._kinds)
        # precomputed bucket assignments survive the fold when every
        # buffered batch carried them (the topology shuffle always does)
        buckets = np.concatenate(self._buckets) \
            if all(b is not None for b in self._buckets) else None
        self._tables, self._kinds, self._buckets = [], [], []
        self._nbytes = 0
        if raw.num_rows == 0:
            return
        schema = self.store.schema
        engine = self.store.options.merge_engine
        kv = build_kv_table(raw, schema,
                            np.arange(raw.num_rows, dtype=np.int64),
                            kinds)
        # the merge runs BEFORE partition routing, so the fold key must
        # include the partition columns — trimmed pks alone would
        # collapse distinct rows across partitions (and swallow
        # cross-partition reroute deletes)
        key_cols = list(schema.partition_keys) + \
            [KEY_PREFIX + k for k in schema.trimmed_primary_keys()]
        res = merge_runs(
            [kv], key_cols, merge_engine=engine, drop_deletes=False,
            seq_fields=self.store.options.sequence_field or None,
            seq_desc=self.store.options.sequence_field_descending)
        idx = res.indices
        self.store._dispatch(raw.take(pa.array(idx)), kinds[idx],
                             None if buckets is None else buckets[idx])


class KeyValueFileStoreWrite:
    """Routes rows to per-(partition,bucket) writers.

    reference: operation/KeyValueFileStoreWrite.java:70."""

    def __init__(self, file_io: FileIO, table_path: str,
                 table_schema: TableSchema, options: CoreOptions,
                 restore_max_seq: Optional[Callable[[Tuple, int], int]]
                 = None, branch: str = "main",
                 bucket_files_map: Optional[Callable[[], Dict]]
                 = None, schema_manager=None):
        from paimon_tpu.parallel.write_pipeline import maybe_wrap_staging
        file_io, self._stager = maybe_wrap_staging(file_io, options)
        self.file_io = file_io
        self.table_path = table_path
        self.schema = table_schema
        self.options = options
        self.branch = branch
        self._bucket_files_map = bucket_files_map
        self._schema_manager = schema_manager
        self.partition_keys = table_schema.partition_keys
        self.path_factory = FileStorePathFactory.from_options(
            table_path, self.partition_keys, options)
        self.kv_writer = KeyValueFileWriter(
            file_io, self.path_factory, table_schema,
            file_format=options.file_format,
            compression=options.file_compression,
            target_file_size=options.target_file_size,
            index_spec=options.file_index_spec,
            bloom_fpp=options.get(CoreOptions.FILE_INDEX_BLOOM_FPP),
            index_in_manifest_threshold=options.get(
                CoreOptions.FILE_INDEX_IN_MANIFEST_THRESHOLD),
            format_per_level=options.file_format_per_level,
            format_options=options.format_options,
            **options.kv_writer_kwargs())
        rt = table_schema.logical_row_type()
        self.total_buckets = options.bucket
        bucket_keys = table_schema.bucket_keys()
        self._dynamic = None
        self._postpone = options.bucket == -2
        if self._postpone:
            # postpone mode (reference postpone/PostponeBucketFileStoreWrite):
            # rows stage un-hashed under bucket-postpone; rescale_postpone
            # redistributes them later
            self.bucket_assigner = None
        elif options.bucket < 1:
            # dynamic bucket mode (reference BucketMode.HASH_DYNAMIC)
            from paimon_tpu.core.bucket import KeyHasher
            from paimon_tpu.core.dynamic_bucket import DynamicBucketAssigner
            from paimon_tpu.core.scan import FileStoreScan
            self._key_hasher = KeyHasher(
                bucket_keys, [rt.get_field(k).type for k in bucket_keys])
            self._dynamic = DynamicBucketAssigner(
                FileStoreScan(file_io, table_path, table_schema, options,
                              branch=branch),
                options.dynamic_bucket_target_row_num)
            self.bucket_assigner = None
        else:
            self.bucket_assigner = FixedBucketAssigner(
                bucket_keys, [rt.get_field(k).type for k in bucket_keys],
                options.bucket)
        from paimon_tpu.ops.normkey import NormalizedKeyEncoder
        from paimon_tpu.types import data_type_to_arrow
        self.key_encoder = NormalizedKeyEncoder(
            [data_type_to_arrow(rt.get_field(k).type)
             for k in table_schema.trimmed_primary_keys()],
            nullable=[rt.get_field(k).type.nullable
                      for k in table_schema.trimmed_primary_keys()])
        self._writers: Dict[Tuple, _BucketWriter] = {}
        self._key_names = table_schema.trimmed_primary_keys()
        # the caller's batches that buffered selections read, oldest
        # first (caller thread only), and their bytes
        self._pins: Dict[int, _Pin] = {}
        self._pinned_bytes = 0
        # serving-plane hook (service/delta.py ServingWriter): called
        # with (partition, bucket, table, kinds, seqs) for every
        # buffered batch, on the single-threaded caller
        self.delta_listener = None
        self._flush_pool = None       # lazily built (write_pipeline)
        # bounded dispatch lookahead: batch N+1's hash/group-by/take
        # runs on a prep worker while batch N routes (seq reservation
        # stays on the caller, strictly in batch order)
        self._prep_pool = None
        self._prep = deque()
        self._restore_max_seq = restore_max_seq
        self.changelog_input = (
            options.changelog_producer == "input")
        # changelog-producer=lookup: every commit compacts its level-0
        # files (ForceUpLevel0Compaction) and writes their changelog;
        # lookup-wait=false leaves that to the next commit
        self.lookup = options.changelog_producer == "lookup"
        self.lookup_wait = options.get(CoreOptions.LOOKUP_WAIT)
        self.spillable = options.get(CoreOptions.WRITE_BUFFER_SPILLABLE)
        self._changelog_counter = 0
        self._local_merger: Optional[LocalMerger] = None
        lm_size = options.get(CoreOptions.LOCAL_MERGE_BUFFER_SIZE)
        if lm_size:
            from paimon_tpu.options import MergeEngine
            if options.merge_engine not in (MergeEngine.DEDUPLICATE,
                                            MergeEngine.FIRST_ROW):
                raise ValueError(
                    "local-merge-buffer-size supports deduplicate / "
                    "first-row merge engines (reference "
                    "HashMapLocalMerger applies whole-row merges)")
            if self.changelog_input:
                raise ValueError(
                    "local-merge-buffer-size folds input rows, which "
                    "would drop changelog-producer=input events")
            self._local_merger = LocalMerger(self, lm_size)

    def flush_pool(self):
        """The shared bucket-flush executor (parallel/write_pipeline.py);
        write.flush.parallelism=1 degrades it to the inline serial path."""
        if self._flush_pool is None:
            from paimon_tpu.parallel.write_pipeline import FlushPool
            self._flush_pool = FlushPool.from_options(self.options)
        return self._flush_pool

    # -- seam for restore (reference operation/WriteRestore.java) ------------

    def restore_max_seq(self, partition: Tuple, bucket: int) -> int:
        if self._restore_max_seq is None:
            return -1
        return self._restore_max_seq(partition, bucket)

    def write_changelog(self, partition: Tuple, bucket: int,
                        table: pa.Table) -> List[DataFileMeta]:
        from paimon_tpu.core.kv_file import write_changelog_file
        return write_changelog_file(
            self.file_io, self.path_factory, self.schema,
            self.options.changelog_file_format,
            self.options.changelog_file_compression,
            partition, bucket, table,
            prefix=self.options.changelog_file_prefix,
            format_options=self.options.format_options)

    # -- writes --------------------------------------------------------------

    def write_arrow(self, table: pa.Table,
                    row_kinds: Optional[np.ndarray] = None,
                    buckets: Optional[np.ndarray] = None):
        """Write a batch of rows (full table schema). Optional `row_kinds`
        int8[N] (RowKind codes); a `_ROW_KIND` column is also honored.
        `buckets` skips re-hashing when the caller already assigned
        them (the multi-writer topology's shuffle)."""
        table, row_kinds = extract_row_kinds(table, row_kinds)

        if self._local_merger is not None and not self._postpone:
            self._local_merger.add(table, row_kinds, buckets)
            return
        self._dispatch(table, row_kinds, buckets)

    def _dispatch(self, table: pa.Table, row_kinds: np.ndarray,
                  precomputed_buckets: Optional[np.ndarray] = None):
        from paimon_tpu.parallel.write_pipeline import lpt_order
        if self._postpone:
            self._drain_prep()
            buckets = np.full(table.num_rows, -2, dtype=np.int32)
            for (part, bucket), idx in lpt_order(
                    group_by_partition_bucket(
                        table, buckets, self.partition_keys)):
                sub = table.take(pa.array(idx))
                self._writer(part, bucket).write(_Rows(sub),
                                                 row_kinds[idx])
            return
        if self._dynamic is not None:
            # partition-first grouping: bucket assignment depends on the
            # partition's hash index (stateful — no lookahead here)
            self._drain_prep()
            zeros = np.zeros(table.num_rows, dtype=np.int32)
            for (part, _), idx in group_by_partition_bucket(
                    table, zeros, self.partition_keys):
                sub = table.take(pa.array(idx))
                sub_kinds = row_kinds[idx]
                buckets = self._dynamic.assign(
                    part, self._key_hasher.hashes(sub))
                for (_, bucket), idx2 in lpt_order(
                        group_by_partition_bucket(sub, buckets, [])):
                    self._writer(part, bucket).write(
                        _Rows(sub.take(pa.array(idx2))), sub_kinds[idx2])
            return

        # fixed-bucket hot path: the hash/group-by/take is a PURE
        # function of the batch, so it runs on a prep worker while the
        # previous batch routes — the "incoming batch's hash overlaps
        # bucket flushes" leg of the pipeline.  Routing (and therefore
        # sequence reservation) stays on this thread, in batch order.
        # A batch of several groups hands each writer a selection: the
        # key columns and kinds taken, the value columns left in the
        # batch for the flush's gather (`_Rows`).
        # The kinds are copied here, on the caller: prep may run after
        # write_arrow has returned and the caller reuses its array.
        def prep(table=table, kinds=row_kinds.copy(),
                 pre=precomputed_buckets):
            from paimon_tpu.metrics import (
                WRITE_HASH_MS, WRITE_HASH_ROWS, WRITE_HASH_VECTOR_ROWS,
                WRITE_ROUTE_MS, WRITE_ROUTE_NOCOPY_ROWS, WRITE_ROUTE_ROWS,
                global_registry,
            )
            from paimon_tpu.obs.trace import metrics_enabled, span
            with span("write.route", cat="write", group="write",
                      metric=WRITE_ROUTE_MS, rows=table.num_rows) as sp:
                buckets = pre
                if buckets is None:
                    hashed, vector = self.bucket_assigner.hashed_rows(
                        table.num_rows)
                    # one bucket hashes nothing: no span, the route a leaf
                    with span("write.hash", cat="write", group="write",
                              metric=WRITE_HASH_MS, rows=table.num_rows) \
                            if hashed else contextlib.nullcontext():
                        buckets = self.bucket_assigner.assign(table)
                    if metrics_enabled():
                        group = global_registry().write_metrics()
                        group.counter(WRITE_HASH_ROWS).inc(hashed)
                        group.counter(WRITE_HASH_VECTOR_ROWS).inc(vector)
                groups = lpt_order(group_by_partition_bucket(
                    table, buckets, self.partition_keys))
                if len(groups) == 1:
                    # the group is the batch: no take (an Arrow table
                    # is immutable, the writer may keep the caller's)
                    out, copied = [(groups[0][0], _Rows(table), kinds)], 0
                else:
                    with _take_span(table.num_rows):
                        out = self._select(table, kinds, groups)
                    copied = table.num_rows
                sp.set(groups=len(groups), copied_rows=copied)
                if metrics_enabled():
                    group = global_registry().write_metrics()
                    group.counter(WRITE_ROUTE_ROWS).inc(table.num_rows)
                    group.counter(WRITE_ROUTE_NOCOPY_ROWS).inc(
                        table.num_rows - copied)
                return out

        pool = self._prep_executor()
        if pool is None:
            self._route(prep())
            return
        from paimon_tpu.obs.trace import carry
        self._prep.append(pool.submit(carry(prep)))
        # bounded lookahead: at most 4 batches prepped ahead (each holds
        # its batch, and the key columns of its groups when it fell
        # into several), routed strictly in submission order
        while len(self._prep) > 4:
            self._route(wait_future(self._prep.popleft(),
                                    "write prep backpressure"))
        while self._prep and self._prep[0].done():
            self._route(wait_future(self._prep.popleft(),
                                    "write prep drain"))

    def _select(self, table: pa.Table, kinds: np.ndarray, groups):
        """Each group of a batch as a selection of it: the key columns
        and kinds taken at the group's rows, the bytes its take would
        hold.  Taken whole where a column's bytes cannot be read off
        its buffers (`taken_nbytes`)."""
        sizes = taken_nbytes(table, [idx for _, idx in groups])
        if sizes is None:
            return [(key, _Rows(table.take(pa.array(idx))), kinds[idx])
                    for key, idx in groups]
        keys = table.select(self._key_names)
        return [(key, _Rows(table, keys.take(pa.array(idx)), idx, nbytes),
                 kinds[idx]) for (key, idx), nbytes in zip(groups, sizes)]

    def _route(self, groups):
        newest = 0
        for (part, bucket), rows, kinds in groups:
            newest = self._pin(rows) if rows.idx is not None \
                else rows.nbytes
            self._writer(part, bucket).write(rows, kinds)
        if self._pins:
            self._bound_pins(newest)

    # -- selections: the caller's batches they pin ---------------------------

    def _pin(self, rows: _Rows) -> int:
        """Record that a buffered selection reads its batch; the
        batch's bytes."""
        pin = self._pins.get(id(rows.table))
        if pin is None:
            pin = self._pins[id(rows.table)] = _Pin(rows.table)
            self._pinned_bytes += pin.nbytes
        pin.rows[id(rows)] = rows
        rows.pin = pin
        return pin.nbytes

    def _unpin(self, rows: _Rows):
        """The selection left its buffer (a flush payload holds it now)
        or was taken: a batch no buffered selection reads is let go."""
        pin, rows.pin = rows.pin, None
        del pin.rows[id(rows)]
        if not pin.rows:
            del self._pins[id(pin.table)]
            self._pinned_bytes -= pin.nbytes

    def _materialise(self, rows: _Rows):
        """Take a selection's rows out of the caller's batch now, as
        the route once did; its accounted bytes are the take's."""
        with _take_span(len(rows.idx)):
            taken = rows.table.take(pa.array(rows.idx))
        if rows.pin is not None:
            self._unpin(rows)
        rows.table, rows.keys, rows.idx = taken, None, None

    def _bound_pins(self, newest: int):
        """Hold the batches that buffered selections pin to the bytes
        the buffers account plus the newest batch: past that, the
        oldest batch's selections are taken whole."""
        limit = newest + sum(w.buffered_bytes for w in self._writers.values())
        while self._pinned_bytes > limit:
            oldest = next(iter(self._pins.values()))
            for rows in list(oldest.rows.values()):
                self._materialise(rows)

    def _drain_prep(self):
        while self._prep:
            self._route(wait_future(self._prep.popleft(),
                                    "write prep drain"))

    def _prep_executor(self):
        """Lookahead pool (up to 4 workers, bounded by the flush
        parallelism); None (inline) on the serial path so
        write.flush.parallelism=1 stays byte-for-byte legacy.  Also
        None with a delta listener attached: the serving plane's
        visibility contract is 'readable when write() returns', which
        requires synchronous in-order routing — deferred prep would
        publish the batch to the delta tier whole batches late."""
        from paimon_tpu.parallel.write_pipeline import (
            resolve_flush_parallelism,
        )
        par = resolve_flush_parallelism(self.options)
        if par <= 1 or self.delta_listener is not None:
            return None
        if self._prep_pool is None:
            from paimon_tpu.parallel.executors import new_thread_pool
            self._prep_pool = new_thread_pool(min(4, par),
                                              "paimon-write-prep")
        return self._prep_pool

    def _writer(self, partition: Tuple, bucket: int) -> _BucketWriter:
        key = (partition, bucket)
        if key not in self._writers:
            self._writers[key] = _BucketWriter(self, partition, bucket)
        return self._writers[key]

    def prepare_commit(self) -> List[CommitMessage]:
        """The pipeline barrier: schedule every bucket's final drain
        (largest pending bytes first, LPT like parallel/packing.py),
        wait for the pool, then assemble messages on the caller thread.
        The first worker error re-raises here with the remaining queued
        flushes cancelled — a failed prepare commits nothing."""
        if self._local_merger is not None:
            self._local_merger.flush()
        self._drain_prep()
        for w in sorted(self._writers.values(),
                        key=lambda w: -w.pending_bytes()):
            w.schedule_final_flush()
        self.flush_pool().drain()
        out = []
        auto_compact = not self.options.write_only and not self._postpone
        existing_map = None
        if auto_compact and self._bucket_files_map is not None:
            # ONE manifest read for the whole commit, not one per bucket
            existing_map = self._bucket_files_map()
        existing_map = existing_map or {}
        deferred = auto_compact and self.lookup and not self.lookup_wait
        todo = []
        for w in self._writers.values():
            msg = w.take_commit_message()
            if msg is None and deferred and any(
                    f.level == 0 for f in existing_map.get(w._key, [])):
                # the level-0 files an earlier commit left for this one
                msg = CommitMessage(w.partition, w.bucket,
                                    self.total_buckets)
            if msg is not None:
                if auto_compact:
                    todo.append((w, msg))
                out.append(msg)
        if self.lookup:
            # every touched bucket compacts in this commit: side by side
            self._per_bucket(
                lambda w, msg: self._maybe_compact(w, msg, existing_map),
                todo, "inline compaction")
        else:
            for w, msg in todo:
                self._maybe_compact(w, msg, existing_map)
        out = [m for m in out if not m.is_empty()]
        if self._dynamic is not None:
            entries = self._dynamic.index_entries()
            if entries:
                if out:
                    out[0].index_entries.extend(entries)
                else:
                    out.append(CommitMessage((), 0, self.total_buckets,
                                             index_entries=entries))
        if self._stager is not None:
            # durability barrier LAST: every file a message names must
            # be acked by the object store before the caller may commit
            # (staged uploads overlapped all the sorting/encoding and
            # the compaction above; an upload failure raises here and
            # poisons the stager — commit nothing, close the writer)
            self._stager.drain()
        return out

    @staticmethod
    def _per_bucket(fn, jobs, what: str):
        """`fn(*job)` for each job, the buckets side by side on a pool
        (threads `paimon-compact_N`, at most min(8, cores)) when there
        are several; the first error raises here."""
        workers = min(8, os.cpu_count() or 1, len(jobs))
        if workers <= 1:
            for job in jobs:
                fn(*job)
            return
        from paimon_tpu.obs.trace import carry
        from paimon_tpu.parallel.executors import new_thread_pool
        pool = new_thread_pool(workers, "paimon-compact")
        try:
            for f in [pool.submit(carry(fn), *job) for job in jobs]:
                wait_future(f, what)
        finally:
            pool.shutdown(wait=True, cancel_futures=True)

    def _maybe_compact(self, w: _BucketWriter, msg: CommitMessage,
                       existing_map: Dict):
        """Inline compaction at prepare-commit when the bucket's sorted
        runs exceed the trigger (reference MergeTreeWriter: compaction
        fires at flush unless write-only). The picked unit may include
        the message's own new L0 files: commit() publishes APPEND before
        COMPACT, so the conflict check still sees them.

        Under `changelog-producer=lookup` the pick forces every level-0
        run up (reference ForceUpLevel0Compaction) and the message
        carries the compaction's changelog, looked up in the writer's
        levels index; with `lookup-wait=false` this commit's own L0
        files wait for the next commit."""
        existing = existing_map.get((msg.partition, msg.bucket), [])
        if self.lookup and not self.lookup_wait:
            files = list(existing)
        else:
            files = existing + msg.new_files
        if not files or (len(files) < 2 and not self.lookup):
            return
        from paimon_tpu.compact.manager import MergeTreeCompactManager
        mgr = MergeTreeCompactManager(
            self.file_io, self.table_path, self.schema, self.options,
            msg.partition, msg.bucket, files,
            schema_manager=self._schema_manager,
            levels_index=self._levels_index(w) if self.lookup else None)
        result = mgr.compact(full=False, force_up_l0=self.lookup)
        if result is None or result.is_empty():
            return
        msg.compact_before = result.before
        msg.compact_after = result.after
        msg.compact_changelog = result.changelog

    def _levels_index(self, w: _BucketWriter):
        if w.lookup_index is None:
            from paimon_tpu.lookup.levels_index import LevelsIndex
            w.lookup_index = LevelsIndex(
                self.key_encoder,
                [KEY_PREFIX + k for k in self._key_names])
        return w.lookup_index

    def close(self):
        if self._prep_pool is not None:
            self._prep_pool.shutdown(wait=True, cancel_futures=True)
            self._prep_pool = None
        self._prep.clear()
        if self._flush_pool is not None:
            # join the workers FIRST so no task mutates spill state
            # while we clean it; abandoned flushes are dropped (their
            # uploads become orphans for maintenance)
            self._flush_pool.shutdown(wait=True)
            self._flush_pool = None
        if self._stager is not None:
            # after the flush pool: no worker stages once we shut the
            # upload pool; abandoned staged files are removed with the
            # stage dir (their half-done uploads are orphans, like
            # abandoned inline uploads)
            self._stager.close()
        for w in self._writers.values():
            w._drop_spills()         # aborted writes must not leak /tmp
        self._writers.clear()
        self._pins.clear()
        self._pinned_bytes = 0
