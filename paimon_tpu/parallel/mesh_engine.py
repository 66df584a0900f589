"""Mesh compaction engine: one full compaction of a primary-key table
across the chips of a host, every merge engine, bounded key windows.

Reached through the normal entry point — `FileStoreTable.compact(
full=True)` on a table with `tpu.mesh.compact=true` (`compact/
compact_action.py`; CLI, SQL `CALL compact` and the maintenance plane's
`group_filter` alike) — or directly as `compact_table_mesh`.

**What a run is.**  Lanes = the devices JAX finds (`bucket_mesh()`: 4
on a four-chip v5e host, 1 on one chip, 8 virtual ones in the tests),
one lane a device.  The buckets with work are packed onto the lanes by
their manifest row counts (greedy LPT, `parallel/packing.py`; no file
is read) and each lane works through its queue one bucket at a time.
A bucket streams as key windows: its sorted runs decode in chunks of
`tpu.mesh.window-rows` rows on one prefetch thread a run (the lane
encode runs there too), and `ops/merge_stream.py iter_merge_windows`
cuts a window at `tpu.merge.window-rows` rows a run, so a window holds
at most runs x that many rows (8 x 256Ki = 2Mi by default) and a key
never straddles two.  After the capped windows comes one at the natural
bound and then a tail of a few rows each time a run ends.

**The step loop** is ONE host thread in lock step: a step takes the
next window of every lane's current bucket, stacks them padded to the
largest one's power of two as `[lanes, n_pad(, L)]` uint32 operands
(key lanes, sequence halves, validity, offset-value codes), uploads
them with one `NamedSharding`, runs `shard_map(segmented merge)` — each
chip sorts its one lane with `ops/merge.py segmented_merge_body`, the
single-chip sort and winner select, plus a `psum` of the winners — downloads `perm` and `winner`,
and takes and emits each lane's winners in turn (`ops/merge.py gather`;
aggregation and partial-update feed the sorted order into the
single-chip epilogue `ops/agg.py aggregate_sorted_segments`, so the
output is row-identical by construction).  A lane whose bucket has
drained, or whose tail is shorter than its neighbours', sorts padding
in that step.  A window that holds a prefix-truncated key takes the
exact host merge (`merge_runs`) instead.  Output files roll per bucket
on the same thread as windows emit; one COMPACT snapshot at the end.
Any merge engine without a kernel raises
`UnsupportedMergeEngineError`, never a silent deduplicate.

**Spans and counters** are the normal path's where the work is the
same: `compact.table` (root) > `compact.task` (`route` mesh: the one
task of this route, on the calling thread) > per step `merge.prep`
(assembly), `compaction.window` > `merge.device` (`route` mesh: first
upload to last download), `merge.gather` per lane; `merge.prep`,
`decode`, `io.read` on the prefetch threads; `encode`, `io.upload` at
each roll.  `PATH_COUNTS["device"]` counts a kernel window, and
`compaction` / `mesh_steps`, `mesh_padded_rows` (lanes x n_pad of every
step) say what the lock step cost.  Per operation that is a few spans a
step and a chunk, never one a row.

**On the chip** (PERF.md, PR 32; `dedup_compact_mesh4`): the 50M-row,
8-bucket table of `chipbench/configs/mor50m-dedup-mesh4.json` on four
v5e chips — see PERF.md sections 5 and 6 for the readings; the step
loop's single thread, not the chips, sets the rate.

**Fault isolation.**  A bucket is the failure domain: a transient error
(object-store 503, injected IO fault, lane or device loss) anywhere in
one bucket's window stream aborts that bucket, deletes the files the
attempt already rolled and requeues it with capped decorrelated-jitter
backoff (`compaction.retry.max-attempts`, `compaction.retry.backoff`)
while the other lanes keep streaming, then degrades it to the
single-chip `compact/manager.py` path (`compaction.mesh.fallback`)
instead of failing the job.  Non-transient errors propagate at once
(`parallel/fault.py` classifies).

The legacy `parallel/sharded_compact.py` (pad every bucket to the
largest) is a second, older engine that no option reaches (ROADMAP D3).
"""

from __future__ import annotations

import time as _time
from dataclasses import dataclass, field as dc_field
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from paimon_tpu.options import ChangelogProducer, CoreOptions, MergeEngine
from paimon_tpu.parallel.packing import (
    bucket_row_counts, pack_buckets, packing_skew,
)

__all__ = ["UnsupportedMergeEngineError", "MeshCompactStats",
           "compact_table_mesh", "SUPPORTED_MERGE_ENGINES"]

SUPPORTED_MERGE_ENGINES = (
    MergeEngine.DEDUPLICATE, MergeEngine.PARTIAL_UPDATE,
    MergeEngine.AGGREGATE, MergeEngine.FIRST_ROW,
)


class UnsupportedMergeEngineError(ValueError):
    """A mesh compaction path was asked to run a merge engine it has no
    kernel for.  Raised instead of silently deduplicating (the legacy
    sharded path's failure mode)."""


@dataclass
class MeshCompactStats:
    buckets: int = 0            # buckets that needed a rewrite
    lanes: int = 0              # mesh lanes (= devices)
    input_rows: int = 0         # manifest row count over rewritten files
    output_rows: int = 0
    windows: int = 0            # device window merges executed
    peak_window_rows: int = 0   # largest single window (pre-padding)
    peak_buffered_rows: int = 0  # max per-bucket run-buffer rows
    skew: float = 1.0           # max/mean lane load after packing
    snapshot_id: Optional[int] = None
    lane_rows: List[int] = dc_field(default_factory=list)
    retries: int = 0            # per-bucket transient-failure retries
    fallbacks: int = 0          # buckets degraded to single-chip
    cleanup_errors: int = 0     # best-effort partial-file deletes failed


# ---------------------------------------------------------------------------
# window kernel: shard_map(segmented merge) over [lanes, N], a lane a device
# ---------------------------------------------------------------------------

_KERNEL_CACHE: dict = {}


class _MeshWindowKernel:
    """Engine-parameterized window merge over a [B, N] lane stack, B the
    mesh's devices: each sorts the one lane its shard holds.

    __call__(lanes[B,N,L], seq_hi[B,N], seq_lo[B,N], invalid[B,N],
    ovc_off[B,N]) -> (perm[B,N], winner[B,N], psum'd total winners).
    `keep` selects the winner row per key segment (last =
    dedup/partial-update/agg segment ends, first = first-row); the first
    `num_key_lanes` lanes define segment identity, further lanes are
    user-defined sequence order.
    """

    def __init__(self, mesh, num_lanes: int, num_key_lanes: int,
                 keep: str, axis: str):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from paimon_tpu.ops.merge import segmented_merge_body

        self.sharding = NamedSharding(mesh, P(axis))
        self._n_dev = mesh.shape[axis]

        def per_lane(lanes, seq_hi, seq_lo, invalid, ovc_off):
            perm, winner, _ = segmented_merge_body(
                [lanes[:, i] for i in range(num_lanes)],
                seq_hi, seq_lo, invalid, keep,
                num_key_lanes=num_key_lanes, ovc_off=ovc_off)
            return perm, winner

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(P(axis), P(axis), P(axis), P(axis),
                           P(axis)),
                 out_specs=(P(axis), P(axis), P()))
        def step(lanes, seq_hi, seq_lo, invalid, ovc_off):
            # one lane a device, so a shard is [1, n_pad(, L)]: sorted
            # as the 1-D program the single-chip path runs.  A vmap
            # over the unit axis has XLA sort [1, n_pad] operands,
            # 57.7 ms a 2Mi-row window on a v5e where the 1-D sort
            # takes a fifth of that (PERF.md section 6, PR 32)
            assert lanes.shape[0] == 1, lanes.shape
            perm, winner = per_lane(lanes[0], seq_hi[0], seq_lo[0],
                                    invalid[0], ovc_off[0])
            total = jax.lax.psum(
                jnp.sum(winner.astype(jnp.int64)), axis)
            return perm[None], winner[None], total.reshape(1)

        self._fn = jax.jit(step)

    def __call__(self, lanes: np.ndarray, seq_hi: np.ndarray,
                 seq_lo: np.ndarray, invalid: np.ndarray,
                 ovc_off: np.ndarray):
        import jax

        args = [jax.device_put(a, self.sharding)
                for a in (lanes, seq_hi, seq_lo, invalid, ovc_off)]
        perm, winner, total = self._fn(*args)
        jax.block_until_ready((perm, winner, total))
        return (np.asarray(perm), np.asarray(winner),
                int(np.asarray(total)[0]))


def _window_kernel(mesh, num_lanes: int, num_key_lanes: int, keep: str,
                   axis: str) -> _MeshWindowKernel:
    key = (mesh, num_lanes, num_key_lanes, keep, axis)
    k = _KERNEL_CACHE.get(key)
    if k is None:
        k = _KERNEL_CACHE[key] = _MeshWindowKernel(
            mesh, num_lanes, num_key_lanes, keep, axis)
    return k


# ---------------------------------------------------------------------------
# engine context + per-bucket streamed jobs
# ---------------------------------------------------------------------------


class _EngineContext:
    """Per-run bundle: reader/writer planes, key encoding, engine mode."""

    def __init__(self, table):
        from paimon_tpu.core.read import MergeFileSplitRead
        from paimon_tpu.core.kv_file import KeyValueFileWriter
        from paimon_tpu.format.blob import blob_column_names

        self.table = table
        self.schema = table.schema
        self.options = table.options
        self.schema_manager = table.schema_manager
        self.schema_cache = {table.schema.id: table.schema}
        self.reader = MergeFileSplitRead(table.file_io, table.path,
                                         table.schema, table.options)
        self.key_cols = self.reader.key_cols
        self.key_encoder = self.reader.key_encoder
        self.path_factory = self.reader.path_factory
        self.writer = KeyValueFileWriter(
            table.file_io, self.path_factory, table.schema,
            file_format=table.options.file_format,
            compression=table.options.file_compression,
            target_file_size=table.options.target_file_size,
            index_spec=table.options.file_index_spec,
            bloom_fpp=table.options.get(CoreOptions.FILE_INDEX_BLOOM_FPP),
            format_per_level=table.options.file_format_per_level,
            format_options=table.options.format_options,
            **table.options.kv_writer_kwargs())
        self.max_level = table.options.max_level
        self.chunk_rows = table.options.get(CoreOptions.MESH_WINDOW_ROWS)
        self.has_blobs = bool(blob_column_names(table.schema))
        self.engine = table.options.merge_engine
        self.keep = ("first" if self.engine == MergeEngine.FIRST_ROW
                     else "last")
        self.seq_fields = table.options.sequence_field or None
        self.seq_desc = table.options.sequence_field_descending
        # fixed lane geometry for the whole run (uniform across buckets)
        self.num_key_lanes = sum(self.key_encoder.lanes_per_col)
        self.num_order_lanes = 0
        if self.seq_fields:
            from paimon_tpu.ops.normkey import NormalizedKeyEncoder
            from paimon_tpu.types import data_type_to_arrow
            rt = table.schema.logical_row_type()
            enc = NormalizedKeyEncoder(
                [data_type_to_arrow(rt.get_field(f).type)
                 for f in self.seq_fields],
                nullable=[True] * len(self.seq_fields))
            self.num_order_lanes = sum(enc.lanes_per_col)
        self.num_lanes = self.num_key_lanes + self.num_order_lanes

    # -- engine-specific window epilogues (host side) -----------------------

    def live_filter(self, merged):
        """Full compaction drops rows whose surviving kind is a
        retract (+I / +U only survive) — same as the single-chip
        manager's _live_view."""
        import pyarrow as pa
        import pyarrow.compute as pc

        from paimon_tpu.ops.merge import KIND_COL
        from paimon_tpu.types import RowKind

        kinds = merged.column(KIND_COL).combine_chunks().cast(pa.int8())
        keep = pc.or_(pc.equal(kinds, RowKind.INSERT),
                      pc.equal(kinds, RowKind.UPDATE_AFTER))
        return merged.filter(keep)

    def expire_filter(self, merged):
        from paimon_tpu.core.read import record_level_expire_filter
        return record_level_expire_filter(self.options, merged)

    def merge_window_host(self, items):
        """Exact single-chip merge of one window — the fallback for
        windows containing prefix-truncated keys (their repair path
        lives in the single-chip kernels) and the reference the
        equivalence tests compare against."""
        from paimon_tpu.ops.agg import merge_runs_agg
        from paimon_tpu.ops.merge import merge_runs

        tables = [it[0] for it in items]
        encoded = [it[1:] for it in items]
        if self.engine in (MergeEngine.DEDUPLICATE, MergeEngine.FIRST_ROW):
            res = merge_runs(
                tables, self.key_cols,
                merge_engine=("first-row"
                              if self.engine == MergeEngine.FIRST_ROW
                              else "deduplicate"),
                drop_deletes=True, key_encoder=self.key_encoder,
                seq_fields=self.seq_fields, seq_desc=self.seq_desc,
                encoded=encoded)
            merged = res.take()
        else:
            merged = merge_runs_agg(tables, self.key_cols, self.schema,
                                    self.options,
                                    key_encoder=self.key_encoder,
                                    seq_fields=self.seq_fields)
            merged = self.live_filter(merged)
        return self.expire_filter(merged)

    def window_operands(self, items):
        """One lane's window as the step stacks it: (window table, lane
        matrix with any user order lanes, int64 sequence, the items'
        row offsets), or None where the window goes to the host merge —
        it is empty, or holds a prefix-truncated key."""
        import pyarrow as pa

        from paimon_tpu.ops.merge import SEQ_COL, user_seq_order_lanes

        wtable = pa.concat_tables([it[0] for it in items],
                                  promote_options="none") \
            if len(items) > 1 else items[0][0]
        if wtable.num_rows == 0 or \
                any(np.asarray(it[2]).any() for it in items):
            return None
        lanes_mat = np.concatenate([np.asarray(it[1]) for it in items]) \
            if len(items) > 1 else np.asarray(items[0][1])
        if self.seq_fields:
            lanes_mat = np.concatenate(
                [lanes_mat, user_seq_order_lanes(
                    wtable, self.seq_fields, self.seq_desc)], axis=1)
        seq = np.asarray(wtable.column(SEQ_COL).combine_chunks()
                         .cast("int64"))
        # each window item is one sorted-run piece: its offset-value
        # codes ride to the device so the kernel's winner-select
        # consumes the single-int offsets first
        item_starts = np.concatenate(
            [[0], np.cumsum([it[0].num_rows for it in items])]
        ).astype(np.int64)
        return wtable, lanes_mat, seq, item_starts

    def merge_window_device(self, wtable, perm_row: np.ndarray,
                            winner_row: np.ndarray):
        """Fold one window given the mesh kernel's sorted order."""
        import pyarrow as pa

        from paimon_tpu.compact.manager import live_span
        from paimon_tpu.ops.merge import KIND_COL, gather, winners_span
        from paimon_tpu.types import RowKind

        n = wtable.num_rows
        if self.engine in (MergeEngine.DEDUPLICATE, MergeEngine.FIRST_ROW):
            with winners_span(n, "mesh") as sp:
                win_pos = np.flatnonzero(winner_row)
                indices = perm_row[win_pos].astype(np.int64)
                kinds = np.asarray(wtable.column(KIND_COL).combine_chunks()
                                   .cast(pa.int8()))
                keep_mask = (kinds[indices] == RowKind.INSERT) | \
                            (kinds[indices] == RowKind.UPDATE_AFTER)
                indices = indices[keep_mask]
                sp.set(winners=len(indices))
            merged = gather(wtable, indices)
            with live_span(merged.num_rows):
                return self.expire_filter(merged)
        # aggregation / partial-update: kernel order + segment ends feed
        # the shared single-chip aggregation epilogue
        from paimon_tpu.ops.agg import aggregate_sorted_segments

        with winners_span(n, "mesh"):
            real = perm_row < n
            order = perm_row[real].astype(np.int64)
            win_sorted = np.asarray(winner_row[real], dtype=bool)
            if len(win_sorted):
                win_sorted[-1] = True
                seg_end = win_sorted
                seg_id = np.concatenate(
                    [[0], np.cumsum(seg_end[:-1])]).astype(np.int64)
            else:
                seg_id = np.zeros(0, np.int64)
        merged = aggregate_sorted_segments(
            wtable, order, seg_id, win_sorted, self.key_cols,
            self.schema, self.options)
        with live_span(merged.num_rows):
            return self.expire_filter(self.live_filter(merged))


def _stack_windows(device_rows, n_pad: int, num_lanes: int):
    """The step's kernel operands: every lane's window padded to
    `n_pad` rows and stacked `[lanes, n_pad(, L)]`; a lane with no
    window this step (drained, backing off, host-merged) is all
    padding, which sorts last and wins nothing."""
    from paimon_tpu.ops.ovc import OVC_OFF_SENTINEL, run_ovc_offsets

    n_dev = len(device_rows)
    lanes_arr = np.zeros((n_dev, n_pad, num_lanes), dtype=np.uint32)
    seq_hi = np.zeros((n_dev, n_pad), dtype=np.uint32)
    seq_lo = np.zeros((n_dev, n_pad), dtype=np.uint32)
    invalid = np.ones((n_dev, n_pad), dtype=np.uint32)
    ovc_arr = np.full((n_dev, n_pad), OVC_OFF_SENTINEL, dtype=np.uint32)
    for li, entry in enumerate(device_rows):
        if entry is None:
            continue
        _, wtable, lanes_mat, seq, item_starts = entry
        k = wtable.num_rows
        lanes_arr[li, :k] = lanes_mat
        u = seq.astype(np.int64).view(np.uint64)
        seq_hi[li, :k] = (u >> np.uint64(32)).astype(np.uint32)
        seq_lo[li, :k] = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        invalid[li, :k] = 0
        ovc_arr[li, :k] = run_ovc_offsets(lanes_arr[li, :k], item_starts)
    return lanes_arr, seq_hi, seq_lo, invalid, ovc_arr


class _BucketJob:
    """One (partition, bucket)'s streamed full rewrite: a window
    iterator over its sorted runs plus a rolling output-file writer."""

    def __init__(self, ctx: _EngineContext, split):
        self.ctx = ctx
        self.split = split
        self.files = list(split.data_files)
        self.stream_stats: Dict[str, int] = {}
        self.acc: List = []
        self.acc_bytes = 0
        self.metas: List = []
        self.out_rows = 0
        self._windows = None
        # backoff deadline (monotonic seconds): a retried bucket is
        # requeued with a not-before instead of sleeping the whole
        # mesh — other lanes keep streaming through the wait
        self.ready_at = 0.0

    def _run_iter(self, run_files):
        """Decode one sorted run in bounded chunks, lane-encoding inside
        the prefetch thread (same shape as the single-chip streamed
        rewrite in compact/manager.py)."""
        from paimon_tpu.core.kv_file import read_kv_file
        from paimon_tpu.core.read import evolve_table
        from paimon_tpu.format import get_format

        from paimon_tpu.fs.caching import scoped_batches
        from paimon_tpu.ops.merge import prep_span

        ctx = self.ctx
        options = ctx.table.options

        def encode(t):
            with prep_span(t.num_rows):
                return (t, *ctx.key_encoder.encode_table_ex(
                    t, ctx.key_cols))

        for f in run_files:
            if ctx.has_blobs:
                t = read_kv_file(ctx.table.file_io, ctx.path_factory,
                                 self.split.partition, self.split.bucket,
                                 f, schema=ctx.schema,
                                 schema_manager=ctx.schema_manager,
                                 options=options)
                t = evolve_table(t, f.schema_id, ctx.schema,
                                 ctx.schema_manager, ctx.schema_cache,
                                 keep_sys_cols=True)
                yield encode(t)
                continue
            ext = f.file_name.rsplit(".", 1)[-1]
            fmt = get_format(ext)
            path = f.external_path or ctx.path_factory.data_file_path(
                self.split.partition, self.split.bucket, f.file_name)
            if fmt.identifier == "parquet" and options.get(
                    CoreOptions.READ_DEVICE_DECODE):
                # row-group-at-a-time device decode (memory bound as
                # the pyarrow batch path); unsupported files drop to
                # the format reader below
                from paimon_tpu.format.rawpage import (
                    DeviceDecodeUnsupported, iter_batches_device,
                )
                batches = None
                try:
                    batches = iter_batches_device(
                        ctx.table.file_io, path, ctx.chunk_rows,
                        options)
                except DeviceDecodeUnsupported:
                    from paimon_tpu.metrics import (
                        SCAN_DEVICE_DECODE_FALLBACKS, global_registry,
                    )
                    global_registry().group("scan").counter(
                        SCAN_DEVICE_DECODE_FALLBACKS).inc()
                if batches is not None:
                    for batch in batches:
                        t = evolve_table(
                            batch, f.schema_id, ctx.schema,
                            ctx.schema_manager, ctx.schema_cache,
                            keep_sys_cols=True)
                        yield encode(t)
                    continue
            # gate held only while advancing the inner iterator (see
            # fs.caching.scoped_batches), never across our yields
            for batch in scoped_batches(
                    fmt.create_reader().read_batches(
                        ctx.table.file_io, path,
                        batch_rows=ctx.chunk_rows), options):
                t = evolve_table(batch, f.schema_id, ctx.schema,
                                 ctx.schema_manager, ctx.schema_cache,
                                 keep_sys_cols=True)
                yield encode(t)

    def next_window(self):
        """Next run-ordered item list, or None when the bucket drains."""
        if self._windows is None:
            from paimon_tpu.compact.manager import _prefetch
            from paimon_tpu.core.read import assemble_runs
            from paimon_tpu.ops.merge_stream import iter_merge_windows

            runs_meta = assemble_runs(self.files)
            self._windows = iter_merge_windows(
                [_prefetch(self._run_iter(rf)) for rf in runs_meta],
                self.ctx.key_cols, self.ctx.key_encoder,
                stats=self.stream_stats,
                window_rows=self.ctx.table.options.get(
                    CoreOptions.MERGE_WINDOW_ROWS))
        return next(self._windows, None)

    def emit(self, merged) -> None:
        if merged.num_rows == 0:
            return
        self.out_rows += merged.num_rows
        self.acc.append(merged)
        self.acc_bytes += merged.nbytes
        if self.acc_bytes >= self.ctx.writer.target_file_size:
            self.flush()

    def flush(self) -> None:
        if not self.acc:
            return
        import pyarrow as pa

        from paimon_tpu.manifest import FileSource

        merged = pa.concat_tables(self.acc, promote_options="none") \
            if len(self.acc) > 1 else self.acc[0]
        self.acc, self.acc_bytes = [], 0
        self.metas.extend(self.ctx.writer.write(
            self.split.partition, self.split.bucket, merged,
            level=self.ctx.max_level, file_source=FileSource.COMPACT))


class _LaneState:
    """A mesh lane's queue of bucket jobs; at most one is streaming."""

    def __init__(self, jobs: List[_BucketJob]):
        self.queue = list(jobs)
        self.current: Optional[_BucketJob] = None

    def next_window(self, finalize):
        """(job, window items) for this lane's next window; None when
        the lane has drained OR every queued job is still inside its
        retry-backoff window (ready_at in the future).  Finished
        buckets flush + finalize before the lane advances."""
        while True:
            if self.current is None:
                now = _time.monotonic()
                ready = next((j for j in self.queue
                              if j.ready_at <= now), None)
                if ready is None:
                    return None
                self.queue.remove(ready)
                self.current = ready
            w = self.current.next_window()
            if w is not None:
                return (self.current, w)
            finalize(self.current)
            self.current = None


# ---------------------------------------------------------------------------
# table-level entry
# ---------------------------------------------------------------------------


def _needs_rewrite(split, max_level: int) -> bool:
    """Mirror the single-chip manager's no-op condition: one file
    already at the top level with no deletes has nothing to fold."""
    fs = split.data_files
    return not (len(fs) == 1 and fs[0].level == max_level
                and (fs[0].delete_row_count or 0) == 0)


def compact_table_mesh(table, mesh=None, axis: str = "buckets",
                       retry_policy=None, group_filter=None,
                       commit_user=None, properties=None,
                       properties_provider=None) -> MeshCompactStats:
    """Full compaction of every bucket of a primary-key table through
    the streaming mesh engine: engine-dispatched window kernels over a
    [B, window] lane stack, skew-aware bucket packing, one COMPACT
    snapshot.  Peak host memory per bucket ~ runs x window-rows,
    independent of bucket size.

    Transient failures are isolated per bucket: retry with jittered
    backoff, then single-chip fallback (see module docstring §4 and
    parallel/fault.py).  `retry_policy` overrides the table's
    compaction.retry.* / compaction.mesh.fallback options."""
    from paimon_tpu.core.commit import FileStoreCommit
    from paimon_tpu.core.write import CommitMessage
    from paimon_tpu.metrics import (
        COMPACTION_BUCKET_FAILURES, COMPACTION_BUCKET_FALLBACKS,
        COMPACTION_BUCKET_RETRIES, global_registry,
    )
    from paimon_tpu.ops.merge import _pad_size
    from paimon_tpu.parallel.fault import (
        BucketRetryPolicy, is_transient_error,
    )
    from paimon_tpu.parallel.sharded_merge import bucket_mesh

    engine = table.options.merge_engine
    if engine not in SUPPORTED_MERGE_ENGINES:
        raise UnsupportedMergeEngineError(
            f"merge-engine {engine!r} has no mesh compaction kernel "
            f"(supported: {', '.join(SUPPORTED_MERGE_ENGINES)})")
    if not table.primary_keys:
        raise ValueError("mesh compaction targets primary-key tables")
    if table.options.changelog_producer != ChangelogProducer.NONE:
        raise ValueError(
            "mesh compaction does not produce changelog; use the "
            "single-chip compaction path for changelog producers")
    if table.options.sequence_field and engine == MergeEngine.FIRST_ROW:
        raise ValueError(
            "sequence.field cannot be used with merge-engine first-row")

    if mesh is None:
        mesh = bucket_mesh(axis=axis)
    n_dev = mesh.shape[axis]

    plan = table.new_read_builder().new_scan().plan()
    max_level = table.options.max_level
    splits = [s for s in plan.splits if s.data_files]
    if group_filter is not None:
        # sharded maintenance plane: this host compacts only the
        # (partition, bucket) groups it owns (the scheduling seam of
        # parallel/maintenance_plane.py) — peers run the same program
        # over their own shares
        splits = [s for s in splits
                  if group_filter(tuple(s.partition), s.bucket)]
    jobs_splits = [s for s in splits if _needs_rewrite(s, max_level)]
    stats = MeshCompactStats(lanes=n_dev)
    if not jobs_splits:
        return stats

    row_counts = bucket_row_counts(jobs_splits)
    lane_assign = pack_buckets(row_counts, n_dev)
    stats.buckets = len(jobs_splits)
    stats.input_rows = sum(row_counts)
    stats.lane_rows = [sum(row_counts[i] for i in lane)
                       for lane in lane_assign]
    stats.skew = packing_skew(row_counts, lane_assign)

    ctx = _EngineContext(table)
    lanes_state = [
        _LaneState([_BucketJob(ctx, jobs_splits[i]) for i in lane])
        for lane in lane_assign
    ]

    messages: List[CommitMessage] = []

    def finalize(job: _BucketJob) -> None:
        job.flush()
        stats.output_rows += job.out_rows
        stats.peak_buffered_rows = max(
            stats.peak_buffered_rows,
            job.stream_stats.get("peak_buffered_rows", 0))
        messages.append(CommitMessage(
            job.split.partition, job.split.bucket,
            job.split.total_buckets,
            compact_before=job.files, compact_after=job.metas))

    # -- per-bucket fault isolation (module docstring §4) -------------------
    from paimon_tpu.obs import trace as _trace
    from paimon_tpu.obs.trace import span as _obs_span
    _trace.sync_from_options(table.options)
    policy = retry_policy or BucketRetryPolicy.from_options(table.options)
    fault_metrics = global_registry().compaction_metrics()
    attempts: Dict[Tuple, int] = {}
    backoffs: Dict[Tuple, object] = {}

    def _job_key(split) -> Tuple:
        return (tuple(split.partition), split.bucket)

    def _cleanup_job(job: _BucketJob) -> None:
        """Abort a failed attempt: drop buffered output, close the
        window stream, delete any files the attempt already rolled —
        the retry/fallback must start from the untouched inputs."""
        job.acc, job.acc_bytes = [], 0
        if job._windows is not None:
            try:
                job._windows.close()
            except Exception:               # noqa: BLE001
                stats.cleanup_errors += 1
            job._windows = None
        for m in job.metas:
            names = [m.file_name, *m.extra_files]
            for name in names:
                path = m.external_path \
                    if (name == m.file_name and m.external_path) \
                    else ctx.path_factory.data_file_path(
                        job.split.partition, job.split.bucket, name)
                try:
                    table.file_io.delete_quietly(path)
                except Exception:           # noqa: BLE001
                    stats.cleanup_errors += 1
        job.metas = []

    def _fallback_single_chip(split) -> Optional[CommitMessage]:
        """Degrade one bucket to the exact single-chip full rewrite
        (same merge semantics — the equivalence tests compare these
        two paths row-for-row), itself retried under the policy."""
        from paimon_tpu.compact.manager import MergeTreeCompactManager

        def run():
            from paimon_tpu.metrics import COMPACTION_FALLBACK_MS
            with _obs_span("compaction.fallback", cat="compaction",
                           group="compaction",
                           metric=COMPACTION_FALLBACK_MS,
                           partition=split.partition,
                           bucket=split.bucket, table=table.path):
                mgr = MergeTreeCompactManager(
                    table.file_io, table.path, table.schema,
                    table.options, split.partition, split.bucket,
                    list(split.data_files),
                    schema_manager=table.schema_manager)
                return mgr.compact(full=True)

        result = policy.retry_call(run)
        if result is None or result.is_empty():
            return None
        return CommitMessage(
            split.partition, split.bucket, split.total_buckets,
            compact_before=result.before, compact_after=result.after,
            compact_changelog=result.changelog)

    def _handle_bucket_failure(lane_idx: int, job: _BucketJob,
                               exc: BaseException) -> None:
        """Ride the degradation ladder for one bucket; re-raises when
        the error is not transient or the ladder is exhausted."""
        if not is_transient_error(exc):
            raise exc
        lane = lanes_state[lane_idx]
        if lane.current is job:
            lane.current = None
        _cleanup_job(job)
        key = _job_key(job.split)
        n = attempts.get(key, 0) + 1
        attempts[key] = n
        if n < max(1, policy.max_attempts):
            stats.retries += 1
            fault_metrics.counter(COMPACTION_BUCKET_RETRIES).inc()
            if key not in backoffs:
                backoffs[key] = policy.new_backoff()
            # deadline, not a sleep: only THIS bucket waits out its
            # jittered backoff; the other lanes keep streaming
            retry_job = _BucketJob(ctx, job.split)
            retry_job.ready_at = _time.monotonic() + \
                backoffs[key].next_ms() / 1000.0
            lane.queue.insert(0, retry_job)
            return
        if policy.fallback:
            stats.fallbacks += 1
            fault_metrics.counter(COMPACTION_BUCKET_FALLBACKS).inc()
            try:
                msg = _fallback_single_chip(job.split)
            except Exception:
                fault_metrics.counter(COMPACTION_BUCKET_FAILURES).inc()
                raise
            if msg is not None:
                messages.append(msg)
            return
        fault_metrics.counter(COMPACTION_BUCKET_FAILURES).inc()
        raise exc

    from paimon_tpu.compact.compact_action import table_span
    from paimon_tpu.metrics import (
        COMPACTION_DURATION_MS, COMPACTION_MESH_PADDED_ROWS,
        COMPACTION_MESH_STEPS, COMPACTION_WINDOW_MS,
    )
    from paimon_tpu.ops.merge import PATH_COUNTS, device_span, prep_span

    kernel = _window_kernel(mesh, ctx.num_lanes, ctx.num_key_lanes,
                            ctx.keep, axis)

    def run_step(step: List[Optional[Tuple]]) -> None:
        """One lock-step mesh step over the lanes' current windows:
        assemble, sort on the chips, take and emit each lane's winners.
        A window with a prefix-truncated key takes the exact host merge
        instead of the kernel."""
        device_rows: List[Optional[Tuple]] = [None] * n_dev
        host_windows: List[Tuple] = []
        failures: List[Tuple] = []
        stacks = None
        with prep_span(sum(it[0].num_rows for item in step
                           if item is not None for it in item[1])):
            for li, item in enumerate(step):
                if item is None:
                    continue
                job, items = item
                try:
                    operands = ctx.window_operands(items)
                except Exception as e:      # noqa: BLE001
                    failures.append((li, job, e))
                    continue
                if operands is None:
                    host_windows.append((li, job, items))
                else:
                    device_rows[li] = (job, *operands)
            n_max = max((e[1].num_rows for e in device_rows
                         if e is not None), default=0)
            if n_max:
                n_pad = _pad_size(n_max)
                stacks = _stack_windows(device_rows, n_pad, ctx.num_lanes)
        # outside the span: a failed bucket may run its whole single-chip
        # fallback here, and the host merge opens `merge_runs`' own spans
        for li, job, e in failures:
            _handle_bucket_failure(li, job, e)
        for li, job, items in host_windows:
            try:
                job.emit(ctx.merge_window_host(items))
            except Exception as e:          # noqa: BLE001
                _handle_bucket_failure(li, job, e)
        if stacks is None:
            return
        real = [e for e in device_rows if e is not None]
        rows = sum(e[1].num_rows for e in real)
        slots = n_dev * n_pad
        try:
            with _obs_span("compaction.window", cat="compaction",
                           group="compaction",
                           metric=COMPACTION_WINDOW_MS,
                           lanes=len(real), rows=n_max,
                           table=table.path), \
                    device_span("mesh", rows, slots,
                                4 * slots * (ctx.num_lanes + 4),
                                5 * slots, lanes=len(real)):
                perm, winner, _ = kernel(*stacks)
        except Exception as e:              # noqa: BLE001
            if not is_transient_error(e):
                # a program the compiler refuses (or a bug) is not a
                # lane failure: no bucket degrades to the single-chip
                # manager on its account
                raise
            # a device loss is a lane failure for every bucket in
            # flight this step: each rides its own ladder
            for li, entry in enumerate(device_rows):
                if entry is not None:
                    _handle_bucket_failure(li, entry[0], e)
            return
        PATH_COUNTS["device"] += len(real)
        fault_metrics.counter(COMPACTION_MESH_STEPS).inc()
        fault_metrics.counter(COMPACTION_MESH_PADDED_ROWS).inc(slots)
        for li, entry in enumerate(device_rows):
            if entry is None:
                continue
            job, wtable = entry[0], entry[1]
            try:
                job.emit(ctx.merge_window_device(wtable, perm[li],
                                                 winner[li]))
            except Exception as e:          # noqa: BLE001
                _handle_bucket_failure(li, job, e)
                continue
            stats.windows += 1
            stats.peak_window_rows = max(stats.peak_window_rows,
                                         wtable.num_rows)

    def run_lanes() -> None:
        while True:
            step: List[Optional[Tuple]] = []
            for li, lane in enumerate(lanes_state):
                try:
                    step.append(lane.next_window(finalize))
                except Exception as e:      # noqa: BLE001
                    failed = lane.current
                    if failed is None:
                        raise
                    _handle_bucket_failure(li, failed, e)
                    step.append(None)
            if any(w is not None for w in step):
                run_step(step)
                continue
            deadlines = [j.ready_at for lane in lanes_state
                         for j in lane.queue]
            if not deadlines and all(lane.current is None
                                     for lane in lanes_state):
                return
            # nothing runnable anywhere: every remaining job is inside
            # its backoff window — sleep to the earliest deadline
            # instead of spinning (only here does the loop ever wait)
            if deadlines:
                wait = min(deadlines) - _time.monotonic()
                if wait > 0:
                    from paimon_tpu.utils.backoff import wait_for
                    with _obs_span("compaction.backoff_wait",
                                   cat="compaction",
                                   pending=len(deadlines)):
                        wait_for(wait, what="compaction backoff")

    # the group phase, as `compact_table`'s other route has it: the root
    # and, inside it, the one task it hands out — the mesh run, here on
    # the calling thread (`duration_ms` / `table_ms` reads 1)
    with table_span(stats.buckets, n_dev, stats.input_rows), \
            _obs_span("compact.task", cat="compaction",
                      group="compaction", metric=COMPACTION_DURATION_MS,
                      route="mesh", lanes=n_dev, buckets=stats.buckets,
                      rows=stats.input_rows, lane_rows=stats.lane_rows,
                      skew=stats.skew):
        run_lanes()

    if not messages:
        _trace.maybe_export()
        return stats
    commit = FileStoreCommit(table.file_io, table.path, table.schema,
                             table.options, commit_user=commit_user,
                             branch=table.branch)
    if properties_provider is not None:
        commit.properties_provider = properties_provider
    stats.snapshot_id = commit.commit(messages, properties=properties)
    _trace.maybe_export()
    return stats
