"""FileStoreTable and its read/write builders.

reference: table/FileStoreTable.java, table/source/ReadBuilderImpl.java:49
(newScan:190, newRead:241), table/sink/BatchWriteBuilder.java,
TableWriteImpl.java:54, TableCommitImpl.java:78.
"""

from __future__ import annotations

import uuid
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

from paimon_tpu.core.commit import FileStoreCommit
from paimon_tpu.core.read import MergeFileSplitRead
from paimon_tpu.core.scan import DataSplit, FileStoreScan, ScanPlan
from paimon_tpu.core.write import CommitMessage, KeyValueFileStoreWrite
from paimon_tpu.fs import FileIO, get_file_io
from paimon_tpu.options import CoreOptions, Options
from paimon_tpu.predicate import Predicate
from paimon_tpu.schema.schema import Schema
from paimon_tpu.schema.schema_manager import SchemaManager
from paimon_tpu.schema.table_schema import TableSchema
from paimon_tpu.snapshot import (
    BranchManager, CommitKind, ConsumerManager, Snapshot, SnapshotManager,
    TagManager,
)
from paimon_tpu.snapshot.snapshot import BATCH_COMMIT_IDENTIFIER

__all__ = ["FileStoreTable", "BatchWriteBuilder", "StreamWriteBuilder",
           "ReadBuilder", "TableWrite", "TableCommit", "TableRead",
           "TableScan"]


class FileStoreTable:
    """A table backed by the file store at `path`."""

    def __init__(self, file_io: FileIO, path: str,
                 table_schema: TableSchema,
                 dynamic_options: Optional[Dict[str, str]] = None,
                 branch: str = "main"):
        self.path = path.rstrip("/")
        opts = dict(table_schema.options)
        if dynamic_options:
            opts.update({k: str(v) for k, v in dynamic_options.items()})
        self.schema = table_schema.copy(opts) \
            if dynamic_options else table_schema
        self.options = CoreOptions(Options(opts))
        if self.options.get(CoreOptions.STORE_BREAKER_ENABLED) or \
                self.options.get(CoreOptions.READ_HEDGE_ENABLED):
            # tail tolerance sits closest to the store, UNDER the
            # caching wrap below: cache hits never pay breaker/hedge
            # accounting, and every real store attempt does
            from paimon_tpu.fs.resilience import maybe_wrap_resilience
            file_io = maybe_wrap_resilience(file_io, self.options)
        disk_dir = self.options.get(CoreOptions.CACHE_DISK_DIR)
        if self.options.get(CoreOptions.READ_CACHE_RANGE) or disk_dir:
            from paimon_tpu.fs.caching import (
                CachingFileIO, shared_cache_state, shared_disk_tier,
            )
            range_bytes = self.options.get(
                CoreOptions.READ_CACHE_RANGE_MAX_BYTES) \
                if self.options.get(CoreOptions.READ_CACHE_RANGE) else 0
            if not isinstance(file_io, CachingFileIO):
                # range-only cache: whole-file capacity 0 keeps
                # read_bytes pass-through, ranged reads (mosaic
                # footers/blobs) hit the (path, offset, len) LRU.
                # The state is the PROCESS-WIDE shared tier: every
                # table instance (each table.copy(), every concurrent
                # serving request) joins one size-bounded cache
                # instead of warming a private one per read.  With
                # cache.disk.dir set, memory misses (capacity 0 means
                # every whole-file read) demote to the host-SSD tier
                # and are served from it before the object store
                file_io = CachingFileIO(
                    file_io, capacity_bytes=0,
                    range_cache_bytes=range_bytes,
                    state=shared_cache_state(0, range_bytes))
            if disk_dir:
                file_io.state.attach_disk(
                    shared_disk_tier(disk_dir, self.options.get(
                        CoreOptions.CACHE_DISK_MAX_BYTES)),
                    promote_hits=self.options.get(
                        CoreOptions.CACHE_DISK_PROMOTE_HITS))
        self.file_io = file_io
        self.branch = branch if branch != "main" else self.options.branch
        self.snapshot_manager = SnapshotManager(file_io, self.path,
                                                self.branch)
        self.schema_manager = SchemaManager(file_io, self.path, self.branch)
        self.tag_manager = TagManager(file_io, self.path)
        self.branch_manager = BranchManager(file_io, self.path)
        self.consumer_manager = ConsumerManager(file_io, self.path)

    # -- creation / loading --------------------------------------------------

    @staticmethod
    def create(path: str, schema: Schema,
               file_io: Optional[FileIO] = None) -> "FileStoreTable":
        fio = file_io or get_file_io(path)
        ts = SchemaManager(fio, path).create_table(schema)
        return FileStoreTable(fio, path, ts)

    @staticmethod
    def load(path: str, file_io: Optional[FileIO] = None,
             dynamic_options: Optional[Dict[str, str]] = None
             ) -> "FileStoreTable":
        fio = file_io or get_file_io(path)
        branch = "main"
        if dynamic_options and "branch" in dynamic_options:
            branch = dynamic_options["branch"]
        ts = SchemaManager(fio, path, branch).latest()
        if ts is None:
            raise FileNotFoundError(f"No table at {path}")
        return FileStoreTable(fio, path, ts, dynamic_options, branch)

    def copy(self, dynamic_options: Dict[str, str]) -> "FileStoreTable":
        base = self.schema_manager.latest()
        return FileStoreTable(self.file_io, self.path, base,
                              dynamic_options, self.branch)

    # -- metadata ------------------------------------------------------------

    @property
    def name(self) -> str:
        return self.path.rstrip("/").split("/")[-1]

    @property
    def primary_keys(self) -> List[str]:
        return self.schema.primary_keys

    @property
    def partition_keys(self) -> List[str]:
        return self.schema.partition_keys

    def row_type(self):
        return self.schema.logical_row_type()

    def arrow_schema(self) -> pa.Schema:
        return self.schema.to_arrow_schema()

    def latest_snapshot(self) -> Optional[Snapshot]:
        return self.snapshot_manager.latest_snapshot()

    # -- builders ------------------------------------------------------------

    def new_batch_write_builder(self) -> "BatchWriteBuilder":
        return BatchWriteBuilder(self)

    def new_stream_write_builder(self) -> "StreamWriteBuilder":
        return StreamWriteBuilder(self)

    def new_read_builder(self) -> "ReadBuilder":
        return ReadBuilder(self)

    def new_distributed_write(self, base_user: str = "writer",
                              process_index: Optional[int] = None,
                              process_count: Optional[int] = None):
        """This process's slice of the multi-host write plane
        (parallel/distributed.py): sharded (partition,bucket)
        ownership over a JAX multi-host mesh, arbitrated commits
        (multihost.commit.arbitration), pinned-snapshot cross-host
        scans and online bucket rescale.  process_index/count default
        from the initialized jax distributed runtime
        (parallel/multihost.initialize)."""
        from paimon_tpu.parallel.distributed import DistributedWritePlane
        return DistributedWritePlane(self, base_user=base_user,
                                     process_index=process_index,
                                     process_count=process_count)

    def new_scan(self) -> FileStoreScan:
        return FileStoreScan(self.file_io, self.path, self.schema,
                             self.options, self.branch)

    # -- convenience ---------------------------------------------------------

    def to_arrow(self, projection: Optional[List[str]] = None,
                 predicate: Optional[Predicate] = None,
                 with_row_ids: bool = False,
                 limit: Optional[int] = None,
                 aggregate=None) -> pa.Table:
        """`aggregate` (ops.scan_agg.ScanAggregate): the splits' partial
        aggregates in place of rows, see `ReadBuilder.with_aggregate`."""
        # request.timeout entry point covering the PLAN too: the
        # manifest walk is store IO and must ride the same deadline
        # as the read (TableRead.to_arrow's own entry scope only
        # guards reads over pre-built plans)
        from paimon_tpu.obs.trace import span
        from paimon_tpu.utils.deadline import deadline_scope
        # the scan's root span: plan, every split (on its pool worker)
        # and the assembly walk back to it
        with deadline_scope(self.options.get(
                CoreOptions.REQUEST_TIMEOUT), entry=True), \
                span("scan.to_arrow", cat="scan", table=self.path):
            rb = self.new_read_builder()
            if projection:
                rb = rb.with_projection(projection)
            if predicate is not None:
                rb = rb.with_filter(predicate)
            if with_row_ids:
                rb = rb.with_row_ids()
            if limit is not None:
                # pushed LIMIT: the pipelined read stops admitting
                # splits once enough rows are buffered
                rb = rb.with_limit(limit)
            if aggregate is not None:
                rb = rb.with_aggregate(aggregate)
            with span("scan.plan", cat="scan"):
                splits = rb.new_scan().plan().splits
            return rb.new_read().to_arrow(splits)

    def compact(self, full: bool = False,
                partition_filter: Optional[dict] = None,
                group_filter=None, commit_user: Optional[str] = None,
                properties: Optional[Dict[str, str]] = None,
                properties_provider=None) -> Optional[int]:
        """Trigger compaction and commit the result
        (reference flink CompactAction, but engine-free here).
        `group_filter` is a (partition, bucket) -> bool scheduling
        predicate — the sharded maintenance plane passes its
        ownership filter so each host compacts only its own groups;
        `commit_user`/`properties`/`properties_provider` land on the
        COMPACT snapshot."""
        from paimon_tpu.compact.compact_action import compact_table
        return compact_table(self, full=full,
                             partition_filter=partition_filter,
                             group_filter=group_filter,
                             commit_user=commit_user,
                             properties=properties,
                             properties_provider=properties_provider)

    def rescale_buckets(self, new_buckets: int, mesh=None,
                        properties: Optional[Dict[str, str]] = None
                        ) -> Optional[int]:
        """Change a fixed-bucket pk table's bucket count: the device
        mesh computes the row routing (abs(hash % B) + all_to_all
        repartition), the host rewrites files and commits an overwrite
        (reference rescale-bucket procedure via ChannelComputer).
        `properties` are stamped on the overwrite snapshot (the
        distributed write plane records its ownership-map generation
        this way)."""
        from paimon_tpu.parallel.rescale import rescale_table_buckets
        return rescale_table_buckets(self, new_buckets, mesh=mesh,
                                     properties=properties)

    def compact_manifests(self, force: bool = True,
                          commit_user: Optional[str] = None,
                          properties: Optional[Dict[str, str]] = None,
                          properties_provider=None) -> Optional[int]:
        """Manifest full-compaction: fold the accumulated delta
        manifests into sorted, partition-clustered base manifests
        (maintenance/manifest_compact.py).  `force=False` runs only
        when the manifest.full-compaction.threshold trigger fires."""
        from paimon_tpu.maintenance.manifest_compact import (
            compact_manifests,
        )
        return compact_manifests(self, force=force,
                                 commit_user=commit_user,
                                 properties=properties,
                                 properties_provider=properties_provider)

    def rescale_postpone(self) -> Optional[int]:
        """Move bucket-postpone staging data into real buckets (reference
        postpone/ rescale job; bucket=-2 tables)."""
        from paimon_tpu.compact.compact_action import rescale_postpone
        return rescale_postpone(self)

    def sort_compact(self, order_by: List[str],
                     strategy: str = "zorder") -> Optional[int]:
        """Cluster an append table by z-order or lexicographic order
        (reference sort-compact action, sort/zorder/ZIndexer.java)."""
        from paimon_tpu.compact.compact_action import sort_compact
        return sort_compact(self, order_by, strategy)

    def system_table(self, name: str) -> pa.Table:
        """Load a system table ('snapshots', 'files', 'audit_log', ...)
        as Arrow (reference table/system/SystemTableLoader.java)."""
        from paimon_tpu.table.system import load_system_table
        return load_system_table(self, name)

    def sync_iceberg(self, committer=None) -> Optional[str]:
        """Export the current snapshot as Iceberg v2 metadata under
        <table>/metadata/ (reference iceberg/IcebergCommitCallback);
        `committer` also publishes it to an Iceberg REST catalog
        (reference IcebergRestMetadataCommitter)."""
        from paimon_tpu.iceberg import sync_iceberg
        return sync_iceberg(self, committer=committer)

    def analyze(self, columns: Optional[List[str]] = None) -> Optional[int]:
        """ANALYZE TABLE: compute and persist table/column statistics
        (reference stats/StatsFileHandler)."""
        from paimon_tpu.stats import analyze_table
        return analyze_table(self, columns)

    def statistics(self) -> Optional[Dict]:
        from paimon_tpu.stats import read_statistics
        return read_statistics(self)

    def delete_where(self, predicate: Predicate) -> Optional[int]:
        """Row-level DELETE: deletion vectors on append tables, -D
        records on primary-key tables (reference DeleteAction /
        BucketedDvMaintainer)."""
        from paimon_tpu.index.dv_maintainer import delete_where
        return delete_where(self, predicate)

    # -- row tracking / data evolution ---------------------------------------

    def update_columns(self, row_ids, updates) -> Optional[int]:
        """Column-level UPDATE by row id on a row-tracked append table:
        only the touched columns of the touched row ranges are rewritten
        as evolution files (reference append/dataevolution/,
        operation/DataEvolutionSplitRead.java)."""
        from paimon_tpu.core.row_tracking import update_columns
        return update_columns(self, row_ids, updates)

    def delete_by_row_ids(self, row_ids) -> Optional[int]:
        """DELETE by row id: pure range arithmetic into deletion
        vectors, no data reads (reference row-id keyed append DVs)."""
        from paimon_tpu.core.row_tracking import delete_by_row_ids
        return delete_by_row_ids(self, row_ids)

    def global_index(self, column: str, rebuild: bool = False):
        """Sorted key -> row-id global index over a row-tracked append
        table (reference paimon-common/.../globalindex/sorted/)."""
        from paimon_tpu.index.global_index import SortedGlobalIndex
        return SortedGlobalIndex.load_or_build(self, column,
                                               rebuild=rebuild)

    # -- maintenance ---------------------------------------------------------

    def expire_snapshots(self, retain_max: Optional[int] = None,
                         retain_min: Optional[int] = None,
                         older_than_ms: Optional[int] = None,
                         dry_run: bool = False,
                         min_retained_snapshot_id: Optional[int] = None):
        """reference operation/ExpireSnapshotsImpl.java."""
        from paimon_tpu.maintenance import expire_snapshots
        return expire_snapshots(
            self, retain_max=retain_max, retain_min=retain_min,
            older_than_ms=older_than_ms, dry_run=dry_run,
            min_retained_snapshot_id=min_retained_snapshot_id)

    def remove_orphan_files(self, older_than_ms: Optional[int] = None,
                            dry_run: bool = False,
                            now_ms: Optional[int] = None,
                            incremental: bool = False):
        """reference operation/OrphanFilesClean.java; `incremental`
        rides the last clean sweep's watermark (maintenance/orphan.py)."""
        from paimon_tpu.maintenance import remove_orphan_files
        return remove_orphan_files(self, older_than_ms=older_than_ms,
                                   dry_run=dry_run, now_ms=now_ms,
                                   incremental=incremental)

    def fsck(self, snapshot_id: Optional[int] = None,
             all_snapshots: bool = True, deep: bool = False,
             incremental: bool = False, stamp_watermark: bool = False):
        """Verify the snapshot→manifest→file graph; returns an
        FsckReport of typed violations (maintenance/fsck.py).
        `incremental` verifies only the delta since the last clean
        sweep's watermark; `stamp_watermark` records a clean run."""
        from paimon_tpu.maintenance import fsck
        return fsck(self, snapshot_id=snapshot_id,
                    all_snapshots=all_snapshots, deep=deep,
                    incremental=incremental,
                    stamp_watermark=stamp_watermark)

    def expire_partitions(self, expiration_ms: Optional[int] = None,
                          now_ms: Optional[int] = None,
                          dry_run: bool = False):
        """reference operation/PartitionExpire.java."""
        from paimon_tpu.maintenance import expire_partitions
        return expire_partitions(self, expiration_ms=expiration_ms,
                                 now_ms=now_ms, dry_run=dry_run)

    def mark_partitions_done(self, partitions):
        """Run the configured partition.mark-done-action(s) — write
        `_SUCCESS` markers etc. (reference
        flink/procedure/MarkPartitionDoneProcedure.java)."""
        from paimon_tpu.maintenance import mark_partitions_done
        return mark_partitions_done(self, partitions)

    def create_tag(self, name: str, snapshot_id: Optional[int] = None):
        snap = (self.snapshot_manager.snapshot(snapshot_id)
                if snapshot_id is not None
                else self.snapshot_manager.latest_snapshot())
        if snap is None:
            raise ValueError("Table has no snapshot to tag")
        self.tag_manager.create_tag(snap, name)
        self.fire_tag_callbacks(name, snap.id)

    def fire_tag_callbacks(self, name: str, snapshot_id: int):
        """Invoke tag.callbacks (also called by auto-tag creation —
        reference wires TagCallbacks into TagAutoManager too)."""
        for cb in self._loaded_tag_callbacks():
            cb.call(self, name, snapshot_id)

    def _loaded_tag_callbacks(self):
        if not hasattr(self, "_tag_callbacks_cache"):
            from paimon_tpu.utils.callbacks import load_callbacks
            self._tag_callbacks_cache = load_callbacks(
                self.options, "tag.callbacks", "tag.callback.#.param")
        return self._tag_callbacks_cache

    def delete_tag(self, name: str):
        self.tag_manager.delete_tag(name)

    def create_branch(self, name: str, tag_name: Optional[str] = None):
        snap = self.tag_manager.get_tag(tag_name) if tag_name else None
        self.branch_manager.create_branch(name, from_snapshot=snap)

    def delete_branch(self, name: str):
        self.branch_manager.drop_branch(name)

    def rename_branch(self, old: str, new: str):
        self.branch_manager.rename_branch(old, new)

    def fast_forward(self, branch_name: str):
        self.branch_manager.fast_forward(branch_name)

    def rollback_to(self, snapshot_id: int):
        """Delete snapshots newer than `snapshot_id`
        (reference table/RollbackHelper.java)."""
        latest = self.snapshot_manager.latest_snapshot_id()
        if latest is None or snapshot_id > latest:
            raise ValueError(f"Cannot rollback to {snapshot_id}")
        if not self.snapshot_manager.snapshot_exists(snapshot_id):
            raise ValueError(f"Snapshot {snapshot_id} does not exist")
        for i in range(latest, snapshot_id, -1):
            self.snapshot_manager.delete_snapshot(i)
        self.snapshot_manager.commit_latest_hint(snapshot_id)


class BatchWriteBuilder:
    def __init__(self, table: FileStoreTable):
        self.table = table
        self.commit_user = str(uuid.uuid4())
        self._overwrite: Optional[dict] = None
        self._static_partition: Optional[dict] = None

    def with_overwrite(self, static_partition: Optional[dict] = None
                       ) -> "BatchWriteBuilder":
        self._overwrite = static_partition or {}
        return self

    def new_write(self, apply_defaults: bool = True) -> "TableWrite":
        """`apply_defaults=False` is for INTERNAL rewrite paths
        (rescale compaction, DV retractions): those round-trip stored
        rows and must be value-preserving — historical NULLs must not
        pick up fields.*.default-value."""
        return TableWrite(self.table, self.commit_user,
                          apply_defaults=apply_defaults)

    def new_commit(self) -> "TableCommit":
        return TableCommit(self.table, self.commit_user, self._overwrite)


class StreamWriteBuilder:
    """Checkpoint-driven streaming writes with exactly-once commits keyed
    by commit identifier (reference table/sink/StreamWriteBuilder.java +
    flink/sink/CommitterOperator.java:196: on checkpoint complete, commit
    every pending identifier not yet committed by this user).

    Usage:
        wb = table.new_stream_write_builder().with_commit_user("job-7")
        w, c = wb.new_write(), wb.new_commit()
        w.write_dicts(batch); msgs = w.prepare_commit()
        c.commit(msgs, commit_identifier=checkpoint_id)
        # on recovery: replay pending checkpoints through
        # c.filter_committed([...]) to drop already-committed ones
    """

    def __init__(self, table: FileStoreTable):
        self.table = table
        self.commit_user = str(uuid.uuid4())

    def with_commit_user(self, commit_user: str) -> "StreamWriteBuilder":
        """A STABLE user id is what makes replay dedup work across
        restarts; defaults to a random uuid like the reference."""
        self.commit_user = commit_user
        return self

    def new_write(self) -> "TableWrite":
        return TableWrite(self.table, self.commit_user)

    def new_commit(self) -> "TableCommit":
        return TableCommit(self.table, self.commit_user)


class TableWrite:
    def __init__(self, table: FileStoreTable, commit_user: str,
                 apply_defaults: bool = True):
        self.table = table
        self._apply_defaults = apply_defaults
        if apply_defaults and table.options.field_default_values() and \
                table.options.merge_engine in ("partial-update",
                                               "aggregation"):
            # NULL carries meaning for these engines (keep existing /
            # skip aggregation); a write-time default fill would
            # silently clobber stored values (reference rejects the
            # combination too)
            raise ValueError(
                "fields.*.default-value is not supported with the "
                f"{table.options.merge_engine} merge engine")
        scan = table.new_scan()

        def restore(partition: Tuple, bucket: int) -> int:
            return scan.max_sequence_number(partition, bucket)

        def bucket_files_map():
            snapshot = table.snapshot_manager.latest_snapshot()
            if snapshot is None:
                return {}
            out = {}
            for e in scan.read_entries(snapshot):
                part = scan._partition_codec.from_bytes(e.partition)
                out.setdefault((part, e.bucket), []).append(e.file)
            return out

        if table.primary_keys:
            self._write = KeyValueFileStoreWrite(
                table.file_io, table.path, table.schema, table.options,
                restore_max_seq=restore, branch=table.branch,
                bucket_files_map=bucket_files_map,
                schema_manager=table.schema_manager)
            if table.schema.cross_partition_update():
                # pk does not cover the partition keys: route partition
                # changes as -D old + +I new via the global index
                # (reference crosspartition/GlobalIndexAssigner)
                from paimon_tpu.core.cross_partition import (
                    CrossPartitionUpsertWrite,
                )
                self._write = CrossPartitionUpsertWrite(self._write, table)
        else:
            from paimon_tpu.core.append import AppendOnlyFileStoreWrite
            self._write = AppendOnlyFileStoreWrite(
                table.file_io, table.path, table.schema, table.options,
                restore_max_seq=restore)

    def write_arrow(self, data: pa.Table,
                    row_kinds: Optional[np.ndarray] = None,
                    buckets=None):
        from paimon_tpu.obs.trace import span
        # one batch write has three calls and so three root spans:
        # write.batch, write.prepare, write.commit
        with span("write.batch", cat="write", rows=data.num_rows):
            data = self._apply_field_defaults(data)
            if buckets is not None:
                self._write.write_arrow(data, row_kinds, buckets=buckets)
            else:
                self._write.write_arrow(data, row_kinds)

    def _apply_field_defaults(self, data: pa.Table) -> pa.Table:
        """NULL incoming values become the column's configured default
        (fields.<col>.default-value — reference DefaultValueRow applied
        on the write path)."""
        if not self._apply_defaults:
            return data
        defaults = getattr(self, "_field_defaults", None)
        if defaults is None:
            defaults = self.table.options.field_default_values()
            self._field_defaults = defaults
        if not defaults:
            return data
        import pyarrow.compute as pc
        schema = self.table.arrow_schema()
        for col, raw in defaults.items():
            if col not in data.column_names:
                continue
            arr = data.column(col)
            if arr.null_count == 0:
                continue
            scalar = pa.scalar(raw).cast(schema.field(col).type)
            data = data.set_column(data.column_names.index(col), col,
                                   pc.fill_null(arr, scalar))
        return data

    def set_delta_listener(self, listener):
        """Serving-plane hook (service/delta.py): `listener(partition,
        bucket, table, kinds, seqs)` fires for every buffered batch on
        the single-threaded write caller, after sequence reservation —
        the hot delta tier publishes lookup visibility from it.  Only
        the primary-key fixed-bucket write path supports it (the
        ServingWriter gates eligibility)."""
        from paimon_tpu.core.write import KeyValueFileStoreWrite
        if not isinstance(self._write, KeyValueFileStoreWrite):
            raise ValueError(
                "delta listener requires the primary-key write path")
        self._write.delta_listener = listener

    def write_pandas(self, df):
        self.write_arrow(pa.Table.from_pandas(df, preserve_index=False))

    def write_dicts(self, rows: Sequence[dict],
                    row_kinds: Optional[Sequence[int]] = None):
        from paimon_tpu.core.write import dicts_to_arrow
        table, kinds = dicts_to_arrow(self.table.arrow_schema(), rows,
                                      row_kinds)
        self.write_arrow(table, kinds)

    def prepare_commit(self) -> List[CommitMessage]:
        """Barrier over the pipelined flush pool
        (parallel/write_pipeline.py): drains every in-flight bucket
        flush, re-raising the first worker error, then returns the
        accumulated commit messages."""
        from paimon_tpu.obs.trace import span
        with span("write.prepare", cat="write"):
            return self._write.prepare_commit()

    def close(self):
        """Shuts down the flush pool (joining its workers) and drops
        buffered/spilled state.  Always call close — also on failure —
        or the writer's pool threads outlive the write; prefer the
        context-manager form: ``with wb.new_write() as w: ...``."""
        self._write.close()

    def __enter__(self) -> "TableWrite":
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class TableCommit:
    def __init__(self, table: FileStoreTable, commit_user: str,
                 overwrite: Optional[dict] = None):
        self.table = table
        self._commit = FileStoreCommit(
            table.file_io, table.path, table.schema, table.options,
            commit_user=commit_user, branch=table.branch)
        self._overwrite = overwrite
        self._callbacks = None        # loaded lazily, once

    def commit(self, messages: Sequence[CommitMessage],
               commit_identifier: int = BATCH_COMMIT_IDENTIFIER,
               watermark: Optional[int] = None,
               properties: Optional[Dict[str, str]] = None
               ) -> Optional[int]:
        """`watermark` (epoch millis) records event-time progress in the
        snapshot — it only ever advances — feeding watermark-mode auto
        tags and the snapshots system table (reference
        TableCommitImpl#withWatermark).  `properties` are stored on the
        snapshot itself, atomically with the data — the stream daemon
        checkpoints its source offsets this way (exactly-once across
        restarts); ignored on the overwrite path.

        A configured `request.timeout` installs an end-to-end deadline
        (entry point): retry/CAS backoffs stop sleeping once it is
        spent and the snapshot CAS is never attempted past it — a
        timed-out commit raises instead of orphan-committing."""
        from paimon_tpu.obs.trace import span
        from paimon_tpu.utils.deadline import deadline_scope
        with deadline_scope(self.table.options.get(
                CoreOptions.REQUEST_TIMEOUT), entry=True), \
                span("write.commit", cat="write",
                     messages=len(messages)):
            return self._commit_with_deadline(
                messages, commit_identifier, watermark, properties)

    def _commit_with_deadline(self, messages, commit_identifier,
                              watermark, properties) -> Optional[int]:
        index_entries = [e for m in messages
                         for e in getattr(m, "index_entries", [])]
        # empty batch commits produce no snapshot unless forced
        # (reference snapshot.ignore-empty-commit, default on for batch
        # writers; streaming keeps empty snapshots for exactly-once
        # progress tracking)
        ignore_empty = self.table.options.get(
            CoreOptions.SNAPSHOT_IGNORE_EMPTY_COMMIT)
        if ignore_empty is None:
            ignore_empty = commit_identifier == BATCH_COMMIT_IDENTIFIER
        if ignore_empty and not messages and not index_entries and \
                self._overwrite is None and not self.table.options.get(
                    CoreOptions.COMMIT_FORCE_CREATE_SNAPSHOT):
            return None
        if self._overwrite is not None:
            sid = self._commit.overwrite(
                messages, partition_filter=self._overwrite or None,
                commit_identifier=commit_identifier,
                index_entries=index_entries or None,
                watermark=watermark)
        else:
            sid = self._commit.commit(
                messages, commit_identifier,
                index_entries=index_entries or None,
                watermark=watermark, properties=properties,
                # a streaming empty commit still snapshots so the
                # identifier is durable for exactly-once replay dedup
                force_create=not ignore_empty)
        if sid is not None and self.table.options.get(
                CoreOptions.TAG_AUTOMATIC_CREATION) not in (None, "none"):
            # reference TagAutoManager rides the commit callback
            from paimon_tpu.maintenance.tag_auto import maybe_create_tags
            maybe_create_tags(self.table)
        if sid is not None:
            # user commit callbacks run post-CAS (reference
            # CommitCallback via commit.callbacks); loaded once per
            # TableCommit, not per commit
            if self._callbacks is None:
                from paimon_tpu.utils.callbacks import load_callbacks
                self._callbacks = load_callbacks(
                    self.table.options, "commit.callbacks",
                    "commit.callback.#.param")
            for cb in self._callbacks:
                cb.call(self.table, sid, messages)
            if commit_identifier == BATCH_COMMIT_IDENTIFIER and \
                    self.table.schema.partition_keys and \
                    self.table.options.get(
                        CoreOptions.PARTITION_END_INPUT_TO_DONE):
                # reference partition.end-input-to-done: a finished
                # batch input marks its partitions done. Pass raw
                # partition TUPLES — mark_partitions_done applies
                # partition.default-name handling for null/blank values
                parts = {tuple(m.partition) for m in messages
                         if m.partition}
                if parts:
                    self.table.mark_partitions_done(sorted(parts))
        return sid

    def filter_committed(self, identifiers: Sequence[int]) -> List[int]:
        return self._commit.filter_committed(identifiers)

    def close(self):
        pass


class ReadBuilder:
    """reference table/source/ReadBuilderImpl.java:49."""

    def __init__(self, table: FileStoreTable):
        self.table = table
        self._projection: Optional[List[str]] = None
        self._predicate: Optional[Predicate] = None
        self._partition_filter: Optional[dict] = None
        self._buckets: Optional[List[int]] = None
        self._limit: Optional[int] = None
        self._aggregate = None

    def with_projection(self, columns: List[str]) -> "ReadBuilder":
        self._projection = list(columns)
        return self

    def with_filter(self, predicate: Predicate) -> "ReadBuilder":
        self._predicate = predicate
        return self

    def with_partition_filter(self, spec: dict) -> "ReadBuilder":
        self._partition_filter = spec
        return self

    def with_buckets(self, buckets: List[int]) -> "ReadBuilder":
        self._buckets = buckets
        return self

    def with_limit(self, limit: int) -> "ReadBuilder":
        self._limit = limit
        return self

    def with_aggregate(self, aggregate) -> "ReadBuilder":
        """Aggregate below the merge: the read returns, per split, one
        row a group of partial sums / counts / minima / maxima
        (`ops.scan_agg.ScanAggregate` says which; its module says what
        the partial columns are) in place of the split's rows, with the
        filter applied exactly.  Buckets are key-disjoint, so the caller
        adds the splits' partials by group.  For a primary-key table of
        the deduplicate or first-row engine (`supports_aggregate`; else
        this raises), after `aggregate.unsupported(...)` said None."""
        if not self.supports_aggregate():
            raise ValueError(
                "with_aggregate needs a primary-key table of the "
                "deduplicate or first-row engine, read without "
                "record-level expiry or the sequence-number column")
        self._aggregate = aggregate
        return self

    def supports_aggregate(self) -> bool:
        """Whether this table's merge keeps one whole row a key and the
        read has no option that works on the merged rows."""
        from paimon_tpu.options import MergeEngine
        opts = self.table.options
        return bool(self.table.primary_keys) and opts.merge_engine in (
            MergeEngine.DEDUPLICATE, MergeEngine.FIRST_ROW) \
            and not opts.record_level_expire_time_ms \
            and not opts.get(CoreOptions.TABLE_READ_SEQUENCE_NUMBER)

    def with_row_ids(self, flag: bool = True) -> "ReadBuilder":
        """Materialize `_ROW_ID` on append-table reads (row tracking)."""
        self._with_row_ids = flag
        return self

    def new_scan(self) -> "TableScan":
        return TableScan(self)

    def new_stream_scan(self):
        from paimon_tpu.table.stream_scan import DataTableStreamScan
        return DataTableStreamScan(self)

    def new_read(self) -> "TableRead":
        return TableRead(self)

    def read_type(self):
        rt = self.table.row_type()
        if self._projection:
            return rt.project(self._projection)
        return rt


def with_fallback_partitions(table, plan: ScanPlan,
                             fallback_branch: str,
                             partition_filter=None, predicate=None,
                             buckets=None) -> ScanPlan:
    """Partition-level branch fallback: partitions with no data in the
    current branch read from `scan.fallback-branch` instead (reference
    table/FallbackReadFileStoreTable.java — e.g. a streaming branch
    backfilled by a batch branch).  Shared by batch scans and the
    chain-table streaming initial full load."""
    fb = FileStoreTable.load(
        table.path, table.file_io,
        dynamic_options={"branch": fallback_branch,
                         "scan.fallback-branch": ""})
    rb = fb.new_read_builder()
    if partition_filter:
        rb = rb.with_partition_filter(partition_filter)
    if predicate is not None:
        rb = rb.with_filter(predicate)
    if buckets:
        rb = rb.with_buckets(buckets)
    fb_plan = rb.new_scan().plan()
    have = {tuple(s.partition) for s in plan.splits}
    from dataclasses import replace as _dc_replace
    extra = [_dc_replace(s, for_streaming=plan.streaming)
             for s in fb_plan.splits
             if tuple(s.partition) not in have]
    return ScanPlan(plan.snapshot_id, list(plan.splits) + extra,
                    streaming=plan.streaming)


class TableScan:
    def __init__(self, builder: ReadBuilder):
        self.builder = builder
        self._scan = builder.table.new_scan()
        if builder._partition_filter:
            self._scan.with_partition_filter(builder._partition_filter)
        if builder._buckets:
            self._scan.with_buckets(builder._buckets)
        if builder._predicate is not None:
            pk = set(builder.table.schema.trimmed_primary_keys())
            fields = set(builder._predicate.fields())
            if fields and fields <= pk:
                self._scan.with_key_filter(builder._predicate)
            else:
                self._scan.with_value_filter(builder._predicate)

    def plan(self, snapshot_id: Optional[int] = None,
             tag_name: Optional[str] = None) -> ScanPlan:
        table = self.builder.table
        snapshot = None
        opts = table.options
        between = opts.get(CoreOptions.INCREMENTAL_BETWEEN)
        if between is not None:
            return self._plan_incremental(between)
        tag_to_snap = opts.get(
            CoreOptions.INCREMENTAL_BETWEEN_TAG_TO_SNAPSHOT)
        if tag_to_snap is not None:
            return self._plan_incremental_tag_diff(tag_to_snap)
        if tag_name is None:
            tag_name = opts.get(CoreOptions.SCAN_TAG_NAME)
        if snapshot_id is None:
            snapshot_id = opts.get(CoreOptions.SCAN_SNAPSHOT_ID)
        ts_millis = opts.get(CoreOptions.SCAN_TIMESTAMP_MILLIS)
        if tag_name is not None:
            snapshot = table.tag_manager.get_tag(tag_name)
        elif snapshot_id is not None:
            snapshot = table.snapshot_manager.snapshot(snapshot_id)
        elif ts_millis is not None:
            snapshot = table.snapshot_manager.earlier_or_equal_time_mills(
                ts_millis)
            if snapshot is None:
                return ScanPlan(None, [])
        plan = self._scan.plan(snapshot)
        fallback = opts.get(CoreOptions.SCAN_FALLBACK_BRANCH)
        if fallback and fallback != table.branch:
            plan = self._with_fallback_partitions(plan, fallback)
        if opts.get(CoreOptions.SCAN_PLAN_SORT_PARTITION):
            # raw partition values (typed order, not lexicographic str);
            # None sorts first within its position
            plan = ScanPlan(
                plan.snapshot_id,
                sorted(plan.splits,
                       key=lambda s: tuple((v is not None, v)
                                           for v in s.partition)),
                streaming=plan.streaming)
        return plan

    def _with_fallback_partitions(self, plan: ScanPlan,
                                  fallback_branch: str) -> ScanPlan:
        return with_fallback_partitions(
            self.builder.table, plan, fallback_branch,
            partition_filter=self.builder._partition_filter,
            predicate=self.builder._predicate,
            buckets=self.builder._buckets)

    def _plan_incremental(self, between: str) -> ScanPlan:
        """Batch incremental read of the deltas in (start, end]
        (reference IncrementalStartingScanner; option
        incremental-between='start,end' — snapshot ids or tag names)."""
        table = self.builder.table

        def resolve(token: str) -> int:
            token = token.strip()
            if token.lstrip("-").isdigit():
                return int(token)
            return table.tag_manager.get_tag(token).id

        parts = between.split(",")
        if len(parts) != 2:
            raise ValueError("incremental-between must be 'start,end'")
        start, end = resolve(parts[0]), resolve(parts[1])
        if end < start:
            raise ValueError(f"incremental-between end {end} < start "
                             f"{start}")
        sm = table.snapshot_manager
        earliest = sm.earliest_snapshot_id()
        latest = sm.latest_snapshot_id()
        if latest is None or end > latest or \
                (earliest is not None and start + 1 < earliest):
            raise ValueError(
                f"incremental-between ({start}, {end}] outside the "
                f"available snapshot range [{earliest}, {latest}]")
        # collect the whole range's delta entries and group them per
        # bucket so pk tables MERGE across snapshots (a key updated
        # twice in the range emits once; reference
        # IncrementalStartingScanner groups per partition/bucket)
        from paimon_tpu.manifest import FileKind
        entries = []
        for sid in range(start + 1, end + 1):
            snap = sm.snapshot(sid)
            if snap.commit_kind != CommitKind.APPEND:
                continue
            metas = self._scan.manifest_list.read(
                snap.delta_manifest_list)
            entries.extend(e for e in self._scan._read_manifests(metas)
                           if e.kind == FileKind.ADD)
        return ScanPlan(end, self._scan.generate_splits(end, entries))

    def _plan_incremental_tag_diff(self, spec: str) -> ScanPlan:
        """'tagName,endSnapshotId': the DATA-FILE DIFF between the
        tag's pinned snapshot and the end snapshot. Unlike the
        range walk in _plan_incremental, this survives expiry of every
        intermediate snapshot — the tag pins its snapshot and the end
        snapshot exists, which is the whole point of a tag-based start
        (reference IncrementalTagStartingScanner; option
        incremental-between-tag-to-snapshot). The first token is ALWAYS
        a tag name, never a snapshot id."""
        table = self.builder.table
        parts = spec.split(",")
        if len(parts) != 2:
            raise ValueError(
                "incremental-between-tag-to-snapshot must be "
                "'tagName,snapshotId'")
        tag_snap = table.tag_manager.get_tag(parts[0].strip())
        end = int(parts[1].strip())
        if end < tag_snap.id:
            raise ValueError(
                f"end snapshot {end} predates tag "
                f"{parts[0].strip()!r} (snapshot {tag_snap.id})")
        end_snap = table.snapshot_manager.snapshot(end)
        base = {(e.partition, e.bucket, e.file.file_name)
                for e in self._scan.read_entries(tag_snap)}
        entries = [e for e in self._scan.read_entries(end_snap)
                   if (e.partition, e.bucket, e.file.file_name)
                   not in base]
        return ScanPlan(end, self._scan.generate_splits(end, entries))


class TableRead:
    def __init__(self, builder: ReadBuilder):
        self.builder = builder
        table = builder.table
        if table.primary_keys:
            self._read = MergeFileSplitRead(
                table.file_io, table.path, table.schema, table.options,
                schema_manager=table.schema_manager)
        else:
            from paimon_tpu.core.append import AppendSplitRead
            self._read = AppendSplitRead(
                table.file_io, table.path, table.schema, table.options,
                schema_manager=table.schema_manager)
            if getattr(builder, "_with_row_ids", False):
                self._read.with_row_ids(True)
        if builder._projection:
            self._read.with_projection(builder._projection)
        if builder._predicate is not None:
            self._read.with_filter(builder._predicate)
        if builder._aggregate is not None:
            self._read.with_aggregate(builder._aggregate)

    def read_split(self, split: DataSplit) -> pa.Table:
        t = self._read.read_split(split)
        return self._finalize(t)

    def iter_splits(self, splits, *, ordered: bool = True):
        """Yield `(index, split, finalized_table)` through the bounded
        prefetch pipeline (parallel/scan_pipeline.py).  Accepts a
        ScanPlan or a list of DataSplits; `ordered=False` yields splits
        in completion order for throughput-only consumers."""
        if isinstance(splits, ScanPlan):
            splits = splits.splits
        for i, s, t in self._read.iter_splits(splits, ordered=ordered):
            # a with_limit() bound applies to the WHOLE read (to_arrow),
            # not to each yielded split table
            yield i, s, self._finalize(t, apply_limit=False)

    def to_arrow(self, splits) -> pa.Table:
        """Accepts a ScanPlan or a list of DataSplits.  A configured
        `request.timeout` installs an end-to-end deadline here (entry
        point; an already-active request deadline wins)."""
        from paimon_tpu.utils.deadline import deadline_scope
        with deadline_scope(self.builder.table.options.get(
                CoreOptions.REQUEST_TIMEOUT), entry=True):
            return self._to_arrow(splits)

    def _to_arrow(self, splits) -> pa.Table:
        if isinstance(splits, ScanPlan):
            split_list, streaming = splits.splits, splits.streaming
        else:
            split_list, streaming = list(splits), None
        limit = self.builder._limit
        if limit is not None and split_list:
            # early exit: stop admitting splits once enough rows are
            # buffered — closing the generator cancels pending prefetch
            tables, n = [], 0
            for _, _, t in self._read.iter_splits(split_list):
                if t.num_rows:
                    tables.append(t)
                    n += t.num_rows
                if n >= limit:
                    break
            if tables:
                from paimon_tpu.core.read import assemble_tables
                out = assemble_tables(tables)
            else:
                if streaming is None:
                    streaming = any(s.for_streaming for s in split_list)
                out = self._read.read_splits([], streaming)
        else:
            out = self._read.read_splits(split_list, streaming)
        return self._finalize(out)

    def _finalize(self, t: pa.Table,
                  apply_limit: bool = True) -> pa.Table:
        if self.builder._aggregate is not None:
            return t                    # partials: no rows to shape
        if self.builder._projection:
            from paimon_tpu.core.read import ROW_KIND_COL
            from paimon_tpu.core.row_tracking import ROW_ID_COL
            cols = [c for c in self.builder._projection
                    if c in t.column_names]
            if ROW_KIND_COL in t.column_names:
                cols.append(ROW_KIND_COL)
            if ROW_ID_COL in t.column_names and \
                    getattr(self.builder, "_with_row_ids", False):
                cols.append(ROW_ID_COL)
            t = t.select(cols)
        if apply_limit and self.builder._limit is not None:
            t = t.slice(0, self.builder._limit)
        return t

    def to_pandas(self, splits: Sequence[DataSplit]):
        return self.to_arrow(splits).to_pandas()
