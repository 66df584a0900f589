"""Table-level compact action: pick per bucket, rewrite the buckets
side by side, commit once.

reference: the dedicated compaction job path (flink action/CompactAction ->
StoreCompactOperator -> MergeTreeCompactManager), engine-free here.

`compact_table` plans on the calling thread — for every (partition,
bucket) group the partition decode, the `group_filter`, the manager's
pick (or the append plan): metadata only, no file is opened — and so
knows the groups that have work, each one's input rows and on-disk
bytes.  The rewrites of those groups then run as tasks on one bounded
pool (threads `paimon-compact_N`); nothing inside a task differs from
what the serial loop ran, and their `CommitMessage`s are committed in
`groups`' order as ONE COMPACT snapshot, whatever order they finish in.

* **Workers** = min(groups with work, the scan's default ceiling
  `scan_pipeline.default_parallelism()` = min(8, cpu count)).  Derived;
  no option.  One group with work — or one core — runs on the calling
  thread with no pool: the file operations of a single-bucket
  compaction are the serial loop's, in its order.
* **Byte budget.**  In-flight groups are also bounded by their input
  files' on-disk bytes against `read.prefetch.max-bytes`, the budget the
  scan of the same table decodes under; at least one group is always
  admitted.  The submitter waits for room in a `compact.admit` span.
* **Streamed groups.**  A primary-key group whose input rows exceed
  `tpu.merge.stream-threshold-rows` takes `_rewrite_streamed`, which
  owns five pool threads and a prefetch thread per run: it is admitted
  only when nothing else is in flight, and nothing beside it.
* **The merge router's link reading** (`ops/merge.py`, once a process)
  is taken on the calling thread before the pool starts, where the
  router would take one at all: timed by the first merge with the
  other tasks' decode and prep contending for the host, it reads the
  link several times too narrow and sends every merge to the host.
* **Failure.**  Once a task has raised nothing more is admitted; the
  running tasks are waited for, the first failure in `groups`' order is
  re-raised and nothing is committed — the files that finished tasks
  wrote are orphans, as the serial loop's earlier buckets' were.  The
  pool is shut down on every path; only a spent request deadline leaves
  without joining workers that may hang (the scan pipeline's rule).
* **Observability.**  `compact.table` (root; `compaction` / `table_ms`;
  attrs `groups`, `workers`, `rows`) is the wall time of the group
  phase; every `compact.task` is its child, on the pool through
  `carry`, so `duration_ms` sums over threads and `duration_ms` /
  `table_ms` is the concurrency achieved.  The gauge `compaction` /
  `concurrent_tasks_peak` holds the last call's most tasks in flight.
  The mesh route (`tpu.mesh.compact`, `parallel/mesh_engine.py`) opens
  the same root through `table_span` — `workers` its lanes — around
  one `compact.task`, its whole step loop on the calling thread.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

from paimon_tpu.compact.manager import MergeTreeCompactManager
from paimon_tpu.options import CoreOptions
from paimon_tpu.core.commit import FileStoreCommit
from paimon_tpu.core.write import CommitMessage
from paimon_tpu.snapshot.snapshot import BATCH_COMMIT_IDENTIFIER

__all__ = ["compact_table", "sort_compact", "rescale_postpone"]


def _group_entries(scan, snapshot):
    """{(partition_bytes, bucket): [files]} + total_buckets map."""
    groups: Dict[Tuple[bytes, int], list] = {}
    total_buckets: Dict[Tuple[bytes, int], int] = {}
    for e in scan.read_entries(snapshot):
        if e.bucket == -2:
            # postpone staging compacts only through rescale_postpone
            # (a normal rewrite would drop its DELETE tombstones)
            continue
        key = (e.partition, e.bucket)
        groups.setdefault(key, []).append(e.file)
        total_buckets[key] = e.total_buckets
    return groups, total_buckets


def _make_append_writer(table, path_factory):
    from paimon_tpu.core.append import AppendFileWriter
    return AppendFileWriter(
        table.file_io, path_factory, table.schema,
        file_format=table.options.file_format,
        compression=table.options.file_compression,
        target_file_size=table.options.target_file_size,
        index_spec=table.options.file_index_spec,
        bloom_fpp=table.options.get(CoreOptions.FILE_INDEX_BLOOM_FPP),
        index_in_manifest_threshold=table.options.get(
            CoreOptions.FILE_INDEX_IN_MANIFEST_THRESHOLD),
        format_options=table.options.format_options)


def _read_bucket(table, path_factory, partition, bucket, files,
                 dvs=None):
    """Read+evolve a bucket's files in sequence order, applying deletion
    vectors so rewrites never resurrect deleted rows."""
    import pyarrow as pa

    from paimon_tpu.core.kv_file import read_kv_file
    from paimon_tpu.core.read import evolve_table

    cache = {table.schema.id: table.schema}
    tables = []
    for f in sorted(files, key=lambda x: x.min_sequence_number):
        t = read_kv_file(table.file_io, path_factory, partition, bucket,
                         f, None, None, schema=table.schema,
                         schema_manager=table.schema_manager)
        if dvs and f.file_name in dvs:
            t = t.filter(pa.array(dvs[f.file_name].keep_mask(t.num_rows)))
        tables.append(evolve_table(t, f.schema_id, table.schema,
                                   table.schema_manager, cache))
    return pa.concat_tables(tables, promote_options="none")


def compact_table(table, full: bool = False,
                  partition_filter: Optional[dict] = None,
                  group_filter=None, commit_user: Optional[str] = None,
                  properties: Optional[Dict[str, str]] = None,
                  properties_provider=None) -> Optional[int]:
    """Compact every (partition, bucket) that has work; commit one COMPACT
    snapshot. Returns the snapshot id or None if nothing to do.

    `group_filter` is a `(partition_tuple, bucket) -> bool` scheduling
    predicate: the sharded maintenance plane passes its ownership
    filter so each host compacts only the groups it owns.
    `commit_user`/`properties` thread through to the COMPACT snapshot
    (the plane stamps its lease + ownership generation on every commit
    it issues); `properties_provider` is the callable form
    (FileStoreCommit.properties_provider), re-evaluated per CAS
    attempt so a long compaction cannot publish stale lease/ownership
    stamps after losing a race to a takeover commit.

    With `tpu.mesh.compact` enabled, full compactions of primary-key
    tables route per merge engine: engines the streaming mesh engine
    implements (parallel/mesh_engine.py) compact multi-chip in one mesh
    program; anything it cannot run — unsupported engines, changelog
    producers, partition-filtered or non-full compactions — falls back
    to the single-chip manager below."""
    if (full and table.schema.primary_keys and partition_filter is None
            and table.options.get(CoreOptions.MESH_COMPACT)):
        from paimon_tpu.options import ChangelogProducer
        from paimon_tpu.parallel.mesh_engine import (
            SUPPORTED_MERGE_ENGINES, compact_table_mesh,
        )
        if (table.options.merge_engine in SUPPORTED_MERGE_ENGINES
                and table.options.changelog_producer
                == ChangelogProducer.NONE):
            return compact_table_mesh(
                table, group_filter=group_filter,
                commit_user=commit_user, properties=properties,
                properties_provider=properties_provider).snapshot_id
    scan = table.new_scan()
    if partition_filter:
        scan.with_partition_filter(partition_filter)
    snapshot = table.snapshot_manager.latest_snapshot()
    if snapshot is None:
        return None
    groups, total_buckets = _group_entries(scan, snapshot)

    is_append = not table.schema.primary_keys
    if is_append and table.options.get(CoreOptions.ROW_TRACKING_ENABLED):
        # row-tracked files own dense id ranges; plain rewrite would
        # reassign positions and orphan evolution overlays / row-id
        # DVs. Their compaction folds each row-range group's overlays
        # into one full file that KEEPS the group's firstRowId
        # (reference append/dataevolution/DataEvolutionCompactTask)
        from paimon_tpu.core.row_tracking import compact_row_tracked
        return compact_row_tracked(table,
                                   partition_filter=partition_filter)
    tasks = _plan_tasks(table, scan, snapshot, groups, total_buckets,
                        full, group_filter)
    messages = [m for m in _run_tasks(table, tasks) if m is not None]

    if not messages:
        return None
    commit = FileStoreCommit(table.file_io, table.path, table.schema,
                             table.options, commit_user=commit_user,
                             branch=table.branch)
    if properties_provider is not None:
        commit.properties_provider = properties_provider
    index_list = [e for m in messages for e in m.index_entries]
    return commit.commit(messages, BATCH_COMMIT_IDENTIFIER,
                         index_entries=index_list or None,
                         properties=properties)


@dataclass
class _GroupTask:
    """One (partition, bucket) group that has work: `rewrite()` is the
    whole of it (no argument, returns the manager's or the append
    plan's result), planned on the calling thread from metadata."""
    partition: Tuple
    bucket: int
    total_buckets: int
    rewrite: Callable[[], object]
    rows: int               # input rows, from the DataFileMetas
    est_bytes: int          # input bytes on disk (the scan's estimate)
    streamed: bool          # takes _rewrite_streamed: runs alone

    def run(self) -> Optional[CommitMessage]:
        result = self.rewrite()
        if result is None or result.is_empty():
            return None
        return CommitMessage(
            partition=self.partition, bucket=self.bucket,
            total_buckets=self.total_buckets,
            compact_before=result.before,
            compact_after=result.after,
            compact_changelog=result.changelog,
            index_entries=getattr(result, "index_entries", []))


def _plan_tasks(table, scan, snapshot, groups, total_buckets, full,
                group_filter) -> List[_GroupTask]:
    """The groups with work, in `groups`' order.  Metadata only: a
    group another host owns, or one with nothing picked, costs no
    worker and opens no file."""
    from functools import partial

    from paimon_tpu.core.append import append_compact_plan

    is_append = not table.schema.primary_keys
    dv_index = scan._load_deletion_vectors(snapshot.id, snapshot) \
        if is_append else {}
    stream_rows = table.options.get(CoreOptions.MERGE_STREAM_THRESHOLD_ROWS)
    tasks: List[_GroupTask] = []
    for (pbytes, bucket), files in groups.items():
        partition = scan._partition_codec.from_bytes(pbytes)
        if group_filter is not None and \
                not group_filter(tuple(partition), bucket):
            continue              # another host's share
        if is_append:
            bucket_dvs = dv_index.get((pbytes, bucket))
            inputs = append_compact_plan(files, table.options, full=full,
                                         dvs=bucket_dvs)
            if not inputs:
                continue
            rewrite = partial(_append_rewrite, table, scan, partition,
                              bucket, inputs, bucket_dvs, pbytes,
                              snapshot)
        else:
            mgr = MergeTreeCompactManager(
                table.file_io, table.path, table.schema, table.options,
                partition, bucket, files,
                schema_manager=table.schema_manager)
            unit = mgr.pick(full)
            if unit is None or not unit.files:
                continue
            inputs = unit.files
            rewrite = partial(mgr.do_compact, unit)
        rows = sum(f.row_count for f in inputs)
        tasks.append(_GroupTask(
            partition, bucket, total_buckets[(pbytes, bucket)], rewrite,
            rows=rows,
            est_bytes=sum(f.file_size for f in inputs),
            streamed=not is_append and rows > stream_rows))
    return tasks


def table_span(groups: int, workers: int, rows: int):
    """`compact.table`: the root of a table compaction's group phase,
    planning done and the commit still to come, on the calling thread —
    whichever route runs the groups (`_run_tasks` below: `workers` pool
    threads; the mesh engine: `workers` lanes).  Its sink `compaction` /
    `table_ms` is the phase's wall time; the gauge beside it holds the
    most tasks in flight, 1 until a pool says otherwise."""
    from paimon_tpu.metrics import (
        COMPACTION_CONCURRENT_TASKS_PEAK, COMPACTION_TABLE_MS,
        global_registry,
    )
    from paimon_tpu.obs.trace import span

    global_registry().group("compaction").gauge(
        COMPACTION_CONCURRENT_TASKS_PEAK).set(1)
    return span("compact.table", cat="compaction", group="compaction",
                metric=COMPACTION_TABLE_MS, groups=groups,
                workers=workers, rows=rows)


def _run_tasks(table, tasks: List[_GroupTask]
               ) -> List[Optional[CommitMessage]]:
    """Run the groups' rewrites, side by side where there are several
    and the cores to run them; their results in `tasks`' order."""
    from paimon_tpu.metrics import (
        COMPACTION_CONCURRENT_TASKS_PEAK, global_registry,
    )
    from paimon_tpu.parallel.scan_pipeline import default_parallelism

    if not tasks:
        return []
    workers = min(len(tasks), default_parallelism())
    with table_span(len(tasks), workers, sum(t.rows for t in tasks)):
        if workers <= 1:
            return [t.run() for t in tasks]
        peak = global_registry().group("compaction").gauge(
            COMPACTION_CONCURRENT_TASKS_PEAK)
        if table.schema.primary_keys:
            # the merge router's one link reading, before the tasks'
            # prep contends for the host: what the serial loop's first
            # merge read
            from paimon_tpu.ops.merge import take_link_reading
            take_link_reading()
        return _run_pooled(tasks, workers, table.options.get(
            CoreOptions.READ_PREFETCH_MAX_BYTES), peak)


def _run_pooled(tasks: List[_GroupTask], workers: int, max_bytes: int,
                peak) -> List[Optional[CommitMessage]]:
    import concurrent.futures as cf

    from paimon_tpu.obs.trace import carry, span
    from paimon_tpu.parallel.executors import new_thread_pool
    from paimon_tpu.utils.deadline import (
        DeadlineExceededError, check_deadline, wait_future,
    )

    pool = new_thread_pool(workers, "paimon-compact")
    futures: list = []          # in `tasks`' order
    inflight: dict = {}         # future -> its task, until seen done
    inflight_bytes = 0
    most = 0
    failed = abandoned = False
    try:
        for task in tasks:
            # admit: worker ceiling + byte budget, always >= 1 in
            # flight; a streamed group alone.  The wait is for any
            # running task to end, sliced so a spent deadline escapes
            with span("compact.admit", cat="compaction",
                      bucket=task.bucket, est_bytes=task.est_bytes):
                while True:
                    for fut in [f for f in inflight if f.done()]:
                        inflight_bytes -= inflight.pop(fut).est_bytes
                        failed = failed or fut.exception() is not None
                    alone = task.streamed or any(
                        t.streamed for t in inflight.values())
                    if failed or not inflight or (
                            not alone and len(inflight) < workers and
                            inflight_bytes + task.est_bytes
                            <= max_bytes):
                        break
                    check_deadline("compaction admit")
                    cf.wait(list(inflight), timeout=0.5,
                            return_when=cf.FIRST_COMPLETED)
            if failed:
                break               # start nothing after a failure
            fut = pool.submit(carry(task.run))
            futures.append(fut)
            inflight[fut] = task
            inflight_bytes += task.est_bytes
            most = max(most, len(inflight))
        peak.set(most)
        # wait for what runs, in order; the first failure in `tasks`'
        # order is the one raised
        messages, error = [], None
        for fut in futures:
            try:
                messages.append(wait_future(fut, "compaction task"))
            except DeadlineExceededError:
                raise
            except Exception as e:      # noqa: BLE001 — re-raised below
                error = error or e
        if error is not None:
            raise error
        return messages
    except DeadlineExceededError:
        # workers may be hung in store calls: answer within the
        # deadline's grace, do not join them (the scan pipeline's rule)
        abandoned = True
        raise
    finally:
        pool.shutdown(wait=not abandoned, cancel_futures=True)


def rescale_postpone(table) -> Optional[int]:
    """Redistribute bucket-postpone staging data into real (dynamic)
    buckets (reference postpone/PostponeBucketFileStoreWrite + the
    rescale job). Returns the snapshot id or None when nothing staged."""
    scan = table.new_scan().with_buckets([-2])
    snapshot = table.snapshot_manager.latest_snapshot()
    if snapshot is None:
        return None
    entries = [e for e in scan.read_entries(snapshot) if e.bucket == -2]
    if not entries:
        return None

    # route rows through a dynamic-bucket writer. Sizing precedence
    # (reference postpone.default-bucket-num /
    # postpone.target-row-num-per-bucket): explicit postpone.* knobs
    # win; an explicitly-set dynamic-bucket.* is respected next; else
    # the postpone defaults (5M rows/bucket, 4 initial) apply
    from paimon_tpu.options import CoreOptions as _CO
    overrides = {"bucket": "-1"}
    raw = table.options.options
    if raw.contains(_CO.POSTPONE_TARGET_ROW_NUM_PER_BUCKET) or \
            not raw.contains(_CO.DYNAMIC_BUCKET_TARGET_ROW_NUM):
        overrides["dynamic-bucket.target-row-num"] = str(
            table.options.get(_CO.POSTPONE_TARGET_ROW_NUM_PER_BUCKET))
    if raw.contains(_CO.POSTPONE_DEFAULT_BUCKET_NUM) or \
            not raw.contains(_CO.DYNAMIC_BUCKET_INITIAL_BUCKETS):
        overrides["dynamic-bucket.initial-buckets"] = str(
            table.options.get(_CO.POSTPONE_DEFAULT_BUCKET_NUM))
    write_table = table.copy(overrides)
    wb = write_table.new_batch_write_builder()
    writer = wb.new_write(apply_defaults=False)
    try:
        return _rescale_with_writer(table, scan, writer, entries)
    finally:
        writer.close()


def _rescale_with_writer(table, scan, writer, entries):
    """The rescale body, writer-lifetime-managed by rescale_postpone's
    try/finally: a prepare_commit() raise (pipelined flush barrier)
    must still join the writer's pool."""
    import numpy as np
    import pyarrow as pa

    from paimon_tpu.core.kv_file import read_kv_file
    from paimon_tpu.core.read import evolve_table
    from paimon_tpu.ops.merge import KIND_COL, SEQ_COL

    cache = {table.schema.id: table.schema}
    value_cols = [f.name for f in table.schema.fields]
    by_part: Dict[bytes, list] = {}
    for e in entries:
        by_part.setdefault(e.partition, []).append(e)
    messages: List[CommitMessage] = []
    for pbytes, es in by_part.items():
        partition = scan._partition_codec.from_bytes(pbytes)
        es.sort(key=lambda e: e.file.min_sequence_number)
        tables = []
        for e in es:
            t = read_kv_file(table.file_io, scan.path_factory, partition,
                             -2, e.file, None, None, schema=table.schema,
                             schema_manager=table.schema_manager)
            tables.append(evolve_table(t, e.file.schema_id, table.schema,
                                       table.schema_manager, cache,
                                       keep_sys_cols=True))
        staged = pa.concat_tables(tables, promote_options="none")
        order = np.argsort(np.asarray(staged.column(SEQ_COL)
                                      .combine_chunks().cast(pa.int64())),
                           kind="stable")
        staged = staged.take(pa.array(order))
        kinds = np.asarray(staged.column(KIND_COL).combine_chunks()
                           .cast(pa.int8()))
        writer.write_arrow(staged.select(value_cols), kinds)
        messages.append(CommitMessage(
            partition=partition, bucket=-2,
            total_buckets=es[0].total_buckets,
            compact_before=[e.file for e in es]))
    # rewritten files commit as compact_after so staging deletion and
    # publication land in ONE atomic COMPACT snapshot (a crash between
    # two snapshots would replay staged rows on the next rescale)
    for m in writer.prepare_commit():
        m.compact_after = m.new_files
        m.new_files = []
        messages.append(m)
    index_entries = [e for m in messages for e in m.index_entries]
    commit = FileStoreCommit(table.file_io, table.path, table.schema,
                             table.options, branch=table.branch)
    return commit.commit(messages, BATCH_COMMIT_IDENTIFIER,
                         index_entries=index_entries or None)


def sort_compact(table, order_by, strategy: str = "zorder"):
    """Rewrite an append table clustered by `order_by` columns
    (reference flink sort-compact: ZorderSorter / OrderSorter over
    append tables; commit kind OVERWRITE per rewrite)."""
    import pyarrow as pa

    from paimon_tpu.manifest import FileSource
    from paimon_tpu.ops.zorder import (
        hilbert_permutation, order_permutation, z_order_permutation,
    )

    if not order_by:
        raise ValueError("sort-compact requires at least one order-by "
                         "column")
    names = {f.name for f in table.schema.fields}
    missing = [c for c in order_by if c not in names]
    if missing:
        raise ValueError(f"Unknown order-by columns {missing}")
    if table.schema.primary_keys:
        raise ValueError("sort-compact applies to append tables "
                         "(pk tables cluster by key already)")
    perm_fn = {"zorder": z_order_permutation,
               "hilbert": hilbert_permutation,
               "order": order_permutation}.get(strategy)
    if perm_fn is None:
        raise ValueError(f"Unknown sort strategy {strategy!r} "
                         f"(zorder | hilbert | order)")

    scan = table.new_scan()
    snapshot = table.snapshot_manager.latest_snapshot()
    if snapshot is None:
        return None
    groups, total_buckets = _group_entries(scan, snapshot)
    dv_index = scan._load_deletion_vectors(snapshot.id, snapshot)

    # DV rows are physically dropped by the rewrite; the bucket's DV
    # index entries must be deleted along with it
    index_entries = []
    if snapshot.index_manifest:
        from paimon_tpu.manifest import FileKind
        from paimon_tpu.manifest.index_manifest import (
            DELETION_VECTORS_INDEX, IndexManifestEntry,
        )
        for e in scan.index_manifest_file.read(snapshot.index_manifest):
            if e.index_file.index_type == DELETION_VECTORS_INDEX and \
                    (e.partition, e.bucket) in groups:
                index_entries.append(IndexManifestEntry(
                    FileKind.DELETE, e.partition, e.bucket, e.index_file))

    writer = _make_append_writer(table, scan.path_factory)
    messages: List[CommitMessage] = []
    for (pbytes, bucket), files in groups.items():
        partition = scan._partition_codec.from_bytes(pbytes)
        ordered = sorted(files, key=lambda f: f.min_sequence_number)
        data = _read_bucket(table, scan.path_factory, partition, bucket,
                            ordered, dvs=dv_index.get((pbytes, bucket)))
        perm = perm_fn(data, order_by)
        clustered = data.take(pa.array(perm))
        after = writer.write(partition, bucket, clustered,
                             ordered[0].min_sequence_number,
                             file_source=FileSource.COMPACT)
        messages.append(CommitMessage(
            partition=partition, bucket=bucket,
            total_buckets=total_buckets[(pbytes, bucket)],
            compact_before=ordered, compact_after=after))
    if not messages:
        return None
    if index_entries:
        messages[0].index_entries.extend(index_entries)
    commit = FileStoreCommit(table.file_io, table.path, table.schema,
                             table.options, branch=table.branch)
    index_list = [e for m in messages for e in m.index_entries]
    return commit.commit(messages, BATCH_COMMIT_IDENTIFIER,
                         index_entries=index_list or None)


def _append_rewrite(table, scan, partition, bucket, picked, bucket_dvs,
                    pbytes, snapshot):
    """Concatenate the small append files `append_compact_plan` picked
    into target-size files (reference
    append/BucketedAppendCompactManager: no keys, order by sequence).
    Deletion vectors of rewritten files are applied (rows physically
    dropped) and the bucket's DV index entries rewritten to cover only
    the surviving files."""
    from paimon_tpu.core.append import AppendCompactResult
    from paimon_tpu.manifest import FileSource

    writer = _make_append_writer(table, scan.path_factory)
    data = _read_bucket(table, scan.path_factory, partition, bucket,
                        picked, dvs=bucket_dvs)
    after = writer.write(partition, bucket, data,
                         picked[0].min_sequence_number,
                         file_source=FileSource.COMPACT)
    result = AppendCompactResult(before=list(picked), after=after)

    picked_names = {f.file_name for f in picked}
    if bucket_dvs and picked_names & set(bucket_dvs):
        from paimon_tpu.index.deletion_vector import (
            DeletionVectorsIndexFile,
        )
        from paimon_tpu.manifest import FileKind
        from paimon_tpu.manifest.index_manifest import (
            DELETION_VECTORS_INDEX, IndexFileMeta, IndexManifestEntry,
        )
        for e in scan.index_manifest_file.read(snapshot.index_manifest):
            if e.index_file.index_type == DELETION_VECTORS_INDEX and \
                    e.partition == pbytes and e.bucket == bucket:
                result.index_entries.append(IndexManifestEntry(
                    FileKind.DELETE, e.partition, e.bucket, e.index_file))
        remaining = {f: dv for f, dv in bucket_dvs.items()
                     if f not in picked_names}
        if remaining:
            dv_file = DeletionVectorsIndexFile(table.file_io,
                                               f"{table.path}/index")
            name, size, ranges = dv_file.write(
                remaining, path_factory=scan.path_factory)
            result.index_entries.append(IndexManifestEntry(
                FileKind.ADD, pbytes, bucket,
                IndexFileMeta(DELETION_VECTORS_INDEX, name, size,
                              sum(d.cardinality()
                                  for d in remaining.values()),
                              dv_ranges=ranges)))
    return result
