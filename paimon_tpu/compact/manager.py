"""Compaction manager + rewriter for one (partition, bucket).

reference: mergetree/compact/MergeTreeCompactManager.java:54
(triggerCompaction:136, submitCompaction:211), MergeTreeCompactTask.java:41
(doCompact:83 -- upgrade:124 metadata-only promotion vs rewrite),
MergeTreeCompactRewriter.java:78.

TPU deviation: the rewrite reads the unit's files to Arrow, merges the
whole bucket in one device kernel (no IntervalPartition sections -- the
sort absorbs arbitrary overlap), and rolls the result into output-level
files. Drop-delete applies when the output is the highest non-empty level.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import pyarrow as pa

from paimon_tpu.compact.levels import Levels
from paimon_tpu.compact.universal import (
    CompactUnit, UniversalCompaction, pick_full_compaction,
)
from paimon_tpu.core.kv_file import KEY_PREFIX, KeyValueFileWriter, read_kv_file
from paimon_tpu.core.read import assemble_runs
from paimon_tpu.fs import FileIO
from paimon_tpu.manifest import DataFileMeta, FileSource
from paimon_tpu.options import CoreOptions, MergeEngine
from paimon_tpu.metrics import (
    COMPACTION_DURATION_MS, LOOKUP_CHANGELOG_MS, global_registry,
)
from paimon_tpu.obs.trace import carry, span
from paimon_tpu.ops.merge import merge_runs, prep_span
from paimon_tpu.utils.deadline import check_deadline, wait_future
from paimon_tpu.ops.normkey import NormalizedKeyEncoder
from paimon_tpu.schema.table_schema import TableSchema
from paimon_tpu.types import data_type_to_arrow
from paimon_tpu.utils.path_factory import FileStorePathFactory

__all__ = ["MergeTreeCompactManager", "CompactResult"]


@dataclass
class CompactResult:
    before: List[DataFileMeta]
    after: List[DataFileMeta]
    changelog: List[DataFileMeta] = field(default_factory=list)

    def is_empty(self) -> bool:
        return not self.before and not self.after and not self.changelog


def _prefetch(it, depth: int = 2):
    """Run a chunk iterator in a background thread with a small bounded
    queue so file decode overlaps the merge kernel (decode releases the
    GIL). One thread per sorted run of a streamed rewrite.  The pump
    polls a cancel flag on every bounded put, so a consumer that
    abandons the generator early (merge error elsewhere) releases the
    thread and its pinned chunks instead of leaking them."""
    import queue as _queue
    import threading as _threading

    q: "_queue.Queue" = _queue.Queue(maxsize=depth)
    _SENTINEL = object()
    cancelled = _threading.Event()

    def pump():
        try:
            for item in it:
                while not cancelled.is_set():
                    try:
                        q.put(item, timeout=0.1)
                        break
                    except _queue.Full:
                        continue
                if cancelled.is_set():
                    return
            q.put(_SENTINEL)
        except BaseException as e:       # noqa: BLE001
            if not cancelled.is_set():
                q.put(("__prefetch_error__", e))

    from paimon_tpu.parallel.executors import spawn_thread
    spawn_thread(carry(pump), name="paimon-prefetch-pump")
    try:
        while True:
            # bounded poll so a request whose deadline is spent stops
            # waiting on a stalled pump (the cancel flag in `finally`
            # then releases the pump thread and its pinned chunks); the
            # `wait` span says who waited for the pump, and closes
            # before the yield
            with span("wait", cat="wait", what="compaction prefetch"):
                while True:
                    try:
                        item = q.get(timeout=0.2)
                        break
                    except _queue.Empty:
                        check_deadline("compaction prefetch")
            if item is _SENTINEL:
                return
            if isinstance(item, tuple) and len(item) == 2 and \
                    item[0] == "__prefetch_error__":
                raise item[1]
            yield item
    finally:
        cancelled.set()


def _get_busy_timer():
    from paimon_tpu.metrics import CompactTimer
    return CompactTimer()


_BUSY_TIMER = _get_busy_timer()


def live_span(rows: int):
    """`compact.live`: what a merge's result loses before it is written —
    the rows whose surviving kind is a retract (a filter that copies
    every column of the merged state) and the record-level expiry."""
    return span("compact.live", cat="compaction", rows=rows)


class MergeTreeCompactManager:
    def __init__(self, file_io: FileIO, table_path: str,
                 schema: TableSchema, options: CoreOptions,
                 partition: Tuple, bucket: int,
                 files: List[DataFileMeta], schema_manager=None,
                 levels_index=None):
        """`levels_index`: the bucket writer's `lookup/levels_index.py`
        index, kept across commits and brought up to date by every
        compaction of this manager; without one, the lookup changelog
        producer builds one for this manager alone."""
        self.file_io = file_io
        self.schema = schema
        self.options = options
        self.partition = partition
        self.bucket = bucket
        self.schema_manager = schema_manager
        self._schema_cache = {schema.id: schema}
        self._file_cache: dict = {}
        self.levels = Levels(files, options.num_levels)
        self.levels_index = levels_index
        self._index_owned = levels_index is not None
        self._index_synced = False
        self._output: Optional[pa.Table] = None    # the rewrite's rows
        self.strategy = UniversalCompaction(
            max_size_amp=options.max_size_amplification_percent,
            size_ratio=options.size_ratio,
            num_run_trigger=options.num_sorted_runs_compaction_trigger,
            total_size_threshold=options.get(
                CoreOptions.COMPACTION_TOTAL_SIZE_THRESHOLD),
            file_num_limit=options.get(
                CoreOptions.COMPACTION_FILE_NUM_LIMIT),
            offpeak_hours=(
                options.get(CoreOptions.COMPACTION_OFFPEAK_START_HOUR),
                options.get(CoreOptions.COMPACTION_OFFPEAK_END_HOUR)),
            offpeak_ratio=options.get(
                CoreOptions.COMPACTION_OFFPEAK_RATIO))
        self.path_factory = FileStorePathFactory.from_options(
            table_path, schema.partition_keys, options)
        self.kv_writer = KeyValueFileWriter(
            file_io, self.path_factory, schema,
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
        rt = schema.logical_row_type()
        self.trimmed_pk = schema.trimmed_primary_keys()
        self.key_cols = [KEY_PREFIX + k for k in self.trimmed_pk]
        self.key_encoder = NormalizedKeyEncoder(
            [data_type_to_arrow(rt.get_field(k).type)
             for k in self.trimmed_pk],
            nullable=[rt.get_field(k).type.nullable
                      for k in self.trimmed_pk])

    # -- picking -------------------------------------------------------------

    def pick(self, full: bool = False,
             force_up_l0: bool = False) -> Optional[CompactUnit]:
        """`force_up_l0`: upstream's ForceUpLevel0Compaction, the pick
        of a writer under `changelog-producer=lookup` — universal's
        pick, else every level-0 run forced up."""
        runs = self.levels.level_sorted_runs()
        if full:
            return pick_full_compaction(
                self.options.num_levels, runs,
                force_rewrite_all=self.options.get(
                    CoreOptions.COMPACTION_FORCE_REWRITE_ALL_FILES))
        unit = self.strategy.pick(self.options.num_levels, runs)
        if unit is None and force_up_l0:
            return self.strategy.force_pick_l0(self.options.num_levels,
                                               runs)
        return unit

    def should_wait_for_compaction(self) -> bool:
        """Write-stall condition (num-sorted-run.stop-trigger)."""
        return (self.levels.num_sorted_runs()
                > self.options.num_sorted_runs_stop_trigger)

    # -- execution -----------------------------------------------------------

    def compact(self, full: bool = False,
                force_up_l0: bool = False) -> Optional[CompactResult]:
        unit = self.pick(full, force_up_l0)
        if unit is None or not unit.files:
            return None
        return self.do_compact(unit)

    def do_compact(self, unit: CompactUnit) -> CompactResult:
        """reference MergeTreeCompactTask.doCompact:83."""
        group = global_registry().group("compaction")
        # managers are constructed per compaction task, so the busy
        # window lives at module scope — a per-instance timer would
        # leave the gauge bound to the first (dead) task's timer
        timer = _BUSY_TIMER
        group.gauge("busy_ratio_1m", timer.busy_ratio)
        timer.start()
        try:
            # the task's root span: every stage below — prefetch
            # thread, merge pool, write pool — walks back to it
            with span("compact.task", cat="compaction",
                      group="compaction", metric=COMPACTION_DURATION_MS,
                      bucket=self.bucket, files=len(unit.files),
                      rows=sum(f.row_count for f in unit.files)):
                result = self._do_compact(unit)
        finally:
            timer.stop()
            group.counter("tasks").inc()
        if self._index_owned:
            self.levels_index.apply(result.before, result.after,
                                    self._output)
        group.counter("input_files").inc(len(unit.files))
        group.counter("output_files").inc(len(result.after))
        return result

    def _do_compact(self, unit: CompactUnit) -> CompactResult:
        from paimon_tpu.options import ChangelogProducer

        files = unit.files
        producer = self.options.changelog_producer
        self._output = None
        # upgrade fast path: single file, no rewrite needed. Both
        # compaction changelog producers must force a rewrite instead:
        # lookup for any L0 promotion (its keys were never changelog'd),
        # full-compaction when promoting INTO the top level (reference
        # FullChangelogMergeTreeCompactRewriter.upgradeChangelog)
        force_rewrite = self.options.get(
            CoreOptions.COMPACTION_FORCE_REWRITE_ALL_FILES)
        if len(files) == 1 and not force_rewrite:
            f = files[0]
            if f.level == unit.output_level:
                return CompactResult([], [])
            from paimon_tpu.options import MergeEngine as ME
            blocked = (
                (producer == ChangelogProducer.LOOKUP and f.level == 0)
                or (producer == ChangelogProducer.FULL_COMPACTION
                    and unit.output_level == self.levels.max_level
                    and f.level == 0)
                # deferred-merge engines (partial-update / aggregation)
                # sort but do NOT merge at L0 flush (core/write.py flush),
                # so an L0 file may hold several versions of one key;
                # promoting it without rewrite would let raw-convertible
                # reads surface the duplicates
                or (f.level == 0 and self.options.merge_engine in
                    (ME.PARTIAL_UPDATE, ME.AGGREGATE))
                # file.format.per.level: a metadata-only promotion would
                # carry the wrong format into the target level
                # (reference upgrade rewrites on format change)
                or (self.kv_writer.format_per_level and
                    self.kv_writer.format_per_level.get(
                        unit.output_level,
                        self.options.file_format.lower())
                    != f.file_name.rsplit(".", 1)[-1].lower()))
            # metadata-only promotion unless deletes must be dropped at the
            # top level (reference MergeTreeCompactTask.upgrade:124)
            if (unit.output_level < self.levels.max_level
                    or (f.delete_row_count or 0) == 0) and not blocked:
                upgraded = f.upgrade(unit.output_level)
                return CompactResult([f], [upgraded])

        if producer == ChangelogProducer.LOOKUP and \
                any(f.level == 0 for f in files):
            # the unit's upper runs come from the index, not the store
            index = self.synced_index()
            for f in files:
                held = index.table_of(f) if f.level > 0 else None
                if held is not None:
                    self._file_cache.setdefault(f.file_name, held)
        drop_delete = (unit.output_level != 0
                       and unit.output_level
                       >= self.levels.non_empty_highest_level())
        total_rows = sum(f.row_count for f in files)
        threshold = self.options.get(
            CoreOptions.MERGE_STREAM_THRESHOLD_ROWS)
        if producer == ChangelogProducer.NONE and total_rows > threshold:
            # bounded-memory path: stream key windows through the kernel
            after = self._rewrite_streamed(files, unit.output_level,
                                           drop_delete)
            return CompactResult(list(files), after)
        merged = self._merged_state(files, drop_deletes=drop_delete)
        after = self.kv_writer.write(self.partition, self.bucket, merged,
                                     level=unit.output_level,
                                     file_source=FileSource.COMPACT)
        changelog = self._produce_changelog(unit, merged, drop_delete)
        self._output = merged
        return CompactResult(list(files), after, changelog)

    def _rewrite_streamed(self, files: List[DataFileMeta],
                          output_level: int,
                          drop_delete: bool) -> List[DataFileMeta]:
        """Streamed whole-bucket rewrite (ops/merge_stream.py): peak
        memory ~ runs x chunk + one key window, independent of bucket
        size — SURVEY hard part (d)."""
        from paimon_tpu.core.read import evolve_table
        from paimon_tpu.format import get_format
        from paimon_tpu.ops.merge_stream import merge_runs_streamed

        chunk_rows = self.options.get(CoreOptions.MERGE_CHUNK_ROWS)
        runs_meta = assemble_runs(files)

        from paimon_tpu.format.blob import blob_column_names
        has_blobs = bool(blob_column_names(self.schema))

        def encode(t: pa.Table):
            with prep_span(t.num_rows):
                return (t, *self.key_encoder.encode_table_ex(
                    t, self.key_cols))

        def run_iter(run_files):
            # yields (table, lanes, truncated): the lane encode runs
            # HERE, inside the prefetch thread, overlapping the merge
            for f in run_files:
                if has_blobs:
                    # blob descriptors must resolve against the whole
                    # sidecar: read this file unstreamed (bounded by
                    # target-file-size), still windowed downstream
                    t = read_kv_file(self.file_io, self.path_factory,
                                     self.partition, self.bucket, f,
                                     schema=self.schema,
                                     schema_manager=self.schema_manager,
                                     options=self.options)
                    t = evolve_table(t, f.schema_id, self.schema,
                                     self.schema_manager,
                                     self._schema_cache,
                                     keep_sys_cols=True)
                    yield encode(t)
                    continue
                ext = f.file_name.rsplit(".", 1)[-1]
                fmt = get_format(ext)
                path = f.external_path or self.path_factory.data_file_path(
                    self.partition, self.bucket, f.file_name)
                if fmt.identifier == "parquet" and self.options.get(
                        CoreOptions.READ_DEVICE_DECODE):
                    # row-group-at-a-time device decode keeps the
                    # streamed plane's ~runs x chunk memory bound;
                    # an unsupported file drops to the pyarrow
                    # read_batches path below
                    from paimon_tpu.format.rawpage import (
                        DeviceDecodeUnsupported, iter_batches_device,
                    )
                    batches = None
                    try:
                        batches = iter_batches_device(
                            self.file_io, path, chunk_rows,
                            self.options)
                    except DeviceDecodeUnsupported:
                        from paimon_tpu.metrics import (
                            SCAN_DEVICE_DECODE_FALLBACKS,
                            global_registry,
                        )
                        global_registry().group("scan").counter(
                            SCAN_DEVICE_DECODE_FALLBACKS).inc()
                    if batches is not None:
                        for batch in batches:
                            t = evolve_table(
                                batch, f.schema_id, self.schema,
                                self.schema_manager,
                                self._schema_cache,
                                keep_sys_cols=True)
                            yield encode(t)
                        continue
                from paimon_tpu.fs.caching import scoped_batches
                # scoped_batches holds the footer-cache gate only
                # WHILE advancing the inner iterator, never across our
                # own yields — a `with` around this loop would leak
                # the thread-local flag to unrelated reads while this
                # generator is suspended
                for batch in scoped_batches(
                        fmt.create_reader().read_batches(
                            self.file_io, path, batch_rows=chunk_rows),
                        self.options):
                    t = evolve_table(batch, f.schema_id, self.schema,
                                     self.schema_manager,
                                     self._schema_cache,
                                     keep_sys_cols=True)
                    yield encode(t)

        # three-stage pipeline: prefetch threads decode+lane-encode,
        # ONE merge worker sorts/dedups windows (so device upload/sort/
        # download — or the host radix — overlaps the next window's
        # decode and cut), and a write pool encodes output files.
        # Futures are consumed in submission order at every stage, so
        # output files stay in key order regardless of completion.
        from concurrent.futures import ThreadPoolExecutor
        futures = []
        acc: List[pa.Table] = []
        acc_bytes = 0

        def _write_one(merged: pa.Table) -> List[DataFileMeta]:
            return self.kv_writer.write(
                self.partition, self.bucket, merged, level=output_level,
                file_source=FileSource.COMPACT)

        def _merge_one(tables, encoded) -> pa.Table:
            with span("compact.window", cat="compaction",
                      rows=sum(t.num_rows for t in tables)):
                return self._merge_tables(tables, drop_delete,
                                          encoded=encoded)

        # two merge workers: the OVC/native merges and the numpy
        # epilogues release the GIL, so adjacent windows genuinely
        # overlap; futures are still consumed in submission order so
        # output files stay in key order
        with ThreadPoolExecutor(max_workers=3) as pool, \
                ThreadPoolExecutor(max_workers=2) as merge_pool:

            def merge_window(items):
                tables = [item[0] for item in items]
                encoded = [item[1:] for item in items]
                return merge_pool.submit(carry(_merge_one), tables,
                                         encoded)

            def flush():
                nonlocal acc, acc_bytes
                if not acc:
                    return
                # surface an already-failed write now instead of merging
                # every remaining window first
                for f in futures:
                    if f.done() and f.exception() is not None:
                        # lint-ok: deadline-wait the f.done() guard
                        # means the result is already available — this
                        # re-raise cannot block
                        f.result()
                # backpressure: at most 3 file-sized tables in flight so
                # a slow disk can't unbound the streamed path's memory
                pending = [f for f in futures if not f.done()]
                if len(pending) >= 3:
                    wait_future(pending[0], "compaction write backpressure")
                merged = pa.concat_tables(acc, promote_options="none")
                futures.append(pool.submit(carry(_write_one), merged))
                acc, acc_bytes = [], 0

            merge_futs: List = []

            def _collect(fut) -> None:
                nonlocal acc_bytes
                window = wait_future(fut, "compaction merge window")
                if window.num_rows == 0:
                    return
                acc.append(window)
                acc_bytes += window.nbytes
                if acc_bytes >= self.kv_writer.target_file_size:
                    flush()

            def emit(fut):
                merge_futs.append(fut)
                # collect any already-finished merges in order, and cap
                # the lookahead at 2 windows so memory stays bounded
                while merge_futs and (merge_futs[0].done()
                                      or len(merge_futs) > 2):
                    _collect(merge_futs.pop(0))

            merge_runs_streamed(
                [_prefetch(run_iter(rf)) for rf in runs_meta],
                self.key_cols, self.key_encoder, emit, merge_window,
                pass_encoded=True,
                window_rows=self.options.get(
                    CoreOptions.MERGE_WINDOW_ROWS))
            while merge_futs:
                _collect(merge_futs.pop(0))
            flush()
            out: List[DataFileMeta] = []
            for f in futures:
                out.extend(wait_future(f, "compaction file write"))
        return out

    # -- changelog producers -------------------------------------------------

    def _produce_changelog(self, unit: CompactUnit, merged: pa.Table,
                           drop_delete: bool) -> List[DataFileMeta]:
        from paimon_tpu.core.kv_file import write_changelog_file
        from paimon_tpu.options import ChangelogProducer
        from paimon_tpu.ops.diff import keyed_changelog_diff

        producer = self.options.changelog_producer
        value_cols = [f.name for f in self.schema.fields]
        cl = None
        if producer == ChangelogProducer.FULL_COMPACTION and \
                unit.output_level == self.levels.max_level:
            # diff previous top level vs the new full result
            # (reference FullChangelogMergeTreeCompactRewriter)
            top = self.levels.levels.get(self.levels.max_level)
            before = self._merged_state(top.files) \
                if top and top.files else None
            live = merged if drop_delete else self._live_view(merged)
            cl = keyed_changelog_diff(before, live, self.key_cols,
                                      self.key_encoder, value_cols)
        elif producer == ChangelogProducer.LOOKUP:
            # the reference's lookup producer changelogs EVERY commit
            # (LookupChangelogMergeFunctionWrapper.java:54): the unit's
            # L0 deltas are replayed in commit order against the state
            # of the keys they touch, so a key inserted AND deleted
            # between two compactions still shows its +I and -D
            l0 = sorted((f for f in unit.files if f.level == 0),
                        key=lambda f: (f.max_sequence_number,
                                       f.min_sequence_number))
            if l0:
                cl = self._lookup_changelog(l0, value_cols)
        if cl is None or cl.num_rows == 0:
            return []
        lookup = producer == ChangelogProducer.LOOKUP
        with span("changelog.write", cat="lookup",
                  group="lookup" if lookup else None,
                  metric=LOOKUP_CHANGELOG_MS, rows=cl.num_rows):
            return write_changelog_file(
                self.file_io, self.path_factory, self.schema,
                self.options.changelog_file_format,
                self.options.changelog_file_compression,
                self.partition, self.bucket, cl,
                prefix=self.options.changelog_file_prefix,
                format_options=self.options.format_options)

    def _lookup_changelog(self, l0: List[DataFileMeta],
                          value_cols: List[str]) -> Optional[pa.Table]:
        """+I / -U,+U / -D of every key the L0 deltas touch, one delta
        after another.  The state before the first is what the levels
        above hold for those keys, probed in the levels index
        (`_before_images`); each delta is merged into it under the
        table's merge engine.  A key that was there gives -U/+U also
        when its value did not change, unless
        `changelog-producer.row-deduplicate` is set; under first-row
        a key that was there keeps its row and gives nothing, so the
        changelog is +I alone (reference FirstRowMergeFunctionWrapper,
        which LookupMergeTreeCompactRewriter takes for first-row)."""
        from paimon_tpu.ops.diff import keyed_changelog_diff

        deltas = [self._read_file(f) for f in l0]
        state = self._before_images(self.synced_index(), deltas)
        keep_unchanged = (
            self.options.merge_engine != MergeEngine.FIRST_ROW
            and not self.options.get(
                CoreOptions.CHANGELOG_ROW_DEDUPLICATE))
        pieces = []
        for delta in deltas:
            runs = ([state] if state is not None and state.num_rows
                    else []) + [delta]
            new_state = self._merge_tables(runs, drop_deletes=True)
            piece = keyed_changelog_diff(
                state, new_state, self.key_cols, self.key_encoder,
                value_cols, restrict_table=delta,
                keep_unchanged=keep_unchanged)
            if piece is not None and piece.num_rows:
                pieces.append(piece)
            state = new_state
        if not pieces:
            return None
        return pa.concat_tables(pieces, promote_options="none")

    def _before_images(self, index, deltas: List[pa.Table]
                       ) -> Optional[pa.Table]:
        """The merged state of the levels above 0 for the keys of
        `deltas`: each run's rows that hold one of the keys, gathered
        from the index and merged like the table merges (key-sorted,
        key-unique, live rows only)."""
        probes = deltas[0] if len(deltas) == 1 else \
            pa.concat_tables(deltas, promote_options="none")
        tables = index.gather(probes)
        if not tables:
            return None
        return self._merge_tables(tables, drop_deletes=True)

    def synced_index(self):
        """The levels index, holding the runs above level 0 this
        manager was given (built on first use, decoding what it
        lacks)."""
        from paimon_tpu.lookup.levels_index import LevelsIndex
        if self.levels_index is None:
            self.levels_index = LevelsIndex(self.key_encoder,
                                            self.key_cols)
        if not self._index_synced:
            self.levels_index.sync(self.levels.all_files(), self._decode)
            self._index_synced = True
        return self.levels_index

    def _decode(self, files: List[DataFileMeta]) -> List[pa.Table]:
        """`files` decoded side by side (`_read_runs`' pool), in order."""
        self._read_runs(files, flatten=True)
        return [self._read_file(f) for f in files]

    # -- merged-state helpers ------------------------------------------------

    def _read_file(self, f: DataFileMeta) -> pa.Table:
        """Read+evolve one data file, memoized: changelog producers walk
        overlapping file sets (unit, levels>0, all, L0), so each file is
        decoded at most once per compaction."""
        from paimon_tpu.core.read import evolve_table

        cached = self._file_cache.get(f.file_name)
        if cached is not None:
            return cached
        raw = read_kv_file(self.file_io, self.path_factory, self.partition,
                           self.bucket, f, schema=self.schema,
                           schema_manager=self.schema_manager,
                           options=self.options)
        t = evolve_table(raw, f.schema_id, self.schema,
                         self.schema_manager, self._schema_cache,
                         keep_sys_cols=True)
        self._file_cache[f.file_name] = t
        return t

    def _read_runs(self, files: List[DataFileMeta],
                   flatten: bool = False) -> List[pa.Table]:
        runs_meta = assemble_runs(files)
        # parquet/orc decode releases the GIL: fan the file reads over a
        # small thread pool (reference compaction reads files with
        # per-task IO threads; here one pool per whole-bucket rewrite)
        flat = [f for rf in runs_meta for f in rf]
        uncached = [f for f in flat
                    if f.file_name not in self._file_cache]
        if len(uncached) > 1:
            from concurrent.futures import ThreadPoolExecutor
            # the task's thread only waits here: who waited, not what
            # ran (the reads are `io.read` / `decode` on the pool)
            with span("wait", cat="wait", what="compaction read"), \
                    ThreadPoolExecutor(
                        max_workers=min(8, len(uncached))) as pool:
                list(pool.map(carry(self._read_file), uncached))
        runs = []
        for run_files in runs_meta:
            tables = [self._read_file(f) for f in run_files]
            if flatten:
                runs.extend(tables)
            else:
                runs.append(pa.concat_tables(tables,
                                             promote_options="none")
                            if len(tables) > 1 else tables[0])
        return runs

    def _live_view(self, merged: pa.Table) -> pa.Table:
        import pyarrow.compute as pc
        from paimon_tpu.ops.merge import KIND_COL
        from paimon_tpu.types import RowKind
        kinds = merged.column(KIND_COL).combine_chunks().cast(pa.int8())
        keep = pc.or_(pc.equal(kinds, RowKind.INSERT),
                      pc.equal(kinds, RowKind.UPDATE_AFTER))
        return merged.filter(keep)

    def _record_level_expire(self, merged: pa.Table) -> pa.Table:
        from paimon_tpu.core.read import record_level_expire_filter
        return record_level_expire_filter(self.options, merged)

    def _merge_tables(self, run_tables: List[pa.Table],
                      drop_deletes: bool,
                      encoded=None) -> pa.Table:
        """Merge run-ordered tables under the table's merge engine —
        the single dispatch shared by the one-shot and streamed paths.
        `encoded`: optional pre-computed (lanes, truncated) per table
        (the streamed path encodes once for the window cut)."""
        engine = self.options.merge_engine
        seq_fields = self.options.sequence_field or None
        if engine in (MergeEngine.DEDUPLICATE, MergeEngine.FIRST_ROW):
            res = merge_runs(
                run_tables, self.key_cols,
                merge_engine=("first-row" if engine == MergeEngine.FIRST_ROW
                              else "deduplicate"),
                drop_deletes=drop_deletes,
                key_encoder=self.key_encoder,
                seq_fields=seq_fields,
                seq_desc=self.options.sequence_field_descending,
                encoded=encoded)
            merged = res.take()
            with live_span(merged.num_rows):
                return self._record_level_expire(merged)
        from paimon_tpu.ops.agg import merge_runs_agg
        merged = merge_runs_agg(run_tables, self.key_cols, self.schema,
                                self.options,
                                key_encoder=self.key_encoder,
                                seq_fields=seq_fields)
        with live_span(merged.num_rows):
            if drop_deletes:
                merged = self._live_view(merged)
            return self._record_level_expire(merged)

    def _merged_state(self, files: List[DataFileMeta],
                      drop_deletes: bool = True) -> Optional[pa.Table]:
        """KV-shaped, key-sorted, key-unique merged state of `files`."""
        if not files:
            return None
        return self._merge_tables(self._read_runs(files), drop_deletes)
