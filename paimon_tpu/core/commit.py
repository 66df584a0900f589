"""FileStoreCommit: two-phase snapshot commit with optimistic retry.

reference: operation/FileStoreCommitImpl.java:139 (javadoc :122-132:
conflict check -> CAS publish; tryCommit retry loop :756), conflict
detection in operation/commit/ConflictDetection.java, atomicity provider
catalog/SnapshotCommit.java:27 (rename CAS here).
"""

from __future__ import annotations

import time as _time
import uuid
from typing import Dict, List, Optional, Sequence, Tuple

from paimon_tpu.core.write import CommitMessage
from paimon_tpu.data.binary_row import BinaryRowCodec
from paimon_tpu.fs import FileIO
from paimon_tpu.manifest import (
    DataFileMeta, FileKind, IndexManifestFile, ManifestEntry, ManifestFile,
    ManifestFileMeta, ManifestList, merge_manifest_entries,
)
from paimon_tpu.options import CoreOptions
from paimon_tpu.schema.table_schema import TableSchema
from paimon_tpu.snapshot import CommitKind, Snapshot, SnapshotManager
from paimon_tpu.snapshot.snapshot import BATCH_COMMIT_IDENTIFIER
from paimon_tpu.utils.path_factory import FileStorePathFactory

__all__ = ["FileStoreCommit", "CommitConflictError"]


class CommitConflictError(RuntimeError):
    pass


class FileStoreCommit:
    def __init__(self, file_io: FileIO, table_path: str,
                 table_schema: TableSchema, options: CoreOptions,
                 commit_user: Optional[str] = None,
                 branch: str = "main"):
        self.file_io = file_io
        self.table_path = table_path.rstrip("/")
        self.schema = table_schema
        self.options = options
        self.commit_user = commit_user or str(uuid.uuid4())
        self.snapshot_manager = SnapshotManager(file_io, table_path, branch)
        self.path_factory = FileStorePathFactory.from_options(
            table_path, table_schema.partition_keys, options)
        rt = table_schema.logical_row_type()
        self.partition_types = [rt.get_field(k).type
                                for k in table_schema.partition_keys]
        self._partition_codec = BinaryRowCodec(self.partition_types)
        compression = options.get(CoreOptions.MANIFEST_COMPRESSION)
        codec = {"zstd": "zstandard", "none": "null"}.get(compression,
                                                          compression)
        mdir = self.path_factory.manifest_dir
        key_types = [rt.get_field(k).type
                     for k in table_schema.trimmed_primary_keys()]
        sidecar = bool(options.get(CoreOptions.MANIFEST_STATS_SIDECAR))
        self.manifest_file = ManifestFile(file_io, mdir, codec,
                                          self.partition_types,
                                          key_types=key_types,
                                          sidecar=sidecar)
        self.manifest_list = ManifestList(
            file_io, mdir, codec, partition_types=self.partition_types,
            key_types=key_types, sidecar=sidecar)
        self.index_manifest_file = IndexManifestFile(file_io, mdir, codec)
        self.manifest_target_size = options.get(
            CoreOptions.MANIFEST_TARGET_FILE_SIZE)
        self.manifest_merge_min = options.get(
            CoreOptions.MANIFEST_MERGE_MIN_COUNT)
        # append tables with row-tracking.enabled get dense row ids
        # assigned at commit (reference FileStoreCommitImpl
        # .assignRowTracking:1046)
        self.row_tracking = (
            options.get(CoreOptions.ROW_TRACKING_ENABLED)
            and not table_schema.primary_keys)
        # optional lost-CAS observer (attempt number per loss): the
        # multi-host write plane (parallel/distributed.py) hangs its
        # commit_conflicts / commit_retries accounting here — commit
        # arbitration is THIS retry loop, observed from outside
        self.conflict_listener: Optional[callable] = None
        # optional () -> {str: str} merged into EVERY snapshot this
        # commit object publishes (explicit per-call properties win on
        # key collisions).  The multi-host maintenance plane hangs its
        # lease-renewal + ownership-generation stamps here so every
        # plane-issued commit — data checkpoints, compactions,
        # heartbeats — carries them: under plane-only traffic the tip
        # is always stamped and ownership/lease recovery never has to
        # walk past foreign snapshots.  Called once per CAS attempt,
        # so lease timestamps stay fresh across commit retries.
        self.properties_provider: Optional[callable] = None

    # -- public API ----------------------------------------------------------

    def commit(self, messages: Sequence[CommitMessage],
               commit_identifier: int = BATCH_COMMIT_IDENTIFIER,
               kind: Optional[str] = None,
               index_entries: Optional[list] = None,
               properties: Optional[Dict[str, str]] = None,
               expected_latest_id: Optional[int] = ...,
               watermark: Optional[int] = None,
               force_create: bool = False) -> Optional[int]:
        """Commit append + compact changes. Returns snapshot id (or None if
        nothing to commit). Append and compact deltas are committed as
        separate snapshots like the reference (APPEND then COMPACT)."""
        append_entries: List[ManifestEntry] = []
        compact_entries: List[ManifestEntry] = []
        changelog_entries: List[ManifestEntry] = []
        compact_changelog_entries: List[ManifestEntry] = []
        for msg in messages:
            pbytes = self._partition_codec.to_bytes(msg.partition)
            for f in msg.new_files:
                append_entries.append(ManifestEntry(
                    FileKind.ADD, pbytes, msg.bucket, msg.total_buckets, f))
            for f in msg.changelog_files:
                changelog_entries.append(ManifestEntry(
                    FileKind.ADD, pbytes, msg.bucket, msg.total_buckets, f))
            for f in msg.compact_before:
                compact_entries.append(ManifestEntry(
                    FileKind.DELETE, pbytes, msg.bucket, msg.total_buckets,
                    f))
            for f in msg.compact_after:
                compact_entries.append(ManifestEntry(
                    FileKind.ADD, pbytes, msg.bucket, msg.total_buckets, f))
            for f in msg.compact_changelog:
                compact_changelog_entries.append(ManifestEntry(
                    FileKind.ADD, pbytes, msg.bucket, msg.total_buckets, f))

        last_id = None
        force_empty = (
            force_create or
            self.options.get(CoreOptions.COMMIT_FORCE_CREATE_SNAPSHOT) or
            self.options.get(
                CoreOptions.SNAPSHOT_IGNORE_EMPTY_COMMIT) is False)
        if append_entries or changelog_entries or index_entries or \
                (force_empty and not compact_entries):
            last_id = self._try_commit(
                append_entries, changelog_entries, commit_identifier,
                kind or CommitKind.APPEND, index_entries=index_entries,
                properties=properties,
                expected_latest_id=expected_latest_id,
                watermark=watermark)
            index_entries = None
        if compact_entries or compact_changelog_entries:
            last_id = self._try_commit(
                compact_entries, compact_changelog_entries,
                commit_identifier, CommitKind.COMPACT,
                check_deleted_files=True, index_entries=index_entries,
                properties=properties, watermark=watermark)
        return last_id

    def overwrite(self, messages: Sequence[CommitMessage],
                  partition_filter: Optional[dict] = None,
                  commit_identifier: int = BATCH_COMMIT_IDENTIFIER,
                  index_entries: Optional[list] = None,
                  watermark: Optional[int] = None,
                  properties: Optional[Dict[str, str]] = None
                  ) -> Optional[int]:
        """INSERT OVERWRITE: delete current files (optionally restricted to
        a partition spec) and add new ones atomically
        (reference FileStoreCommitImpl.overwrite). The delete set is
        recomputed from the latest snapshot on every CAS attempt so files
        committed concurrently between planning and publish do not
        survive the overwrite."""
        adds: List[ManifestEntry] = []
        for msg in messages:
            pbytes = self._partition_codec.to_bytes(msg.partition)
            for f in msg.new_files:
                adds.append(ManifestEntry(
                    FileKind.ADD, pbytes, msg.bucket, msg.total_buckets, f))

        def entries_fn(latest: Optional[Snapshot]) -> List[ManifestEntry]:
            entries: List[ManifestEntry] = []
            if latest is not None:
                for e in self._read_all_entries(latest):
                    if e.kind != FileKind.ADD:
                        continue
                    if partition_filter and not self._partition_matches(
                            e.partition, partition_filter):
                        continue
                    entries.append(ManifestEntry(
                        FileKind.DELETE, e.partition, e.bucket,
                        e.total_buckets, e.file))
            return entries + adds

        return self._try_commit([], [], commit_identifier,
                                CommitKind.OVERWRITE, entries_fn=entries_fn,
                                index_entries=index_entries,
                                properties=properties,
                                watermark=watermark)

    def filter_committed(self, commit_identifiers: Sequence[int]
                         ) -> List[int]:
        """Drop identifiers already committed by this user (exactly-once
        replay dedup, reference FileStoreCommit.filterCommitted:52)."""
        committed = set()
        for snap in self.snapshot_manager.snapshots():
            if snap.commit_user == self.commit_user:
                committed.add(snap.commit_identifier)
        return [c for c in commit_identifiers if c not in committed]

    # -- internals -----------------------------------------------------------

    def _read_all_entries(self, snapshot: Snapshot) -> List[ManifestEntry]:
        metas = self.manifest_list.read_all(snapshot.base_manifest_list,
                                            snapshot.delta_manifest_list)
        entries: List[ManifestEntry] = []
        for m in metas:
            entries.extend(self.manifest_file.read(m.file_name))
        return merge_manifest_entries(entries)

    def _partition_matches(self, pbytes: bytes, spec: dict) -> bool:
        values = self._partition_codec.from_bytes(pbytes)
        for i, k in enumerate(self.schema.partition_keys):
            if k in spec and str(values[i]) != str(spec[k]):
                return False
        return True

    def _try_commit(self, entries: List[ManifestEntry],
                    changelog_entries: List[ManifestEntry],
                    commit_identifier: int, kind: str,
                    check_deleted_files: bool = False,
                    index_entries: Optional[list] = None,
                    properties: Optional[Dict[str, str]] = None,
                    entries_fn=None,
                    expected_latest_id: Optional[int] = ...,
                    statistics: Optional[str] = None,
                    watermark: Optional[int] = None,
                    force_full_manifest_merge: bool = False,
                    skip_missing_manifests: bool = False) -> int:
        """One commit, from its entries to the published snapshot (or
        the error that gave up), under the `commit` span: its duration
        is `commit.duration_ms`, one sample a commit — a commit that
        gives up leaves a sample too."""
        from paimon_tpu.metrics import COMMIT_DURATION_MS
        from paimon_tpu.obs.trace import span as _span, sync_from_options

        sync_from_options(self.options)
        with _span("commit", cat="commit", group="commit",
                   metric=COMMIT_DURATION_MS, kind=kind,
                   table=self.table_path):
            return self._commit_attempts(
                entries, changelog_entries, commit_identifier, kind,
                check_deleted_files=check_deleted_files,
                index_entries=index_entries, properties=properties,
                entries_fn=entries_fn,
                expected_latest_id=expected_latest_id,
                statistics=statistics, watermark=watermark,
                force_full_manifest_merge=force_full_manifest_merge,
                skip_missing_manifests=skip_missing_manifests)

    def _commit_attempts(self, entries: List[ManifestEntry],
                         changelog_entries: List[ManifestEntry],
                         commit_identifier: int, kind: str, *,
                         check_deleted_files: bool,
                         index_entries: Optional[list],
                         properties: Optional[Dict[str, str]],
                         entries_fn,
                         expected_latest_id: Optional[int],
                         statistics: Optional[str],
                         watermark: Optional[int],
                         force_full_manifest_merge: bool,
                         skip_missing_manifests: bool) -> int:
        from paimon_tpu.metrics import global_registry

        from paimon_tpu.obs.trace import span as _span
        from paimon_tpu.utils.backoff import Backoff
        from paimon_tpu.utils.deadline import DeadlineExceededError

        _metrics = global_registry().group("commit")
        _attempts = 0
        _max_retries = self.options.get(CoreOptions.COMMIT_MAX_RETRIES)
        _min_wait = self.options.get(CoreOptions.COMMIT_MIN_RETRY_WAIT)
        _max_wait = self.options.get(CoreOptions.COMMIT_MAX_RETRY_WAIT)
        # decorrelated jitter between the retry-wait bounds, bounded in
        # total time by commit.timeout (utils/backoff.py — shared with
        # RetryingObjectStoreBackend and the mesh bucket-retry ladder)
        _backoff = Backoff(_min_wait, _max_wait,
                           self.options.get(CoreOptions.COMMIT_TIMEOUT))
        new_manifest: Optional[ManifestFileMeta] = None
        changelog_manifest: Optional[ManifestFileMeta] = None
        entries_orig = list(entries)
        # per-attempt artifacts, pre-bound so the deadline-abort
        # handler below can delete whatever the CURRENT attempt
        # had written when the deadline tripped: a
        # DeadlineExceededError can surface from ANY store read
        # inside an attempt (every FileIO read checks the
        # deadline), not only at the CAS gate — an abort must
        # never leave this attempt's manifests orphaned
        base_name = delta_name = changelog_name = None
        index_manifest = prev_index = None
        merged_manifests: List[ManifestFileMeta] = []

        def _delete_attempt_lists():
            """Drop the CURRENT attempt's manifest lists, index
            manifest and merged manifests — shared by the lost-CAS
            retry and the deadline-abort handler so the two abort
            paths cannot drift (closure: reads the attempt's latest
            bindings; every delete is quiet + deadline-shielded)."""
            if base_name:
                self.manifest_list.delete(base_name)
            if delta_name:
                self.manifest_list.delete(delta_name)
            if changelog_name:
                self.manifest_list.delete(changelog_name)
            if index_manifest is not None and \
                    index_manifest != prev_index:
                self.file_io.delete_quietly(
                    self.index_manifest_file.path(index_manifest))
            for m in merged_manifests:
                self.file_io.delete_quietly(
                    self.manifest_file.path(m.file_name))

        try:
            while True:
                if _attempts > _max_retries or \
                        (_attempts > 0 and _backoff.budget_exhausted()):
                    # the per-attempt cleanup keeps the (reusable) delta and
                    # changelog manifest FILES; on giving up they would be
                    # orphaned with no snapshot referencing them
                    for m in (new_manifest, changelog_manifest):
                        if m is not None:
                            self.file_io.delete_quietly(
                                self.manifest_file.path(m.file_name))
                    raise CommitConflictError(
                        f"Commit lost the snapshot CAS race "
                        f"{_attempts - 1} times (commit.max-retries="
                        f"{_max_retries}, commit.timeout); giving up")
                if _attempts > 0:
                    with _span("commit.backoff", cat="commit",
                               attempt=_attempts, table=self.table_path):
                        _backoff.pause()
                _attempts += 1
                latest = self.snapshot_manager.latest_snapshot()
                if expected_latest_id is not ... and \
                        (latest.id if latest else None) != expected_latest_id:
                    # the caller's plan is stale (e.g. deletion vectors built
                    # against an older snapshot): surface a conflict so it can
                    # replan instead of silently losing concurrent changes
                    raise CommitConflictError(
                        f"Snapshot advanced past "
                        f"{expected_latest_id} before commit; replan required")
                if entries_fn is not None:
                    # delete/add set depends on the latest snapshot (e.g.
                    # overwrite): recompute per attempt; per-attempt manifests
                    # are cleaned up on CAS loss below
                    entries = entries_fn(latest)
                    new_manifest = None
                next_row_id = latest.next_row_id if latest else None
                candidates = entries if entries_fn is not None else \
                    entries_orig
                ids_assigned = False
                if self.row_tracking and any(
                        e.kind == FileKind.ADD and e.file.first_row_id is None
                        for e in candidates):
                    # row-id start depends on the latest snapshot, so the
                    # assignment re-runs from the pre-assignment entries
                    # (and the manifest is rewritten) on every CAS attempt
                    from paimon_tpu.core.row_tracking import assign_row_ids
                    start = next_row_id
                    if start is None:
                        # tracking enabled on an existing table: ids for old
                        # files stay unassigned; new ids start past all rows
                        start = latest.total_record_count if latest else 0
                    entries, next_row_id = assign_row_ids(candidates, start)
                    new_manifest = None
                    ids_assigned = True
                if check_deleted_files and latest is not None:
                    self._assert_files_exist(latest, entries)

                from paimon_tpu.metrics import COMMIT_MANIFEST_ENCODE_MS

                def _write_manifest(manifest_entries, which):
                    with _span("commit.manifest_encode", cat="commit",
                               group="commit",
                               metric=COMMIT_MANIFEST_ENCODE_MS,
                               which=which, attempt=_attempts,
                               entries=len(manifest_entries)):
                        return self.manifest_file.write(
                            manifest_entries, schema_id=self.schema.id)

                if new_manifest is None and entries and \
                        changelog_manifest is None and changelog_entries:
                    # both manifests are needed and independent: encode +
                    # upload the delta manifest on a worker while the
                    # changelog manifest encodes here, so commit prep waits
                    # on completion, not initiation (write-pipeline PR)
                    from paimon_tpu.parallel.executors import new_thread_pool
                    pool = new_thread_pool(1, "paimon-commit")
                    try:
                        from paimon_tpu.obs.trace import carry
                        fut = pool.submit(carry(_write_manifest), entries,
                                          "delta")
                        changelog_manifest = _write_manifest(
                            changelog_entries, "changelog")
                        from paimon_tpu.utils.deadline import wait_future
                        new_manifest = wait_future(
                            fut, "commit delta manifest write")
                    finally:
                        pool.shutdown(wait=True)
                if new_manifest is None and entries:
                    new_manifest = _write_manifest(entries, "delta")
                if changelog_manifest is None and changelog_entries:
                    changelog_manifest = _write_manifest(changelog_entries,
                                                         "changelog")

                if latest is None:
                    base_metas: List[ManifestFileMeta] = []
                    new_id = 1
                    prev_total = 0
                    prev_index = None
                else:
                    base_metas = self.manifest_list.read_all(
                        latest.base_manifest_list, latest.delta_manifest_list)
                    new_id = latest.id + 1
                    prev_total = latest.total_record_count
                    prev_index = latest.index_manifest

                base_metas, merged_manifests = \
                    self._maybe_merge_manifests(
                        base_metas, force=force_full_manifest_merge,
                        skip_missing=skip_missing_manifests)
                base_name, base_size = self.manifest_list.write(base_metas)
                delta_metas = [new_manifest] if new_manifest else []
                delta_name, delta_size = self.manifest_list.write(delta_metas)
                changelog_name = None
                changelog_size = None
                if changelog_manifest is not None:
                    changelog_name, changelog_size = self.manifest_list.write(
                        [changelog_manifest])

                index_manifest = self.index_manifest_file.combine(
                    prev_index, index_entries or [])

                # watermarks only advance (reference FileStoreCommitImpl:
                # max of provided and previous)
                wm_vals = [w for w in
                           (watermark, latest.watermark if latest else None)
                           if w is not None]
                new_watermark = max(wm_vals) if wm_vals else None
                if force_full_manifest_merge and \
                        getattr(self, "_force_merge_total", None) is not None:
                    # the full rewrite recounted every live entry — use the
                    # true total (skip_missing may have dropped manifests)
                    prev_total = self._force_merge_total
                    self._force_merge_total = None
                delta_rows = sum(
                    (e.file.row_count if e.kind == FileKind.ADD
                     else -e.file.row_count) for e in entries)
                changelog_rows = sum(e.file.row_count
                                     for e in changelog_entries)
                eff_properties = properties
                if self.properties_provider is not None:
                    # provider stamps merge UNDER the explicit ones;
                    # evaluated per attempt so lease renewals reflect
                    # the attempt that actually publishes
                    merged_props = dict(self.properties_provider() or {})
                    merged_props.update(properties or {})
                    eff_properties = merged_props or None
                from paimon_tpu.obs.trace import current_context_token
                _ctx = current_context_token()
                if _ctx is not None:
                    # store-carried trace context: readers of this
                    # snapshot (scan plans, lease folds) link their
                    # spans back to the committing process's span in
                    # the merged fleet trace.  setdefault — an
                    # explicit/provider-stamped context (takeover
                    # attribution) wins over the ambient span.
                    eff_properties = dict(eff_properties or {})
                    eff_properties.setdefault("trace.context", _ctx)
                snapshot = Snapshot(
                    id=new_id,
                    schema_id=self.schema.id,
                    base_manifest_list=base_name,
                    base_manifest_list_size=base_size,
                    delta_manifest_list=delta_name,
                    delta_manifest_list_size=delta_size,
                    changelog_manifest_list=changelog_name,
                    changelog_manifest_list_size=changelog_size,
                    index_manifest=index_manifest,
                    commit_user=self.commit_user,
                    commit_identifier=commit_identifier,
                    commit_kind=kind,
                    time_millis=int(_time.time() * 1000),
                    total_record_count=prev_total + delta_rows,
                    delta_record_count=delta_rows,
                    changelog_record_count=changelog_rows or None,
                    properties=eff_properties,
                    statistics=statistics,
                    next_row_id=next_row_id,
                    watermark=new_watermark,
                )
                from paimon_tpu.metrics import COMMIT_CAS_MS
                from paimon_tpu.utils.deadline import check_deadline
                # the point of no return is the CAS itself: a request
                # whose deadline is already spent must raise HERE, before
                # publishing — a 504'd caller can clean up / retry an
                # UNcommitted attempt, but an orphan-committed snapshot
                # would make the timeout a lie (the except handler around
                # the whole retry loop cleans this attempt's artifacts)
                check_deadline("commit CAS")
                with _span("commit.cas", cat="commit", group="commit",
                           metric=COMMIT_CAS_MS, attempt=_attempts,
                           snapshot=new_id, table=self.table_path) as _cas:
                    _won = self.snapshot_manager.try_commit(snapshot)
                    _cas.set(won=_won)
                if _won:
                    _metrics.counter("commits").inc()
                    if _attempts > 1:
                        _metrics.counter("retries").inc(_attempts - 1)
                    return new_id
                # lost the race: clean up everything written for this attempt
                # and retry against the new latest (the delta manifest is
                # reusable across attempts unless the entry set is dynamic)
                if self.conflict_listener is not None:
                    self.conflict_listener(_attempts)
                from paimon_tpu.obs.flight import (
                    EV_COMMIT_CONFLICT, record,
                )
                record(EV_COMMIT_CONFLICT, attempt=_attempts,
                       snapshot=new_id, user=self.commit_user)
                _delete_attempt_lists()
                if (entries_fn is not None or ids_assigned) and \
                        new_manifest is not None:
                    # the entry set was rebuilt for this attempt (dynamic
                    # entries or per-attempt row-id assignment): its manifest
                    # is stale too, and must not be referenced by the retry
                    self.file_io.delete_quietly(
                        self.manifest_file.path(new_manifest.file_name))
                    new_manifest = None

        except DeadlineExceededError:
            # same cleanup as a lost CAS, plus the manifests the
            # exhausted-retries path would drop: nothing written
            # for this attempt may outlive the abort (deletes are
            # deadline-shielded via delete_quietly)
            _delete_attempt_lists()
            for m in (new_manifest, changelog_manifest):
                if m is not None:
                    self.file_io.delete_quietly(
                        self.manifest_file.path(m.file_name))
            raise

    def _assert_files_exist(self, latest: Snapshot,
                            entries: List[ManifestEntry]):
        """Compaction conflict checks (reference
        operation/commit/ConflictDetection.java):
        1. every file we delete must still be live
        2. files we add at level > 0 must not overlap the key range of a
           concurrent live file at the same level (two racing
           compactions writing the same level would corrupt the
           no-overlap invariant levels >= 1 rely on)"""
        deletes = [e for e in entries if e.kind == FileKind.DELETE]
        adds_upper = [e for e in entries
                      if e.kind == FileKind.ADD and e.file.level > 0]
        if not deletes and not adds_upper:
            return
        live_entries = [e for e in self._read_all_entries(latest)
                        if e.kind == FileKind.ADD]
        live = {e.identifier() for e in live_entries}
        for d in deletes:
            ident = (d.partition, d.bucket, d.file.level, d.file.file_name,
                     tuple(d.file.extra_files), d.file.embedded_index,
                     d.file.external_path)
            if ident not in live:
                raise CommitConflictError(
                    f"File to delete no longer exists: "
                    f"{d.file.file_name} (level {d.file.level}); "
                    f"a concurrent compaction won. Retry the compaction "
                    f"from the new snapshot.")
        if not adds_upper:
            return
        key_types = [
            self.schema.logical_row_type().get_field(k).type.copy(False)
            for k in self.schema.trimmed_primary_keys()]
        if not key_types:
            return
        key_codec = BinaryRowCodec(key_types)

        def decode_key(b: bytes):
            # BinaryRow bytes are NOT order-comparable (little-endian
            # slots); decode to value tuples like the reference's typed
            # comparator
            if not b:
                return None
            try:
                return tuple(key_codec.from_bytes(b))
            except Exception:
                return None

        deleted_names = {(d.partition, d.bucket, d.file.file_name)
                         for d in deletes}
        for a in adds_upper:
            a_min = decode_key(a.file.min_key)
            a_max = decode_key(a.file.max_key)
            if a_min is None or a_max is None:
                continue
            for e in live_entries:
                if (e.partition, e.bucket, e.file.level) != \
                        (a.partition, a.bucket, a.file.level):
                    continue
                if (e.partition, e.bucket, e.file.file_name) \
                        in deleted_names:
                    continue       # replaced by this very commit
                e_min = decode_key(e.file.min_key)
                e_max = decode_key(e.file.max_key)
                if e_min is None or e_max is None:
                    continue
                if a_min <= e_max and e_min <= a_max:
                    raise CommitConflictError(
                        f"Key range of new file {a.file.file_name} "
                        f"(level {a.file.level}) overlaps live file "
                        f"{e.file.file_name}; a concurrent compaction "
                        f"wrote this level. Retry from the new snapshot.")

    def compact_manifests(self, skip_missing: bool = False,
                          properties: Optional[Dict[str, str]] = None
                          ) -> Optional[int]:
        """Force one full manifest rewrite: every base+delta manifest is
        read, DELETE entries are folded away, and the merged entry set
        is committed as a COMPACT snapshot with an empty delta — the
        base rewritten as sorted, partition-clustered, size-bounded
        manifests (reference flink/procedure/CompactManifestProcedure +
        manifest full-compaction). Returns the new snapshot id, or None
        when the table has no snapshot.  `skip_missing` tolerates
        manifest FILES deleted out of band (reference
        RemoveUnexistingManifestsProcedure) — entries they held are
        lost, which is the point of that repair.

        A pure full-compaction commits as COMPACT with an empty delta
        — the live-entry set is unchanged, so the delta-apply plan
        cache folds it as a no-op.  The `skip_missing` repair DROPS
        entries without DELETE records, so it commits as OVERWRITE:
        every cached plan (this process or any other) invalidates
        instead of serving ghost entries for files the repair
        removed."""
        if self.snapshot_manager.latest_snapshot() is None:
            return None
        return self._try_commit([], [], BATCH_COMMIT_IDENTIFIER,
                                CommitKind.OVERWRITE if skip_missing
                                else CommitKind.COMPACT,
                                properties=properties,
                                force_full_manifest_merge=True,
                                skip_missing_manifests=skip_missing)

    def _maybe_merge_manifests(self, metas: List[ManifestFileMeta],
                               force: bool = False,
                               skip_missing: bool = False
                               ) -> Tuple[List[ManifestFileMeta],
                                          List[ManifestFileMeta]]:
        """Full-rewrite small manifests when there are too many
        (reference manifest/ManifestFileMerger); `force` merges
        EVERYTHING and folds DELETE entries (compact_manifests).
        Returns (metas, newly_written) so the caller can delete fresh
        files if the commit attempt loses the CAS."""
        if force:
            entries: List[ManifestEntry] = []
            for m in metas:
                try:
                    entries.extend(self.manifest_file.read(m.file_name))
                except FileNotFoundError:
                    if not skip_missing:
                        raise
                    # repair mode: the manifest is gone, its entries
                    # are unrecoverable — drop it from the chain
            merged = merge_manifest_entries(entries)
            # the rewrite KNOWS the true row total; expose it so the
            # snapshot does not inherit counts from dropped manifests
            self._force_merge_total = sum(
                e.file.row_count for e in merged
                if e.kind == FileKind.ADD)
            if not merged:
                return [], []
            # sorted, partition-clustered, size-bounded base manifests
            # (reference Paimon manifest full-compaction): each output
            # manifest covers a narrow (partition, bucket, key) band,
            # so the per-manifest stats the columnar sidecar persists
            # stay selective and the vectorized prune keeps whole
            # manifests unfetched.  Raw-byte key order is a clustering
            # heuristic only — correctness never depends on it.
            merged.sort(key=lambda e: (e.partition, e.bucket,
                                       e.file.min_key or b""))
            total_size = sum(m.file_size for m in metas)
            total_entries = sum(m.num_added_files + m.num_deleted_files
                                for m in metas) or 1
            per_entry = max(64, total_size // total_entries) \
                if total_size else 256
            chunk = max(1, int(self.manifest_target_size // per_entry))
            out = []
            for i in range(0, len(merged), chunk):
                out.append(self.manifest_file.write(
                    merged[i:i + chunk], schema_id=self.schema.id))
            return out, list(out)
        if len(metas) < self.manifest_merge_min:
            return metas, []
        small = [m for m in metas if m.file_size < self.manifest_target_size]
        if len(small) < 2:
            return metas, []
        big = [m for m in metas if m.file_size >= self.manifest_target_size]
        entries: List[ManifestEntry] = []
        for m in small:
            entries.extend(self.manifest_file.read(m.file_name))
        merged = merge_manifest_entries(entries)
        out = list(big)
        written = []
        if merged:
            meta = self.manifest_file.write(merged, schema_id=self.schema.id)
            out.append(meta)
            written.append(meta)
        return out, written
