"""Behavior tests for the round-3 wired CoreOptions: commit retry
bounds, empty-commit handling, sequence sort order, plan partition
sorting, partition expiration cap."""

import numpy as np
import pyarrow as pa
import pytest

from paimon_tpu.schema import Schema
from paimon_tpu.table import FileStoreTable
from paimon_tpu.types import BigIntType, DoubleType, IntType, VarCharType


def _pk_table(path, extra_opts=None):
    opts = {"bucket": "1"}
    opts.update(extra_opts or {})
    schema = (Schema.builder()
              .column("id", BigIntType(False))
              .column("seq", IntType())
              .column("v", DoubleType())
              .primary_key("id")
              .options(opts)
              .build())
    return FileStoreTable.create(str(path), schema)


def _write(table, rows):
    wb = table.new_batch_write_builder()
    w = wb.new_write()
    w.write_dicts(rows)
    sid = wb.new_commit().commit(w.prepare_commit())
    w.close()
    return sid


class TestSequenceSortOrder:
    def test_descending_smaller_sequence_wins(self, tmp_path):
        t = _pk_table(tmp_path / "t", {
            "sequence.field": "seq",
            "sequence.field.sort-order": "descending"})
        _write(t, [{"id": 1, "seq": 5, "v": 5.0}])
        _write(t, [{"id": 1, "seq": 3, "v": 3.0}])   # smaller -> wins
        _write(t, [{"id": 1, "seq": 9, "v": 9.0}])   # larger -> loses
        assert t.to_arrow().to_pylist() == \
            [{"id": 1, "seq": 3, "v": 3.0}]
        # survives compaction too
        t.compact(full=True)
        assert t.to_arrow().to_pylist() == \
            [{"id": 1, "seq": 3, "v": 3.0}]

    def test_ascending_default_unchanged(self, tmp_path):
        t = _pk_table(tmp_path / "t", {"sequence.field": "seq"})
        _write(t, [{"id": 1, "seq": 5, "v": 5.0}])
        _write(t, [{"id": 1, "seq": 3, "v": 3.0}])
        assert t.to_arrow().to_pylist() == \
            [{"id": 1, "seq": 5, "v": 5.0}]

    def test_descending_null_still_loses(self, tmp_path):
        t = _pk_table(tmp_path / "t", {
            "sequence.field": "seq",
            "sequence.field.sort-order": "descending"})
        _write(t, [{"id": 1, "seq": 7, "v": 7.0}])
        _write(t, [{"id": 1, "seq": None, "v": 0.0}])
        assert t.to_arrow().to_pylist() == \
            [{"id": 1, "seq": 7, "v": 7.0}]


class TestEmptyCommit:
    def test_empty_batch_commit_skipped(self, tmp_path):
        t = _pk_table(tmp_path / "t")
        _write(t, [{"id": 1, "seq": 1, "v": 1.0}])
        before = t.latest_snapshot().id
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        sid = wb.new_commit().commit(w.prepare_commit())
        assert sid is None
        assert t.latest_snapshot().id == before

    def test_forced_empty_commit(self, tmp_path):
        t = _pk_table(tmp_path / "t",
                      {"snapshot.ignore-empty-commit": "false"})
        _write(t, [{"id": 1, "seq": 1, "v": 1.0}])
        before = t.latest_snapshot().id
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        sid = wb.new_commit().commit(w.prepare_commit())
        assert sid == before + 1


class TestCommitRetries:
    def test_max_retries_bounds_cas_race(self, tmp_path, monkeypatch):
        from paimon_tpu.core.commit import CommitConflictError
        t = _pk_table(tmp_path / "t", {"commit.max-retries": "2",
                                       "commit.min-retry-wait": "1",
                                       "commit.max-retry-wait": "2"})
        _write(t, [{"id": 1, "seq": 1, "v": 1.0}])
        # a snapshot manager that always loses the CAS
        from paimon_tpu.snapshot import SnapshotManager
        monkeypatch.setattr(SnapshotManager, "try_commit",
                            lambda self, snap: False)
        with pytest.raises(CommitConflictError, match="max-retries"):
            _write(t, [{"id": 2, "seq": 1, "v": 2.0}])


class TestPlanSortPartition:
    def test_splits_sorted_by_partition(self, tmp_path):
        schema = (Schema.builder()
                  .column("p", VarCharType(10, False))
                  .column("v", BigIntType())
                  .partition_keys("p")
                  .options({"bucket": "1", "bucket-key": "v",
                            "scan.plan-sort-partition": "true"})
                  .build())
        t = FileStoreTable.create(str(tmp_path / "t"), schema)
        for part in ["zz", "aa", "mm"]:
            wb = t.new_batch_write_builder()
            w = wb.new_write()
            w.write_dicts([{"p": part, "v": 1}])
            wb.new_commit().commit(w.prepare_commit())
            w.close()
        splits = t.new_read_builder().new_scan().plan().splits
        parts = [s.partition[0] for s in splits]
        assert parts == sorted(parts)


class TestStreamingWiredOptions:
    def test_consumer_ignore_progress(self, tmp_path):
        t = _pk_table(tmp_path / "t", {"consumer-id": "c1"})
        _write(t, [{"id": 1, "seq": 1, "v": 1.0}])
        scan = t.new_read_builder().new_stream_scan()
        p1 = scan.plan()
        scan.notify_checkpoint_complete(scan.checkpoint())
        _write(t, [{"id": 2, "seq": 1, "v": 2.0}])
        # a fresh scan resumes past snapshot 1...
        scan2 = t.new_read_builder().new_stream_scan()
        p2 = scan2.plan()
        assert p2.snapshot_id == 2 and not p2.splits == p1.splits
        # ...unless consumer.ignore-progress starts it fresh
        t3 = t.copy({"consumer.ignore-progress": "true"})
        scan3 = t3.new_read_builder().new_stream_scan()
        p3 = scan3.plan()
        assert p3.snapshot_id == 2 and len(p3.splits) > 0
        read = t3.new_read_builder().new_read()
        import pyarrow as pa
        full = pa.concat_tables([read.read_split(s) for s in p3.splits],
                                promote_options="none")
        assert full.num_rows == 2          # full load, not just delta

    def test_bounded_watermark_ends_stream(self, tmp_path):
        t = _pk_table(tmp_path / "t",
                      {"scan.bounded.watermark": "1000"})
        wb = t.new_stream_write_builder()
        w = wb.new_write()
        w.write_dicts([{"id": 1, "seq": 1, "v": 1.0}])
        wb.new_commit().commit(w.prepare_commit(), commit_identifier=1,
                               watermark=500)
        scan = t.new_read_builder().new_stream_scan()
        assert scan.plan() is not None          # initial full load
        w2 = wb.new_write()
        w2.write_dicts([{"id": 2, "seq": 1, "v": 2.0}])
        wb.new_commit().commit(w2.prepare_commit(), commit_identifier=2,
                               watermark=2000)       # past the bound
        assert scan.plan() is None              # stream ended
        assert scan.plan() is None

    def test_streaming_read_overwrite(self, tmp_path):
        t = _pk_table(tmp_path / "t")
        _write(t, [{"id": 1, "seq": 1, "v": 1.0}])
        scan = t.new_read_builder().new_stream_scan()
        scan.plan()
        wb = t.new_batch_write_builder().with_overwrite()
        w = wb.new_write()
        w.write_dicts([{"id": 9, "seq": 1, "v": 9.0}])
        wb.new_commit().commit(w.prepare_commit())
        # default: overwrite snapshots are skipped
        plan = scan.plan()
        assert plan is not None and plan.splits == []
        # with the flag: the overwrite's delta is read
        t2 = t.copy({"streaming-read-overwrite": "true"})
        scan2 = t2.new_read_builder().new_stream_scan()
        scan2.plan()
        scan2.restore(2)
        plan2 = scan2.plan()
        assert plan2 is not None and len(plan2.splits) > 0


class TestSplitBinning:
    def test_append_bucket_bins_by_target_size(self, tmp_path):
        schema = (Schema.builder()
                  .column("v", BigIntType())
                  .options({"bucket": "-1",
                            "source.split.target-size": "1kb",
                            "source.split.open-file-cost": "16b"})
                  .build())
        t = FileStoreTable.create(str(tmp_path / "t"), schema)
        for _ in range(6):          # six small files in one bucket
            wb = t.new_batch_write_builder()
            w = wb.new_write()
            w.write_dicts([{"v": i} for i in range(50)])
            wb.new_commit().commit(w.prepare_commit())
            w.close()
        splits = t.new_read_builder().new_scan().plan().splits
        assert len(splits) > 1          # binned, not one giant split
        total = sum(sum(f.row_count for f in s.data_files)
                    for s in splits)
        assert total == 300
        assert t.to_arrow().num_rows == 300

    def test_pk_bucket_never_bins(self, tmp_path):
        t = _pk_table(tmp_path / "t",
                      {"source.split.target-size": "1kb",
                       "source.split.open-file-cost": "16b",
                       "write-only": "true"})
        for i in range(4):
            _write(t, [{"id": i, "seq": 1, "v": 1.0}])
        splits = t.new_read_builder().new_scan().plan().splits
        assert len(splits) == 1          # merge needs the whole bucket


class TestCompactionWiredOptions:
    def test_total_size_threshold_full_compacts(self, tmp_path):
        t = _pk_table(tmp_path / "t",
                      {"write-only": "true",
                       "compaction.total-size-threshold": "10mb"})
        for i in range(2):          # only 2 runs: below run trigger
            _write(t, [{"id": i, "seq": 1, "v": 1.0}])
        sid = t.compact()           # not full — strategy picks anyway
        assert sid is not None
        splits = t.new_read_builder().new_scan().plan().splits
        assert len(splits[0].data_files) == 1

    def test_file_num_limit_forces_pick(self, tmp_path):
        t = _pk_table(tmp_path / "t",
                      {"write-only": "true",
                       "compaction.total-size-threshold": "0",
                       "compaction.file-num-limit": "3"})
        for i in range(3):
            _write(t, [{"id": i, "seq": 1, "v": 1.0}])
        assert t.compact() is not None


class TestChangelogFileOptions:
    def test_changelog_format_and_prefix(self, tmp_path):
        t = _pk_table(tmp_path / "t",
                      {"changelog-producer": "input",
                       "changelog-file.format": "avro",
                       "changelog-file.prefix": "cl-"})
        wb = t.new_stream_write_builder()
        w = wb.new_write()
        w.write_dicts([{"id": 1, "seq": 1, "v": 1.0}])
        wb.new_commit().commit(w.prepare_commit(), commit_identifier=1)
        import os
        found = []
        for root, _, names in os.walk(str(tmp_path / "t")):
            found += [n for n in names if n.startswith("cl-")]
        assert found and all(n.endswith(".avro") for n in found)
        # changelog stream decodes the avro files
        t2 = t.copy({"scan.mode": "from-snapshot-full",
                     "scan.snapshot-id": "1"})
        scan = t2.new_read_builder().new_stream_scan()
        plan = scan.plan()
        assert plan is not None


class TestPartitionExpireCap:
    def test_expiration_max_num(self, tmp_path):
        schema = (Schema.builder()
                  .column("dt", VarCharType(10, False))
                  .column("v", BigIntType())
                  .partition_keys("dt")
                  .options({"bucket": "1", "bucket-key": "v",
                            "partition.expiration-time": "1 d",
                            "partition.expiration-max-num": "2"})
                  .build())
        t = FileStoreTable.create(str(tmp_path / "t"), schema)
        for day in ["2000-01-01", "2000-01-02", "2000-01-03",
                    "2000-01-04"]:
            wb = t.new_batch_write_builder()
            w = wb.new_write()
            w.write_dicts([{"dt": day, "v": 1}])
            wb.new_commit().commit(w.prepare_commit())
            w.close()
        expired = t.expire_partitions()
        assert len(expired) == 2                     # capped
        # oldest two went first
        assert sorted(e[0] for e in expired) == \
            ["2000-01-01", "2000-01-02"]
        remaining = set(
            np.asarray(t.to_arrow().column("dt")).tolist())
        assert remaining == {"2000-01-03", "2000-01-04"}


class TestParquetFormatOptions:
    def test_enable_dictionary_off(self, tmp_path):
        """parquet.enable.dictionary=false reaches the parquet writer
        (reference: format options forwarded to FileFormat factories)."""
        import pyarrow.parquet as pq

        t = _pk_table(tmp_path / "t", {
            "parquet.enable.dictionary": "false"})
        _write(t, [{"id": i, "seq": 1, "v": 1.0} for i in range(10)])
        t2 = _pk_table(tmp_path / "t2")
        _write(t2, [{"id": i, "seq": 1, "v": 1.0} for i in range(10)])

        def dict_encoded(table):
            split = table.new_read_builder().new_scan().plan().splits[0]
            f = split.data_files[0]
            path = (f"{table.path}/bucket-0/{f.file_name}")
            md = pq.ParquetFile(path).metadata
            col = md.row_group(0).column(0)
            return "PLAIN_DICTIONARY" in str(col.encodings) or \
                "RLE_DICTIONARY" in str(col.encodings)

        assert not dict_encoded(t)
        assert dict_encoded(t2)       # default stays dictionary-on


class TestCompressionCodecs:
    @pytest.mark.parametrize("fmt,codec", [
        ("parquet", "lz4"), ("parquet", "snappy"), ("parquet", "zstd"),
        ("orc", "lz4"), ("orc", "snappy")])
    def test_file_compression_codecs(self, tmp_path, fmt, codec):
        """file.compression codecs beyond zstd round-trip per format
        (reference compression/: lz4, zstd, aircompressor snappy)."""
        t = _pk_table(tmp_path / f"{fmt}_{codec}", {
            "file.format": fmt, "file.compression": codec})
        _write(t, [{"id": i, "seq": 1, "v": float(i)} for i in range(50)])
        out = t.to_arrow()
        assert out.num_rows == 50
        if fmt == "parquet":
            import pyarrow.parquet as pq
            f = (t.new_read_builder().new_scan().plan()
                 .splits[0].data_files[0])
            md = pq.ParquetFile(
                f"{t.path}/bucket-0/{f.file_name}").metadata
            assert md.row_group(0).column(0).compression == codec.upper()


class TestMaintenanceOptions:
    def test_clean_empty_directories(self, tmp_path):
        """snapshot.clean-empty-directories removes emptied partition
        dirs after expire (reference SnapshotDeletion)."""
        from paimon_tpu.schema import Schema
        schema = (Schema.builder()
                  .column("dt", VarCharType(nullable=False))
                  .column("v", IntType())
                  .partition_keys("dt")
                  .options({"bucket": "1", "bucket-key": "v",
                            "snapshot.num-retained.min": "1",
                            "snapshot.num-retained.max": "1",
                            "snapshot.clean-empty-directories": "true"})
                  .build())
        t = FileStoreTable.create(str(tmp_path / "t"), schema)
        _write(t, [{"dt": "a", "v": 1}])
        # overwrite the partition away, then expire the old snapshot
        wb = t.new_batch_write_builder().with_overwrite({"dt": "a"})
        w = wb.new_write()
        wb.new_commit().commit(w.prepare_commit())
        w.close()
        _write(t, [{"dt": "b", "v": 2}])
        t.expire_snapshots()
        import os
        assert not os.path.exists(os.path.join(str(t.path), "dt=a"))
        assert os.path.exists(os.path.join(str(t.path), "dt=b"))

    def test_delete_file_threads_and_manifest_parallelism(self, tmp_path):
        """delete-file.thread-num + scan.manifest.parallelism produce
        the same results as the serial paths."""
        t = _pk_table(tmp_path / "t", {
            "delete-file.thread-num": "4",
            "scan.manifest.parallelism": "4",
            "snapshot.num-retained.min": "1",
            "snapshot.num-retained.max": "1"})
        for i in range(4):
            _write(t, [{"id": j, "seq": i, "v": float(i)}
                       for j in range(20)])
        t.compact(full=True)
        res = t.expire_snapshots()
        assert res.deleted_data_files > 0
        rows = {r["id"]: r["v"] for r in t.to_arrow().to_pylist()}
        assert len(rows) == 20 and rows[0] == 3.0


def _spill_dirs():
    import glob
    import os
    import tempfile
    return set(glob.glob(os.path.join(tempfile.gettempdir(),
                                      "paimon-spill-*")))


class TestSpillableWriteBuffer:
    @pytest.fixture(autouse=True)
    def _snapshot_tmp(self, tmp_path, monkeypatch):
        # a temp dir of its own: another worker's spill test running at
        # the same moment must not show up in this one's listing
        import tempfile
        own = tmp_path / "tmp"
        own.mkdir()
        monkeypatch.setattr(tempfile, "tempdir", str(own))
        self._before = _spill_dirs()

    def _write_many(self, t, batches=6, per=500):
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        for b in range(batches):
            w.write_dicts([{"id": (b * per + i) % 1500, "seq": b,
                            "v": float(b)} for i in range(per)])
        wb.new_commit().commit(w.prepare_commit())
        w.close()

    def test_spillable_merges_to_fewer_l0_files(self, tmp_path):
        """write-buffer-spillable: spilled runs merge into one L0 write
        at prepare-commit instead of one file per buffer-full
        (reference SortBufferWriteBuffer spill + MergeSorter)."""
        common = {"write-buffer-size": "40kb", "write-only": "true"}
        t_plain = _pk_table(tmp_path / "plain", common)
        t_spill = _pk_table(tmp_path / "spill", {
            **common, "write-buffer-spillable": "true"})
        for t in (t_plain, t_spill):
            self._write_many(t)

        def l0_files(t):
            split = t.new_read_builder().new_scan().plan().splits[0]
            return [f for f in split.data_files if f.level == 0]

        plain, spill = l0_files(t_plain), l0_files(t_spill)
        assert len(plain) > 1              # small buffer => many flushes
        assert len(spill) < len(plain)     # merged at prepare-commit
        # bit-identical read-back between the two paths
        a = {r["id"]: (r["seq"], r["v"])
             for r in t_plain.to_arrow().to_pylist()}
        b = {r["id"]: (r["seq"], r["v"])
             for r in t_spill.to_arrow().to_pylist()}
        assert a == b and len(a) == 1500
        # no NEW spill temp dirs survive (delta-based: other runs may
        # have left stale dirs in the shared tmp)
        assert _spill_dirs() == self._before

    def test_spillable_aggregation_engine(self, tmp_path):
        """Deferred-merge engines keep every row through the spill."""
        from paimon_tpu.schema import Schema
        schema = (Schema.builder()
                  .column("id", BigIntType(False))
                  .column("total", BigIntType())
                  .primary_key("id")
                  .options({"bucket": "1", "write-only": "true",
                            "write-buffer-size": "10kb",
                            "write-buffer-spillable": "true",
                            "merge-engine": "aggregation",
                            "fields.total.aggregate-function": "sum"})
                  .build())
        t = FileStoreTable.create(str(tmp_path / "agg"), schema)
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        for b in range(5):
            w.write_dicts([{"id": i, "total": 1} for i in range(300)])
        wb.new_commit().commit(w.prepare_commit())
        w.close()
        rows = {r["id"]: r["total"] for r in t.to_arrow().to_pylist()}
        assert len(rows) == 300 and all(v == 5 for v in rows.values())

    def test_spillable_with_input_changelog(self, tmp_path):
        """changelog-producer=input still records EVERY arrival through
        the spill path (one changelog row per written row)."""
        t = _pk_table(tmp_path / "cl", {
            "write-buffer-size": "10kb",
            "write-buffer-spillable": "true",
            "changelog-producer": "input"})
        self._write_many(t, batches=3, per=400)
        snap = t.snapshot_manager.latest_snapshot()
        plan = t.new_scan().plan_changelog(snap)
        total = sum(f.row_count for s in plan.splits
                    for f in s.data_files)
        assert total == 3 * 400

    def test_spill_dirs_cleaned_on_abort(self, tmp_path):
        """close() without prepare_commit removes spill temp dirs.
        Serial flush path: the mid-write spill-exists precondition is
        deterministic only inline — the pipelined abort-cleanup twin
        lives in test_write_pipeline.py."""
        t = _pk_table(tmp_path / "abort", {
            "write-buffer-size": "10kb",
            "write-buffer-spillable": "true",
            "write.flush.parallelism": "1"})
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        for b in range(4):
            w.write_dicts([{"id": i, "seq": b, "v": 1.0}
                           for i in range(400)])
        assert _spill_dirs() - self._before   # spills exist mid-write
        w.close()                     # abort: no prepare_commit
        assert _spill_dirs() == self._before
