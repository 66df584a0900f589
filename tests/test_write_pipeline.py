"""Pipelined write/ingest engine (parallel/write_pipeline.py).

Row-identity of the pipelined flush pool against the serial write path
across every merge engine, the spillable buffer and append tables;
sequence-number safety under concurrent flush scheduling (reserved at
write() time, tier-1); transient-fault retry semantics (storms retry
and complete, exhausted storms RAISE at the prepare-commit barrier);
executor-thread hygiene + the in-flight byte budget (tier-1); LPT
flush scheduling; the write metric group; and the two-phase
upload-failure path-context regression.
"""

import threading
import time

import numpy as np
import pytest

from paimon_tpu.fs import get_file_io
from paimon_tpu.fs.object_store import TransientStoreError
from paimon_tpu.schema import Schema
from paimon_tpu.table import FileStoreTable
from paimon_tpu.types import BigIntType, DoubleType
from tests.store_oracle import make_random_engine_table

ENGINES = ["deduplicate", "first-row", "partial-update", "aggregation"]

# small buffers force MANY flushes per commit so the pool actually
# pipelines; parallelism 4 on a 4-bucket table exercises real overlap
PIPED = {"write.flush.parallelism": "4", "write-buffer-size": "16 kb"}
SERIAL = {"write.flush.parallelism": "1", "write-buffer-size": "16 kb"}


def _rows(table):
    return sorted(table.to_arrow().to_pylist(),
                  key=lambda r: (r["pt"], r["id"]))


def _write_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("paimon-write")]


def _wait_no_write_threads(before=(), timeout=5.0):
    """Write-pipeline threads beyond `before` still alive after a GC
    pass.  gc.collect() first: dangling executors of OTHER tests'
    never-closed writers only release their workers when collected, and
    this check is about OUR writer's close() joining OUR pool."""
    import gc
    gc.collect()
    before = set(before)
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        cur = [t for t in _write_threads() if t not in before]
        if not cur:
            return []
        time.sleep(0.01)
    return [t for t in _write_threads() if t not in before]


# -- row identity ------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
def test_pipelined_equals_serial_all_engines(tmp_path, engine):
    """Same seed, serial vs pipelined writers: the tables' full
    merge-on-read scans must be row-identical (store_oracle tables are
    bit-deterministic per seed, so the two writes are twins)."""
    serial = make_random_engine_table(
        str(tmp_path / f"s_{engine}"), seed=77, engine=engine,
        extra_options=SERIAL)
    piped = make_random_engine_table(
        str(tmp_path / f"p_{engine}"), seed=77, engine=engine,
        extra_options=PIPED)
    a, b = _rows(serial), _rows(piped)
    assert a == b and len(a) > 0


def test_pipelined_equals_serial_spillable(tmp_path):
    """write-buffer-spillable: spill writes + folding + the final
    merge ride the same per-bucket actor, so the pipelined table must
    still match serial (changelog-producer=input rides along)."""
    common = {"write-buffer-spillable": "true",
              "sort-spill-buffer-size": "8 kb",
              "local-sort.max-num-file-handles": "3",
              "write-buffer-size": "64 kb",
              "changelog-producer": "input"}
    serial = make_random_engine_table(
        str(tmp_path / "s"), seed=9, engine="deduplicate",
        extra_options={**common, "write.flush.parallelism": "1"})
    piped = make_random_engine_table(
        str(tmp_path / "p"), seed=9, engine="deduplicate",
        extra_options={**common, "write.flush.parallelism": "4"})
    assert _rows(serial) == _rows(piped)
    # both produced a changelog stream of the same total length
    def changelog_rows(t):
        return sum(s.changelog_record_count or 0
                   for s in t.snapshot_manager.snapshots())
    assert changelog_rows(serial) == changelog_rows(piped) > 0


def test_pipelined_equals_serial_append(tmp_path):
    def build(tag, par):
        schema = (Schema.builder()
                  .column("id", BigIntType(False))
                  .column("v", DoubleType())
                  .options({"bucket": "-1",
                            "write.flush.parallelism": par,
                            "write-buffer-size": "8 kb"})
                  .build())
        table = FileStoreTable.create(str(tmp_path / tag), schema)
        wb = table.new_batch_write_builder()
        with wb.new_write() as w:
            for c in range(6):
                w.write_dicts([{"id": c * 1000 + i, "v": float(i)}
                               for i in range(300)])
            wb.new_commit().commit(w.prepare_commit())
        return table
    a = build("s", "1").to_arrow().sort_by("id")
    b = build("p", "4").to_arrow().sort_by("id")
    assert a.equals(b) and a.num_rows == 1800


# -- sequence-number safety (tier-1) -----------------------------------------

def _bucket_seqs(table):
    """{(partition, bucket): sorted seq list} over every data file."""
    from paimon_tpu.core.kv_file import read_kv_file
    scan = table.new_scan()
    out = {}
    for split in table.new_read_builder().new_scan().plan().splits:
        seqs = out.setdefault((split.partition, split.bucket), [])
        for meta in split.data_files:
            t = read_kv_file(table.file_io, scan.path_factory,
                             split.partition, split.bucket, meta,
                             None, None)
            seqs.extend(t.column("_SEQUENCE_NUMBER").to_pylist())
    return {k: sorted(v) for k, v in out.items()}


def test_no_duplicate_or_reordered_seq_across_pipelined_flushes(tmp_path):
    """Sequence ranges are reserved at write() time on the caller
    thread: many concurrent flushes must never duplicate or reorder a
    sequence number within a bucket, across commits included."""
    table = make_random_engine_table(
        str(tmp_path / "t"), seed=41, engine="deduplicate",
        deletes=False, extra_options=PIPED)
    per_bucket = _bucket_seqs(table)
    assert per_bucket
    for key, seqs in per_bucket.items():
        assert len(seqs) == len(set(seqs)), \
            f"duplicate sequence numbers in bucket {key}"
    # second commit continues the per-bucket sequence from the restore
    wb = table.new_batch_write_builder()
    with wb.new_write() as w:
        w.write_dicts([{"pt": 0, "id": i, "v1": 1, "v2": 1.0,
                        "name": "x"} for i in range(50)])
        wb.new_commit().commit(w.prepare_commit())
    again = _bucket_seqs(table)
    for key, seqs in again.items():
        assert len(seqs) == len(set(seqs)), \
            f"duplicate sequence numbers after restore in bucket {key}"


# -- fault semantics ---------------------------------------------------------

class WriteStormFileIO:
    """Duck-typed FileIO: the first `faults` data-file write_bytes
    calls fail with a 503 (a passing transient storm).  Global counter,
    not per-path — retried flushes write FRESH file names."""

    def __init__(self, inner, faults=3):
        self.inner = inner
        self.left = faults
        self.faults = 0
        self.lock = threading.Lock()

    def write_bytes(self, path, data, overwrite=True):
        if path.rsplit("/", 1)[-1].startswith("data-"):
            with self.lock:
                if self.left > 0:
                    self.left -= 1
                    self.faults += 1
                    raise TransientStoreError(f"503 on {path}")
        return self.inner.write_bytes(path, data, overwrite=overwrite)

    def __getattr__(self, name):
        return getattr(self.inner, name)


def _storm_table(tmp_path, storm, **opts):
    schema = (Schema.builder()
              .column("id", BigIntType(False))
              .column("v", DoubleType())
              .primary_key("id")
              .options({"bucket": "2", "write-only": "true"})
              .build())
    table = FileStoreTable.create(str(tmp_path / "t"), schema)
    return FileStoreTable.load(
        table.path, file_io=storm,
        dynamic_options={"write.flush.parallelism": "4",
                         "write-buffer-size": "8 kb",
                         "write.retry.backoff": "0", **opts})


def test_mid_write_503_storm_retries_and_completes(tmp_path):
    from paimon_tpu.metrics import WRITE_RETRIES, global_registry
    storm = WriteStormFileIO(get_file_io(str(tmp_path)), faults=3)
    # the faults are counted across flush tasks: one task may meet all
    # three, so it gets faults + 1 attempts (the exhausted case is the
    # next test's)
    table = _storm_table(tmp_path, storm,
                         **{"write.retry.max-attempts": "4"})
    r0 = global_registry().write_metrics().counter(WRITE_RETRIES).count
    wb = table.new_batch_write_builder()
    with wb.new_write() as w:
        w.write_dicts([{"id": i, "v": float(i)} for i in range(2000)])
        wb.new_commit().commit(w.prepare_commit())
    assert storm.faults == 3
    assert global_registry().write_metrics() \
        .counter(WRITE_RETRIES).count >= r0 + 3
    got = table.to_arrow()
    assert got.num_rows == 2000


def test_exhausted_write_storm_raises_at_barrier(tmp_path):
    """A storm outliving write.retry.max-attempts must RAISE the
    original transient error at the prepare-commit barrier — a flush is
    never silently dropped — and close() must join the workers."""
    storm = WriteStormFileIO(get_file_io(str(tmp_path)), faults=10 ** 9)
    table = _storm_table(tmp_path, storm,
                         **{"write.retry.max-attempts": "2"})
    before = _write_threads()
    wb = table.new_batch_write_builder()
    w = wb.new_write()
    try:
        with pytest.raises(TransientStoreError):
            w.write_dicts([{"id": i, "v": float(i)}
                           for i in range(2000)])
            w.prepare_commit()
    finally:
        w.close()
    assert not _wait_no_write_threads(before), "leaked write threads"
    # nothing was committed
    assert table.snapshot_manager.latest_snapshot() is None


def test_non_transient_error_propagates_without_retry(tmp_path):
    from paimon_tpu.parallel.write_pipeline import FlushPool
    pool = FlushPool(parallelism=4, max_bytes=1 << 20)
    calls = []

    def bad():
        calls.append(1)
        raise ValueError("schema bug")

    pool.submit(("p", 0), 10, bad)
    with pytest.raises(ValueError, match="schema bug"):
        pool.drain()
    pool.shutdown()
    assert len(calls) == 1, "non-transient errors must not retry"


def test_failed_drain_poisons_the_pool():
    """After a drain() raised, the cancelled tasks' payloads are gone
    (snapshots detached, seqs reserved): a retried prepare on the same
    writer would silently commit with rows missing, so every later
    submit/drain must RAISE instead of pretending to succeed."""
    from paimon_tpu.parallel.write_pipeline import FlushPool
    pool = FlushPool(parallelism=2, max_bytes=1 << 30)

    def boom():
        raise ValueError("flush died")

    pool.submit(("a", 0), 1, boom)
    with pytest.raises(ValueError, match="flush died"):
        pool.drain()
    with pytest.raises(RuntimeError, match="close this writer"):
        pool.drain()
    with pytest.raises(RuntimeError, match="close this writer"):
        pool.submit(("a", 0), 1, lambda: None)
    pool.shutdown()


def test_failed_prepare_commit_never_silently_commits(tmp_path):
    """End-to-end twin of the poison test: after a prepare_commit()
    raised (exhausted storm), a second prepare_commit() on the same
    writer raises too — it must not return a partial message set."""
    storm = WriteStormFileIO(get_file_io(str(tmp_path)), faults=10 ** 9)
    table = _storm_table(tmp_path, storm,
                         **{"write.retry.max-attempts": "2"})
    wb = table.new_batch_write_builder()
    w = wb.new_write()
    try:
        with pytest.raises(TransientStoreError):
            w.write_dicts([{"id": i, "v": float(i)}
                           for i in range(2000)])
            w.prepare_commit()
        storm.left = 0                # the "storm" passes...
        with pytest.raises(RuntimeError, match="close this writer"):
            w.prepare_commit()        # ...but the writer is poisoned
    finally:
        w.close()
    assert table.snapshot_manager.latest_snapshot() is None


def test_error_cancels_queued_flushes():
    from paimon_tpu.parallel.write_pipeline import FlushPool
    pool = FlushPool(parallelism=2, max_bytes=1 << 30)
    ran = []
    gate = threading.Event()

    def slow_fail():
        gate.wait(5)
        raise ValueError("boom")

    pool.submit(("a", 0), 1, slow_fail)
    for i in range(5):
        pool.submit(("a", 0), 1, lambda i=i: ran.append(i))
    gate.set()
    with pytest.raises(ValueError, match="boom"):
        pool.drain()
    pool.shutdown()
    assert ran == [], "queued tasks after the failure must be cancelled"


# -- tier-1 hygiene: threads + byte budget -----------------------------------

def test_no_leaked_threads_after_write_close(tmp_path):
    before = _write_threads()
    table = make_random_engine_table(
        str(tmp_path / "t"), seed=1, engine="deduplicate",
        commits=1, extra_options=PIPED)
    assert not _wait_no_write_threads(before), \
        "leaked threads after close"
    assert _rows(table)


def test_flush_byte_budget_respected():
    from paimon_tpu.parallel.write_pipeline import FlushPool
    pool = FlushPool(parallelism=4, max_bytes=1)
    running = []

    def task():
        running.append(1)
        time.sleep(0.005)

    for i in range(8):
        pool.submit(("b", i), 1000, task)
    pool.drain()
    pool.shutdown()
    # a 1-byte budget degenerates to exactly one flush in flight
    assert pool.max_inflight_tasks == 1
    assert pool.peak_inflight_bytes <= 1000
    # an ample budget actually pipelines distinct buckets
    pool2 = FlushPool(parallelism=4, max_bytes=1 << 30)
    gate = threading.Event()
    for i in range(4):
        pool2.submit(("b", i), 1000, gate.wait)
    gate.set()
    pool2.drain()
    pool2.shutdown()
    assert pool2.max_inflight_tasks > 1


def test_same_bucket_flushes_never_overlap():
    """The per-key actor: two tasks of one bucket must run strictly in
    submission order, even with idle workers available."""
    from paimon_tpu.parallel.write_pipeline import FlushPool
    pool = FlushPool(parallelism=4, max_bytes=1 << 30)
    order = []
    lock = threading.Lock()

    def task(i):
        with lock:
            order.append(("start", i))
        time.sleep(0.002)
        with lock:
            order.append(("end", i))

    for i in range(6):
        pool.submit(("pt", 7), 1, lambda i=i: task(i))
    pool.drain()
    pool.shutdown()
    assert order == [(p, i) for i in range(6) for p in ("start", "end")]


@pytest.mark.parametrize("par", ["1", "4"])
def test_aggressive_spill_folding_exact_counts(tmp_path, par):
    """Regression: spill file names must be fold-proof.  With
    max-num-file-handles=2 every spill triggers a fold, and the old
    len(spills)/listdir-derived names could REPEAT after a fold shrank
    both — truncating a live run.  Counts must be exact on both the
    serial and pipelined paths (changelog-producer=input doubles as an
    exactly-once event counter)."""
    schema = (Schema.builder()
              .column("id", BigIntType(False))
              .column("v", DoubleType())
              .primary_key("id")
              .options({"bucket": "1", "write-only": "true",
                        "changelog-producer": "input",
                        "write-buffer-spillable": "true",
                        "sort-spill-buffer-size": "4 kb",
                        "local-sort.max-num-file-handles": "2",
                        "write-buffer-size": "64 kb",
                        "write.flush.parallelism": par,
                        "write.retry.backoff": "0"})
              .build())
    table = FileStoreTable.create(str(tmp_path / "t"), schema)
    wb = table.new_batch_write_builder()
    with wb.new_write() as w:
        for b in range(10):
            w.write_dicts([{"id": b * 1000 + i, "v": float(b)}
                           for i in range(200)])
        wb.new_commit().commit(w.prepare_commit())
    assert table.to_arrow().num_rows == 2000
    snap = table.snapshot_manager.latest_snapshot()
    assert snap.changelog_record_count == 2000


def test_spill_dirs_cleaned_on_pipelined_abort(tmp_path, monkeypatch):
    """close() without prepare_commit joins the pool workers and then
    removes every spill temp dir the async spill tasks created."""
    import glob
    import os
    import tempfile as _tempfile

    # a temp dir of its own: another worker's spill test running at the
    # same moment must not show up in this one's listing
    own = tmp_path / "tmp"
    own.mkdir()
    monkeypatch.setattr(_tempfile, "tempdir", str(own))

    def spill_dirs():
        return set(glob.glob(
            os.path.join(_tempfile.gettempdir(), "paimon-spill-*")))

    before = spill_dirs()
    schema = (Schema.builder()
              .column("id", BigIntType(False))
              .column("v", DoubleType())
              .primary_key("id")
              .options({"bucket": "1", "write-only": "true",
                        "write-buffer-size": "10kb",
                        "write-buffer-spillable": "true",
                        "write.flush.parallelism": "4"})
              .build())
    table = FileStoreTable.create(str(tmp_path / "t"), schema)
    wb = table.new_batch_write_builder()
    w = wb.new_write()
    # well past the 4-batch prep lookahead so spills actually schedule
    for b in range(12):
        w.write_dicts([{"id": i, "v": float(b)} for i in range(400)])
    deadline = time.monotonic() + 5.0
    while not (spill_dirs() - before) and time.monotonic() < deadline:
        time.sleep(0.01)
    assert spill_dirs() - before, "no spill dir appeared mid-write"
    w.close()                     # abort: no prepare_commit
    assert spill_dirs() == before
    assert table.snapshot_manager.latest_snapshot() is None


# -- LPT scheduling ----------------------------------------------------------

def test_prepare_commit_schedules_largest_bucket_first(tmp_path, monkeypatch):
    schema = (Schema.builder()
              .column("id", BigIntType(False))
              .column("v", DoubleType())
              .primary_key("id")
              .options({"bucket": "4", "write-only": "true",
                        "write.flush.parallelism": "4"})
              .build())
    table = FileStoreTable.create(str(tmp_path / "t"), schema)
    wb = table.new_batch_write_builder()
    with wb.new_write() as w:
        # skew: bucket of id=0 gets 10x the rows of the others
        w.write_dicts([{"id": i % 4, "v": float(i)} for i in range(40)]
                      + [{"id": 0, "v": float(i)} for i in range(400)])
        store = w._write
        submitted = []
        pool = store.flush_pool()
        real_submit = pool.submit

        def recording(key, est, fn):
            submitted.append(est)
            return real_submit(key, est, fn)

        monkeypatch.setattr(pool, "submit", recording)
        wb.new_commit().commit(w.prepare_commit())
    assert len(submitted) >= 2
    assert submitted == sorted(submitted, reverse=True), \
        f"final flushes not scheduled largest-first: {submitted}"


# -- metrics -----------------------------------------------------------------

def test_write_metric_group_exposes_pipeline_counters(tmp_path):
    from paimon_tpu.metrics import (
        WRITE_FLUSHED_BYTES, WRITE_FLUSHES, global_registry,
    )
    group = global_registry().write_metrics()
    f0 = group.counter(WRITE_FLUSHES).count
    b0 = group.counter(WRITE_FLUSHED_BYTES).count
    make_random_engine_table(str(tmp_path / "t"), seed=3,
                             engine="deduplicate", commits=1,
                             extra_options=PIPED)
    assert group.counter(WRITE_FLUSHES).count > f0
    assert group.counter(WRITE_FLUSHED_BYTES).count > b0
    snap = global_registry().snapshot()
    assert "flushes" in snap.get("write", {})


# -- two-phase upload failures carry the path (satellite bugfix) -------------

def test_two_phase_upload_failure_names_the_file(tmp_path):
    """A failed part upload inside close_for_commit() must raise the
    SAME exception type with the destination path in the message — not
    the backend's generic error."""
    from paimon_tpu.fs.object_store import (
        LocalObjectStoreBackend, ObjectStoreFileIO,
    )

    class DiskFullBackend(LocalObjectStoreBackend):
        def put(self, key, data, if_none_match=False):
            raise RuntimeError("disk full")

    fio = ObjectStoreFileIO(DiskFullBackend(str(tmp_path / "bucket")))
    s = fio.new_two_phase_stream("objfs://tbl/bucket-0/data-123.parquet")
    s.write(b"payload")
    with pytest.raises(RuntimeError,
                       match=r"tbl/bucket-0/data-123\.parquet"):
        s.close_for_commit()


def test_two_phase_close_killable_via_failing_fileio(tmp_path):
    """FailingFileIO intercepts the close()-time upload as a mutating
    op, and the injected error names the destination path (crash
    sweeps kill mid-upload through this hook)."""
    from tests.failing_fileio import FailingFileIO, InjectedIOError
    fio = FailingFileIO(get_file_io(str(tmp_path)), "tp-close")
    FailingFileIO.reset("tp-close", 0)
    try:
        s = fio.new_two_phase_stream(str(tmp_path / "part-0.bin"))
        s.write(b"x")
        with pytest.raises(InjectedIOError, match=r"part-0\.bin"):
            s.close_for_commit()
    finally:
        FailingFileIO.disarm("tp-close")
    ops = [r.op for r in FailingFileIO.ops("tp-close")]
    assert "two_phase.close" in ops
