"""`compact_table` runs its groups' compaction tasks side by side
(ISSUE 31): same rows as the oracle and as the one-worker run, one
COMPACT snapshot, messages in `groups`' order whatever order the tasks
finish in, the failure rule, the inline path, the streamed group alone,
`group_filter` and the byte budget."""

import os
import threading
import time

import pytest

from paimon_tpu import predicate as P
from paimon_tpu.compact.manager import MergeTreeCompactManager
from paimon_tpu.core.commit import FileStoreCommit
from paimon_tpu.metrics import global_registry
from paimon_tpu.schema import Schema
from paimon_tpu.table import FileStoreTable
from paimon_tpu.types import BigIntType
from tests.failing_fileio import FailingFileIO, InjectedIOError
from tests.store_oracle import StoreOracle, _rows_equal

# engine -> buckets, as ISSUE 31 lists them
ENGINE_BUCKETS = {"deduplicate": 8, "aggregation": 4, "partial-update": 2}


@pytest.fixture
def cores(monkeypatch):
    """`os.cpu_count` as the ceiling sees it."""
    def set_cores(n):
        monkeypatch.setattr(os, "cpu_count", lambda: n)
    set_cores(8)
    return set_cores


def _oracle(path, engine, buckets, seed=31, writes=6, partitioned=False,
            key_space=400):
    o = StoreOracle(str(path), seed, engine=engine, bucket=str(buckets),
                    partitioned=partitioned, key_space=key_space,
                    allow_expire=False, allow_schema_add=False)
    for _ in range(writes):
        o.step_write()
    return o


def _sorted_rows(table):
    return sorted(table.to_arrow().to_pylist(),
                  key=lambda r: (r["pt"], r["id"]))


def _peak():
    return global_registry().group("compaction") \
        .gauge("concurrent_tasks_peak").value


def _compact_threads():
    return sorted(t.name for t in threading.enumerate()
                  if t.name.startswith("paimon-compact"))


def _record_tasks(monkeypatch, delay=None):
    """Wrap `do_compact`: every task's (bucket, thread name, most tasks
    running while it ran); `delay(bucket)` seconds are slept first."""
    seen, running, lock = [], set(), threading.Lock()
    inner = MergeTreeCompactManager.do_compact

    def do_compact(self, unit):
        key = (tuple(self.partition), self.bucket)
        with lock:
            running.add(key)
        most = len(running)
        try:
            if delay is not None:
                time.sleep(delay(self.bucket))
            out = inner(self, unit)
            with lock:
                most = max(most, len(running))
            return out
        finally:
            with lock:
                running.discard(key)
            seen.append((key, threading.current_thread().name, most,
                         sum(f.row_count for f in unit.files)))
    monkeypatch.setattr(MergeTreeCompactManager, "do_compact", do_compact)
    return seen


def _record_commits(monkeypatch):
    """Every `FileStoreCommit.commit` call's messages."""
    calls = []
    inner = FileStoreCommit.commit

    def commit(self, messages, *args, **kwargs):
        calls.append(list(messages))
        return inner(self, messages, *args, **kwargs)
    monkeypatch.setattr(FileStoreCommit, "commit", commit)
    return calls


@pytest.mark.parametrize("engine", sorted(ENGINE_BUCKETS))
def test_side_by_side_equals_the_oracle_and_one_snapshot(
        tmp_path, cores, monkeypatch, engine):
    buckets = ENGINE_BUCKETS[engine]
    o = _oracle(tmp_path / "t", engine, buckets)
    _record_tasks(monkeypatch, delay=lambda b: 0.05)    # they overlap
    before = o.table.latest_snapshot().id
    sid = o.table.compact(full=True)
    assert sid == before + 1
    assert o.table.latest_snapshot().id == sid       # exactly one more
    assert o.table.latest_snapshot().commit_kind == "COMPACT"
    assert 2 <= _peak() <= buckets
    o.check_now(f"side-by-side full compaction ({engine})")
    # fully compacted: one file a bucket
    plan = o.table.new_scan().plan()
    assert sorted(s.bucket for s in plan.splits) == list(range(buckets))
    assert all(len(s.data_files) == 1 for s in plan.splits)
    assert _compact_threads() == []


@pytest.mark.parametrize("engine", sorted(ENGINE_BUCKETS))
def test_side_by_side_equals_the_one_worker_run(tmp_path, cores,
                                                monkeypatch, engine):
    """Twins from one seed, one compacted on eight cores and one on a
    single core (the serial loop): the same rows, and the same files
    by bucket, level and row count."""
    buckets = ENGINE_BUCKETS[engine]
    many = _oracle(tmp_path / "many", engine, buckets)
    one = _oracle(tmp_path / "one", engine, buckets)
    _record_tasks(monkeypatch, delay=lambda b: 0.05)    # they overlap
    assert many.table.compact(full=True) is not None
    assert 2 <= _peak() <= buckets
    cores(1)
    assert one.table.compact(full=True) is not None
    assert _peak() == 1
    assert _rows_equal(_sorted_rows(many.table),
                       _sorted_rows(one.table)) is None

    def files(table):
        return sorted((s.bucket, f.level, f.row_count)
                      for s in table.new_scan().plan().splits
                      for f in s.data_files)
    assert files(many.table) == files(one.table)


def _append_dv_table(path, buckets=4, commits=5, rows=40):
    schema = (Schema.builder().column("id", BigIntType(False))
              .column("x", BigIntType())
              .options({"bucket": str(buckets), "bucket-key": "id"})
              .build())
    table = FileStoreTable.create(str(path), schema)
    for c in range(commits):
        wb = table.new_batch_write_builder()
        with wb.new_write() as w:
            w.write_dicts([{"id": c * rows + i, "x": c}
                           for i in range(rows)])
            wb.new_commit().commit(w.prepare_commit())
    table.delete_where(P.less_than("id", 30))
    return FileStoreTable.load(str(path))


def test_append_buckets_with_deletion_vectors_side_by_side(
        tmp_path, cores, monkeypatch):
    many = _append_dv_table(tmp_path / "many")
    one = _append_dv_table(tmp_path / "one")
    want = list(range(30, 200))
    commits = _record_commits(monkeypatch)
    assert many.compact(full=True) is not None
    # one commit of all four buckets (its index entries ride with it)
    assert [sorted(m.bucket for m in c) for c in commits] == \
        [[0, 1, 2, 3]]
    assert many.latest_snapshot().commit_kind == "COMPACT"
    assert 1 <= _peak() <= 4
    assert sorted(many.to_arrow().column("id").to_pylist()) == want
    cores(1)
    assert one.compact(full=True) is not None
    assert _peak() == 1
    assert sorted(one.to_arrow().column("id").to_pylist()) == want
    for table in (many, one):
        # the deleted rows are gone for good: no vector is left
        snap = table.snapshot_manager.latest_snapshot()
        entries = table.new_scan().index_manifest_file.read(
            snap.index_manifest) if snap.index_manifest else []
        assert not [e for e in entries
                    if e.index_file.index_type == "DELETION_VECTORS"]
        plan = table.new_scan().plan()
        assert all(len(s.data_files) == 1 for s in plan.splits)
    assert _compact_threads() == []


def test_messages_keep_the_groups_order_when_tasks_finish_in_reverse(
        tmp_path, cores, monkeypatch):
    o = _oracle(tmp_path / "t", "deduplicate", 8)
    from paimon_tpu.compact.compact_action import _group_entries
    scan = o.table.new_scan()
    order = [bucket for _, bucket in _group_entries(
        scan, o.table.snapshot_manager.latest_snapshot())[0]]
    assert sorted(order) == list(range(8))
    # the first group submitted sleeps longest
    rank = {b: i for i, b in enumerate(order)}
    seen = _record_tasks(monkeypatch,
                         delay=lambda b: 0.04 * (8 - rank[b]))
    commits = _record_commits(monkeypatch)
    assert o.table.compact(full=True) is not None
    finished = [key[1] for key, *_ in seen]
    assert finished != order, "the tasks did not finish out of order"
    assert len(commits) == 1                          # one commit
    assert [m.bucket for m in commits[0]] == order
    assert max(most for _, _, most, _ in seen) >= 2
    o.check_now("compaction with reversed completions")


class _BucketFault(FailingFileIO):
    """Fails every mutating operation under one bucket's directory."""

    def __init__(self, inner, bucket):
        super().__init__(inner, f"bucket-fault-{bucket}")
        self.marker = f"/bucket-{bucket}/"

    def _tick(self, op, path):
        if self.marker in path:
            raise InjectedIOError(f"injected failure: {op} {path}")


@pytest.mark.parametrize("bucket", [0, 5])
def test_a_failing_bucket_raises_commits_nothing_and_leaks_no_thread(
        tmp_path, cores, monkeypatch, bucket):
    o = _oracle(tmp_path / "t", "deduplicate", 8)
    before = o.table.latest_snapshot().id
    threads = sorted(t.name for t in threading.enumerate())
    broken = FileStoreTable(_BucketFault(o.table.file_io, bucket),
                            o.table.path, o.table.schema_manager.latest())
    commits = _record_commits(monkeypatch)
    with pytest.raises(InjectedIOError, match=f"/bucket-{bucket}/"):
        broken.compact(full=True)
    assert commits == []
    assert o.table.latest_snapshot().id == before
    assert sorted(t.name for t in threading.enumerate()) == threads
    # nothing was lost: the table reads as before and compacts cleanly
    o.check_now("failed compaction")
    assert o.table.compact(full=True) == before + 1
    o.check_now("compaction after the failure")


def test_the_first_failure_in_group_order_is_the_one_raised(
        tmp_path, cores, monkeypatch):
    """Two groups fail; the later one fails first in time."""
    o = _oracle(tmp_path / "t", "deduplicate", 8)
    from paimon_tpu.compact.compact_action import _group_entries
    order = [bucket for _, bucket in _group_entries(
        o.table.new_scan(),
        o.table.snapshot_manager.latest_snapshot())[0]]
    early, late = order[1], order[6]
    inner = MergeTreeCompactManager.do_compact

    def do_compact(self, unit):
        if self.bucket == late:
            raise RuntimeError(f"bucket {late} failed")
        if self.bucket == early:
            time.sleep(0.3)
            raise RuntimeError(f"bucket {early} failed")
        return inner(self, unit)
    monkeypatch.setattr(MergeTreeCompactManager, "do_compact", do_compact)
    before = o.table.latest_snapshot().id
    with pytest.raises(RuntimeError, match=f"bucket {early} failed"):
        o.table.compact(full=True)
    assert o.table.latest_snapshot().id == before
    assert _compact_threads() == []


def test_nothing_is_admitted_after_a_failure(tmp_path, cores,
                                             monkeypatch):
    """Two cores, eight groups: the first task fails while the second
    runs; the six behind them never start."""
    cores(2)
    o = _oracle(tmp_path / "t", "deduplicate", 8)
    started = []
    inner = MergeTreeCompactManager.do_compact

    def do_compact(self, unit):
        started.append(self.bucket)
        if len(started) == 1:
            raise RuntimeError("first task failed")
        time.sleep(0.3)
        return inner(self, unit)
    monkeypatch.setattr(MergeTreeCompactManager, "do_compact", do_compact)
    with pytest.raises(RuntimeError, match="first task failed"):
        o.table.compact(full=True)
    assert len(started) <= 3, started
    assert _compact_threads() == []


@pytest.mark.parametrize("engine", ["deduplicate", "append"])
def test_one_group_runs_on_the_calling_thread(tmp_path, cores,
                                              monkeypatch, engine):
    from paimon_tpu.parallel import executors
    pools = []
    inner_pool = executors.new_thread_pool

    def new_thread_pool(workers, prefix):
        pools.append(prefix)
        return inner_pool(workers, prefix)
    monkeypatch.setattr(executors, "new_thread_pool", new_thread_pool)
    if engine == "append":
        table = _append_dv_table(tmp_path / "t", buckets=1)
        assert table.compact(full=True) is not None
        assert sorted(table.to_arrow().column("id").to_pylist()) == \
            list(range(30, 200))
    else:
        o = _oracle(tmp_path / "t", engine, 1)
        seen = _record_tasks(monkeypatch)
        assert o.table.compact(full=True) is not None
        assert [(name, most) for _, name, most, _ in seen] == \
            [(threading.current_thread().name, 1)]
        o.check_now("inline compaction")
    assert "paimon-compact" not in pools
    assert _peak() == 1


def test_only_the_groups_with_work_count(tmp_path, cores, monkeypatch):
    """A second full compaction of a compacted table has no group with
    work: no task, no pool, no snapshot."""
    o = _oracle(tmp_path / "t", "deduplicate", 8)
    assert o.table.compact(full=True) is not None
    seen = _record_tasks(monkeypatch)
    latest = o.table.latest_snapshot().id
    assert o.table.compact(full=True) is None
    assert seen == [] and o.table.latest_snapshot().id == latest


def test_a_streamed_group_runs_alone(tmp_path, cores, monkeypatch):
    """Partition 0 holds most rows and is over the (lowered) streaming
    threshold: while one of its buckets is in flight nothing else is;
    the small groups of the other partitions still run side by side."""
    o = StoreOracle(str(tmp_path / "t"), 31, engine="deduplicate",
                    bucket="2", partitioned=True, key_space=4000,
                    allow_expire=False, allow_schema_add=False)
    real = o.rng.randrange
    # three of four rows go to partition 0
    o._gen_row = _skewed_rows(o, real)
    for _ in range(12):
        o.step_write()
    rows = {}
    for s in o.table.new_scan().plan().splits:
        rows[(tuple(s.partition), s.bucket)] = sum(
            f.row_count for f in s.data_files)
    threshold = max(v for k, v in rows.items() if k[0] != (0,))
    assert min(rows[((0,), b)] for b in (0, 1)) > threshold
    table = o.table.copy(
        {"tpu.merge.stream-threshold-rows": str(threshold)})
    seen = _record_tasks(monkeypatch, delay=lambda b: 0.05)
    assert table.compact(full=True) is not None
    assert len(seen) == len(rows) == 6
    for key, name, most, task_rows in seen:
        assert name.startswith("paimon-compact")
        if task_rows > threshold:
            assert key[0] == (0,) and most == 1, (key, most)
    assert max(most for *_, most, _ in seen) >= 2     # the small ones
    o.check_now("compaction with streamed groups")


def _skewed_rows(o, randrange):
    def gen_row():
        pt = 0 if o.rng.random() < 0.75 else 1 + randrange(2)
        kid = randrange(o.key_space)
        return (pt, kid), {"v1": randrange(1000),
                           "v2": round(o.rng.uniform(0, 100), 6),
                           "name": o.rng.choice(["a", "b", None])}
    return gen_row


def test_every_group_streamed_reads_a_peak_of_one(tmp_path, cores,
                                                  monkeypatch):
    o = _oracle(tmp_path / "t", "deduplicate", 4)
    table = o.table.copy({"tpu.merge.stream-threshold-rows": "1"})
    seen = _record_tasks(monkeypatch, delay=lambda b: 0.02)
    assert table.compact(full=True) is not None
    assert len(seen) == 4
    assert all(most == 1 for *_, most, _ in seen)
    assert _peak() == 1
    o.check_now("streamed groups in turn")


def test_group_filter_skips_groups_without_a_worker(tmp_path, cores,
                                                    monkeypatch):
    from paimon_tpu import obs
    o = _oracle(tmp_path / "t", "deduplicate", 8)
    seen = _record_tasks(monkeypatch)
    asked = []

    def owns(partition, bucket):
        asked.append((threading.current_thread().name, bucket))
        return bucket in (2, 6)
    obs.enable_tracing(max_spans=10_000)
    try:
        assert o.table.compact(full=True, group_filter=owns) is not None
        spans = obs.take_spans()
    finally:
        obs.disable_tracing()
        obs.collector().clear()
    assert sorted(b for _, b in asked) == list(range(8))
    assert {name for name, _ in asked} == \
        {threading.current_thread().name}
    assert sorted(key[1] for key, *_ in seen) == [2, 6]
    top = next(s for s in spans if s.name == "compact.table")
    assert (top.attrs["groups"], top.attrs["workers"]) == (2, 2)
    assert 1 <= _peak() <= 2
    # the other six keep their runs
    plan = o.table.new_scan().plan()
    assert {s.bucket for s in plan.splits
            if len(s.data_files) == 1} == {2, 6}
    o.check_now("filtered compaction")


def test_a_budget_below_one_group_still_admits_one(tmp_path, cores,
                                                   monkeypatch):
    o = _oracle(tmp_path / "t", "deduplicate", 8)
    table = o.table.copy({"read.prefetch.max-bytes": "1 b"})
    seen = _record_tasks(monkeypatch, delay=lambda b: 0.02)
    assert table.compact(full=True) is not None
    assert len(seen) == 8
    assert all(most == 1 for *_, most, _ in seen)
    assert _peak() == 1
    o.check_now("compaction under a one-byte budget")


def test_the_budget_bounds_the_groups_in_flight(tmp_path, cores,
                                                monkeypatch):
    """A budget of about three groups' bytes: more than one in flight,
    fewer than all eight."""
    o = _oracle(tmp_path / "t", "deduplicate", 8)
    sizes = [sum(f.file_size for f in s.data_files)
             for s in o.table.new_scan().plan().splits]
    table = o.table.copy(
        {"read.prefetch.max-bytes": f"{3 * max(sizes)} b"})
    seen = _record_tasks(monkeypatch, delay=lambda b: 0.05)
    assert table.compact(full=True) is not None
    assert len(seen) == 8
    assert 2 <= _peak() <= 3 * max(sizes) // min(sizes) < 8
    o.check_now("compaction under a three-group budget")


def test_the_ceiling_is_the_scans(cores):
    from paimon_tpu.parallel.scan_pipeline import (
        default_parallelism, resolve_parallelism,
    )
    for n, want in ((1, 1), (4, 4), (8, 8), (64, 8)):
        cores(n)
        assert default_parallelism() == want == resolve_parallelism(None)


def test_more_groups_than_cores_under_a_short_switch_interval(tmp_path,
                                                              cores):
    """Sixteen append buckets on eight workers, the interpreter switching
    threads every 10 us: the shared path factory hands every output file
    its own name, every row survives, every bucket is compacted."""
    import sys
    table = _append_dv_table(tmp_path / "t", buckets=16, commits=4,
                             rows=160)
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        assert table.compact(full=True) is not None
    finally:
        sys.setswitchinterval(was)
    assert 2 <= _peak() <= 8
    plan = table.new_scan().plan()
    names = [f.file_name for s in plan.splits for f in s.data_files]
    assert len(names) == len(set(names)) == 16
    assert sorted(table.to_arrow().column("id").to_pylist()) == \
        list(range(30, 640))
    assert _compact_threads() == []


@pytest.mark.parametrize("engine", ["deduplicate", "append"])
def test_the_link_is_read_before_the_tasks_contend_for_the_host(
        tmp_path, cores, monkeypatch, engine):
    """On an accelerator the merge router times the link once a
    process; with tasks side by side that reading is taken on the
    calling thread before the first task starts (a table without keys
    merges nothing and takes none)."""
    from paimon_tpu.ops import merge as M
    events = []
    # built first: a flush sorts through the router too
    table = _append_dv_table(tmp_path / "t") if engine == "append" \
        else None
    o = None if table else _oracle(tmp_path / "t", engine, 4)
    monkeypatch.delenv("PAIMON_FORCE_HOST_SORT", raising=False)
    monkeypatch.delenv("PAIMON_FORCE_DEVICE_SORT", raising=False)
    monkeypatch.setattr(M.jax, "default_backend", lambda: "tpu")

    def reading():          # a link too narrow to pay: merges stay here
        events.append(("link", threading.current_thread().name))
        return (1e6, 1e6)
    monkeypatch.setattr(M, "_measure_link_bandwidth", reading)
    if table is not None:
        assert table.compact(full=True) is not None
        assert events == []
        return
    inner = MergeTreeCompactManager.do_compact

    def do_compact(self, unit):
        events.append(("task", threading.current_thread().name))
        return inner(self, unit)
    monkeypatch.setattr(MergeTreeCompactManager, "do_compact", do_compact)
    assert o.table.compact(full=True) is not None
    assert events[0] == ("link", threading.current_thread().name)
    assert sum(kind == "task" for kind, _ in events) == 4
    monkeypatch.undo()          # the check scans: off the fake backend
    o.check_now("compaction with the link read ahead")
