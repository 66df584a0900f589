"""Per-bucket fault tolerance of the streaming mesh compaction engine
(parallel/mesh_engine.py §4 + parallel/fault.py): transient faults in
one bucket's window stream retry with backoff, degrade to the
single-chip path when retries exhaust, and the committed output stays
file-level identical to a fault-free run.  Non-transient errors
propagate immediately.
"""

import os

import pytest

import jax

from paimon_tpu.metrics import (
    COMPACTION_BUCKET_FAILURES, COMPACTION_BUCKET_FALLBACKS,
    COMPACTION_BUCKET_RETRIES, global_registry,
)
from paimon_tpu.parallel import (
    BucketRetryPolicy, bucket_mesh, compact_table_mesh,
    is_transient_error,
)
from paimon_tpu.parallel import mesh_engine as me
from paimon_tpu.table import FileStoreTable
from tests.failing_fileio import FailingFileIO, InjectedIOError
from tests.store_oracle import make_random_engine_table
from tests.test_mesh_engine import _bucket_kv, _rows

# the class the installed jax raises for device loss AND for compile
# refusals (jax.errors.JaxRuntimeError -> RuntimeError); the status
# that leads its message tells the two apart
from jax.errors import JaxRuntimeError  # noqa: E402


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= 8
    return bucket_mesh(8)


def _twins(tmp_path, engine, seed, **kw):
    clean = make_random_engine_table(str(tmp_path / "clean"), seed,
                                     engine, **kw)
    faulty = make_random_engine_table(str(tmp_path / "faulty"), seed,
                                      engine, **kw)
    return clean, faulty


def _broken(table, name):
    fio = FailingFileIO(table.file_io, name)
    return FileStoreTable(fio, table.path,
                          table.schema_manager.latest(),
                          branch=table.branch)


def _counter(name):
    return global_registry().compaction_metrics().counter(name).count


def _policy(**kw):
    kw.setdefault("max_attempts", 3)
    kw.setdefault("backoff_base_ms", 0.0)
    return BucketRetryPolicy(**kw)


def test_transient_fault_retries_to_identical_output(tmp_path, mesh):
    clean, faulty = _twins(tmp_path, "deduplicate", seed=101, buckets=1)
    assert compact_table_mesh(clean, mesh).snapshot_id is not None

    name = "mesh-retry"
    broken = _broken(faulty, name)
    retries0 = _counter(COMPACTION_BUCKET_RETRIES)
    FailingFileIO.reset(name, 0, fail_times=1)   # one transient kill
    try:
        stats = compact_table_mesh(broken, mesh,
                                   retry_policy=_policy())
    finally:
        FailingFileIO.disarm(name)
    assert stats.snapshot_id is not None
    assert stats.retries >= 1 and stats.fallbacks == 0
    assert _counter(COMPACTION_BUCKET_RETRIES) == retries0 + stats.retries
    assert [r for r in FailingFileIO.ops(name) if r.killed]

    reread = FileStoreTable.load(faulty.path)
    assert reread.latest_snapshot().commit_kind == "COMPACT"
    # file-level identical to the fault-free twin, not merely
    # state-identical: same keys, seqs, kinds, values per bucket
    assert _bucket_kv(reread) == _bucket_kv(clean)
    assert _rows(reread) == _rows(clean)


def test_storm_exhausts_retries_then_single_chip_fallback(tmp_path,
                                                          mesh):
    clean, faulty = _twins(tmp_path, "aggregation", seed=55, buckets=1)
    assert compact_table_mesh(clean, mesh).snapshot_id is not None

    name = "mesh-fallback"
    broken = _broken(faulty, name)
    fallbacks0 = _counter(COMPACTION_BUCKET_FALLBACKS)
    # the storm outlives the mesh retries (2 kills, max_attempts=2)
    # but has passed by the time the single-chip fallback runs
    FailingFileIO.reset(name, 0, fail_times=2)
    try:
        stats = compact_table_mesh(broken, mesh,
                                   retry_policy=_policy(max_attempts=2))
    finally:
        FailingFileIO.disarm(name)
    assert stats.snapshot_id is not None
    assert stats.retries == 1 and stats.fallbacks == 1
    assert _counter(COMPACTION_BUCKET_FALLBACKS) == fallbacks0 + 1

    reread = FileStoreTable.load(faulty.path)
    assert _bucket_kv(reread) == _bucket_kv(clean)
    assert _rows(reread) == _rows(clean)


def test_device_loss_degrades_every_bucket(tmp_path, mesh, monkeypatch):
    """A dead kernel (device/lane loss) fails every in-flight bucket;
    each rides its own ladder down to the single-chip path and the job
    still commits the fault-free result."""
    clean, faulty = _twins(tmp_path, "deduplicate", seed=77, buckets=3)
    assert compact_table_mesh(clean, mesh).snapshot_id is not None

    monkeypatch.setattr(
        me._MeshWindowKernel, "__call__",
        lambda self, *a: (_ for _ in ()).throw(
            JaxRuntimeError("UNAVAILABLE: device lost")))
    stats = compact_table_mesh(faulty, mesh,
                               retry_policy=_policy(max_attempts=2))
    assert stats.snapshot_id is not None
    assert stats.fallbacks >= 1
    reread = FileStoreTable.load(faulty.path)
    assert _bucket_kv(reread) == _bucket_kv(clean)
    assert _rows(reread) == _rows(clean)


@pytest.mark.parametrize("message", [
    "INTERNAL: Mosaic failed to compile TPU kernel: unsupported op",
    "UNIMPLEMENTED: While rewriting computation to not contain X64 "
    "element types, XLA encountered an HLO for which this rewriting "
    "is not implemented",
    "INVALID_ARGUMENT: layout mismatch",
    "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
    "memory in memory space hbm",
])
def test_compile_failure_of_the_window_kernel_propagates(
        tmp_path, mesh, monkeypatch, message):
    """A program the compiler refuses is not a lane failure: the job
    fails at the call that needed the kernel — no retry, no bucket
    quietly degraded to the single-chip manager, nothing committed."""
    table = make_random_engine_table(str(tmp_path / "t"), 21,
                                     "deduplicate", buckets=3)
    calls = {"n": 0}

    def refuse(self, *a):
        calls["n"] += 1
        raise JaxRuntimeError(message)

    monkeypatch.setattr(me._MeshWindowKernel, "__call__", refuse)
    retries0 = _counter(COMPACTION_BUCKET_RETRIES)
    fallbacks0 = _counter(COMPACTION_BUCKET_FALLBACKS)
    with pytest.raises(JaxRuntimeError, match=message.split(":")[0]):
        compact_table_mesh(table, mesh, retry_policy=_policy())
    assert calls["n"] == 1
    assert _counter(COMPACTION_BUCKET_RETRIES) == retries0
    assert _counter(COMPACTION_BUCKET_FALLBACKS) == fallbacks0
    assert FileStoreTable.load(table.path).latest_snapshot() \
        .commit_kind != "COMPACT"


def test_fallback_disabled_raises_after_retries(tmp_path, mesh):
    table = make_random_engine_table(str(tmp_path / "t"), 9,
                                     "deduplicate", buckets=1)
    name = "mesh-no-fallback"
    broken = _broken(table, name)
    failures0 = _counter(COMPACTION_BUCKET_FAILURES)
    FailingFileIO.reset(name, 0)               # hard fault: never clears
    try:
        with pytest.raises(InjectedIOError):
            compact_table_mesh(
                broken, mesh,
                retry_policy=_policy(max_attempts=2, fallback=False))
    finally:
        FailingFileIO.disarm(name)
    assert _counter(COMPACTION_BUCKET_FAILURES) == failures0 + 1
    # nothing committed; the table still reads at its last snapshot
    reread = FileStoreTable.load(table.path)
    assert reread.latest_snapshot().commit_kind != "COMPACT"
    reread.to_arrow()


def test_non_transient_error_propagates_immediately(tmp_path, mesh,
                                                    monkeypatch):
    """Programming errors must not ride the retry ladder — they would
    loop deterministically and degrade silently."""
    table = make_random_engine_table(str(tmp_path / "t"), 13,
                                     "deduplicate", buckets=1)
    calls = {"n": 0}

    def boom(self, *a, **kw):
        calls["n"] += 1
        raise ValueError("schema bug")

    monkeypatch.setattr(me._EngineContext, "merge_window_device", boom)
    monkeypatch.setattr(me._EngineContext, "merge_window_host", boom)
    with pytest.raises(ValueError, match="schema bug"):
        compact_table_mesh(table, mesh, retry_policy=_policy())
    assert calls["n"] == 1                     # no retry attempts


def test_is_transient_error_taxonomy():
    from paimon_tpu.fs.object_store import TransientStoreError

    assert is_transient_error(TransientStoreError("503"))
    assert is_transient_error(InjectedIOError("killed"))
    assert is_transient_error(OSError("io"))
    assert is_transient_error(FileNotFoundError("raced"))
    assert is_transient_error(JaxRuntimeError("UNAVAILABLE: device lost"))
    assert is_transient_error(JaxRuntimeError("device lost"))
    # the real class is what the name check keys on
    assert "JaxRuntimeError" in {c.__name__
                                 for c in JaxRuntimeError.__mro__}
    assert not is_transient_error(
        JaxRuntimeError("INTERNAL: Mosaic failed to compile"))
    assert not is_transient_error(
        JaxRuntimeError("RESOURCE_EXHAUSTED: out of HBM at compile"))
    assert not is_transient_error(ValueError("bug"))
    assert not is_transient_error(KeyError("bug"))
    assert not is_transient_error(RuntimeError("generic"))


def test_retry_policy_from_options(tmp_path):
    table = make_random_engine_table(
        str(tmp_path / "t"), 3, "deduplicate", commits=1,
        rows_per_commit=10,
        extra_options={"compaction.retry.max-attempts": "7",
                       "compaction.retry.backoff": "250 ms",
                       "compaction.mesh.fallback": "false"})
    policy = BucketRetryPolicy.from_options(table.options)
    assert policy.max_attempts == 7
    assert policy.backoff_base_ms == 250
    assert policy.fallback is False


def test_retry_policy_retry_call():
    attempts = {"n": 0}

    def flaky():
        attempts["n"] += 1
        if attempts["n"] < 3:
            raise OSError("transient")
        return "ok"

    seen = []
    policy = BucketRetryPolicy(max_attempts=3, backoff_base_ms=0)
    assert policy.retry_call(
        flaky, on_retry=lambda n, e: seen.append(n)) == "ok"
    assert attempts["n"] == 3 and seen == [1, 2]

    attempts["n"] = 0
    with pytest.raises(OSError):
        BucketRetryPolicy(max_attempts=2,
                          backoff_base_ms=0).retry_call(flaky)
    assert attempts["n"] == 2                  # capped

    def bug():
        raise ValueError("no retry")

    with pytest.raises(ValueError):
        policy.retry_call(bug)
