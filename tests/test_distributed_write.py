"""Multi-host write plane (parallel/distributed.py): sharded bucket
ownership, commit arbitration, snapshot-consistent cross-host scans,
online rescale.

Three layers:

1. Fake-topology unit tests — two `DistributedWritePlane`s with
   explicit (process_index, process_count) over ONE table in ONE
   process exercise the ownership split, routing modes, property
   stamping, version resume, rescale handoff and conflict accounting
   without a mesh (the agreement primitives degrade to no-ops at
   jax.process_count()==1).

2. REAL 2-process harnesses (tier-1) — subprocess workers bring up
   jax's distributed runtime (Gloo CPU collectives, the
   test_multihost_real recipe), form one 8-device mesh and drive the
   actual cross-host contract: disjoint input streams rerouted to
   owners over the mesh ('exchange'), concurrent CAS-arbitrated
   commits, coordinator (single-committer) arbitration, pinned
   cross-host scans, rescale under live traffic.  The parent then
   audits the ISSUE's acceptance: final table byte-identical to the
   single-process oracle, linear snapshot history, fsck-clean, and
   the multihost metric group live on the Prometheus /metrics
   endpoint.

3. A slow 4-process soak — bounded 503 storms (FailingFileIO) riding
   the write-retry ladder, plus one process killed MID-COMMIT (after
   its manifests uploaded, before the snapshot CAS): survivors
   converge, the dead process's staged files never reach the table,
   and maintenance sweeps them (remove_orphan_files + fsck clean).
"""

import os
import socket
import subprocess
import sys

import pyarrow as pa
import pytest

from paimon_tpu.schema import Schema
from paimon_tpu.table import FileStoreTable
from paimon_tpu.types import BigIntType, IntType

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_NO_CPU_COLLECTIVES = "Multiprocess computations aren't implemented"


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _schema(buckets: int = 4, extra=None):
    opts = {"bucket": str(buckets)}
    opts.update(extra or {})
    return (Schema.builder()
            .column("id", BigIntType(False))
            .column("v", IntType())
            .primary_key("id")
            .options(opts)
            .build())


def _oracle(tmp_path, rows, buckets: int = 4) -> pa.Table:
    """Single-process reference ingest of the same global rows."""
    t = FileStoreTable.create(str(tmp_path / "oracle"), _schema(buckets))
    wb = t.new_batch_write_builder()
    with wb.new_write() as w:
        w.write_dicts(rows)
        wb.new_commit().commit(w.prepare_commit())
    return t.to_arrow().sort_by("id")


def _assert_linear_snapshots(table, allowed_users):
    """Snapshot history is linear: ids contiguous from earliest to
    latest, every snapshot present and committed by an expected
    user."""
    sm = table.snapshot_manager
    earliest, latest = sm.earliest_snapshot_id(), sm.latest_snapshot_id()
    assert earliest == 1
    users = set()
    for sid in range(earliest, latest + 1):
        assert sm.snapshot_exists(sid), f"gap at snapshot {sid}"
        users.add(sm.snapshot(sid).commit_user)
    assert users <= set(allowed_users), users


def _run_workers(worker_src, tmp_path, n_procs, args=None,
                 expected_rc=None, timeout=420):
    port = _free_port()
    table_path = str(tmp_path / "t")
    worker_py = tmp_path / "worker.py"
    worker_py.write_text(worker_src)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)       # workers pin their own devices
    procs = [subprocess.Popen(
        [sys.executable, str(worker_py), str(pid), str(port),
         table_path, REPO, str(n_procs)] + [str(a) for a in (args or [])],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for pid in range(n_procs)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    if any(_NO_CPU_COLLECTIVES in out for out in outs):
        pytest.skip("jaxlib CPU backend lacks Gloo cross-process "
                    "collectives; multi-host CPU emulation cannot run")
    for pid, (p, out) in enumerate(zip(procs, outs)):
        want = (expected_rc or {}).get(pid, 0)
        assert p.returncode == want, \
            f"proc {pid} rc={p.returncode} (want {want}):\n{out[-4000:]}"
    return table_path, outs


# -- 1. fake-topology unit tests ---------------------------------------------

class TestOwnership:
    def test_owner_deterministic_and_covering(self):
        from paimon_tpu.parallel.distributed import owner_of
        owners = [owner_of((), b, 4) for b in range(64)]
        assert owners == [owner_of((), b, 4) for b in range(64)]
        assert set(owners) == {0, 1, 2, 3}        # everyone owns some
        assert all(0 <= o < 4 for o in owners)
        # partitions shard too, and differently from bare buckets
        assert owner_of(("2024-01-01",), 0, 4) in range(4)
        assert owner_of((), 0, 1) == 0

    def test_handoffs_counts_moved_and_new_buckets(self):
        from paimon_tpu.parallel.distributed import OwnershipMap
        a = OwnershipMap(1, 2, 4)
        b = OwnershipMap(2, 2, 8)
        moved = a.handoffs_to(b)
        expect = 4        # 4 brand-new buckets; owners of 0..3 keep
        expect += sum(1 for i in range(4)
                      if a.owner_of((), i) != b.owner_of((), i))
        assert moved == expect

    def test_properties_roundtrip(self):
        from paimon_tpu.parallel.distributed import (
            OWNERSHIP_BUCKETS_PROP, OWNERSHIP_PROCESSES_PROP,
            OWNERSHIP_VERSION_PROP, OwnershipMap,
        )
        p = OwnershipMap(3, 2, 8).to_properties()
        assert p[OWNERSHIP_VERSION_PROP] == "3"
        assert p[OWNERSHIP_PROCESSES_PROP] == "2"
        assert p[OWNERSHIP_BUCKETS_PROP] == "8"


class TestFakeTopologyPlane:
    """Two planes with explicit (pid, count) over one table — the
    ownership/arbitration logic minus the mesh collectives."""

    def _planes(self, tmp_path, routing="spmd", extra=None):
        opts = {"multihost.write.routing": routing}
        opts.update(extra or {})
        t = FileStoreTable.create(str(tmp_path / "t"),
                                  _schema(4, opts))
        p0 = t.new_distributed_write(process_index=0, process_count=2)
        # second plane is process 1 of the same 2-process topology,
        # over its own table handle (separate writers, one store)
        p1 = FileStoreTable.load(str(tmp_path / "t")) \
            .new_distributed_write(process_index=1, process_count=2)
        return t, p0, p1

    def test_spmd_split_covers_and_commits_converge(self, tmp_path):
        t, p0, p1 = self._planes(tmp_path)
        rows = [{"id": i, "v": i} for i in range(200)]
        for p in (p0, p1):                 # identical global batch
            p.write_dicts(rows)
            assert p.commit() is not None
        final = FileStoreTable.load(t.path).to_arrow().sort_by("id")
        assert final.num_rows == 200       # zero lost, zero dup
        assert final.column("id").to_pylist() == list(range(200))
        assert FileStoreTable.load(t.path).fsck().ok
        p0.close(), p1.close()

    def test_ownership_split_is_disjoint(self, tmp_path):
        t, p0, p1 = self._planes(tmp_path)
        data = pa.table({"id": pa.array(range(500), pa.int64()),
                         "v": pa.array([0] * 500, pa.int32())})
        l0, f0, _ = p0._split_local_foreign(data)
        l1, f1, _ = p1._split_local_foreign(data)
        assert sorted(set(l0) | set(l1)) == list(range(500))
        assert set(l0).isdisjoint(set(l1))
        assert sorted(set(l0) | set(f0)) == list(range(500))
        p0.close(), p1.close()

    def test_local_only_raises_on_foreign_rows(self, tmp_path):
        from paimon_tpu.parallel.distributed import OwnershipError
        t, p0, p1 = self._planes(tmp_path, routing="local-only")
        with pytest.raises(OwnershipError, match="local-only"):
            p0.write_dicts([{"id": i, "v": 0} for i in range(100)])
        p0.close(), p1.close()

    def test_commit_stamps_ownership_properties(self, tmp_path):
        from paimon_tpu.parallel.distributed import (
            OWNERSHIP_VERSION_PROP, resume_ownership_version,
        )
        t, p0, p1 = self._planes(tmp_path)
        p0.write_dicts([{"id": i, "v": 0} for i in range(50)])
        p0.commit()
        snap = FileStoreTable.load(t.path).latest_snapshot()
        assert snap.properties[OWNERSHIP_VERSION_PROP] == "1"
        assert resume_ownership_version(FileStoreTable.load(t.path)) == 1
        p0.close(), p1.close()

    def test_rescale_drain_handoff(self, tmp_path):
        from paimon_tpu.metrics import (
            MULTIHOST_OWNERSHIP_HANDOFFS, global_registry,
        )
        t, p0, p1 = self._planes(tmp_path)
        rows1 = [{"id": i, "v": 1} for i in range(100)]
        for p in (p0, p1):
            p.write_dicts(rows1)
        # fake topology runs the two planes SEQUENTIALLY, so the
        # drains must land before the first rescale call like the
        # real-mesh barrier orders them — p1 draining after p0's
        # rewrite would stamp the old ownership generation past the
        # new one, which fsck now flags as ownership-inconsistency
        # (the REAL 2-process coordinator test covers true
        # buffered-rows-during-rescale traffic)
        p0.commit()
        p1.commit()
        handoffs = global_registry().multihost_metrics().counter(
            MULTIHOST_OWNERSHIP_HANDOFFS)
        before = handoffs.count
        p0.rescale_buckets(8)              # elected rewriter
        p1.rescale_buckets(8)              # peer: drain + reopen only
        assert p0.table.options.bucket == 8
        assert p1.table.options.bucket == 8
        assert p0.ownership.version == 2 == p1.ownership.version
        assert handoffs.count > before
        rows2 = [{"id": 100 + i, "v": 2} for i in range(60)]
        for p in (p0, p1):
            p.write_dicts(rows2)
            p.commit()
        final = FileStoreTable.load(t.path)
        assert final.to_arrow().num_rows == 160
        assert final.options.bucket == 8
        assert final.fsck().ok
        p0.close(), p1.close()

    def test_rescale_preserves_dynamic_options_and_stamps_version(
            self, tmp_path):
        """Review fixes: (1) the handoff reload must re-apply
        load-time dynamic options (copy() REPLACES them — losing
        write-only / retry tuning mid-run changed behavior after a
        rescale); (2) the rescale overwrite snapshot itself carries
        the bumped ownership version, so a process restarting before
        the first post-rescale commit cannot resume a regressed
        generation."""
        from paimon_tpu.options import CoreOptions
        from paimon_tpu.parallel.distributed import (
            OWNERSHIP_VERSION_PROP, resume_ownership_version,
        )
        FileStoreTable.create(str(tmp_path / "t"), _schema(4))
        t = FileStoreTable.load(
            str(tmp_path / "t"),
            dynamic_options={"write-only": "true",
                             "write.retry.max-attempts": "8"})
        plane = t.new_distributed_write(process_index=0,
                                        process_count=1)
        plane.write_dicts([{"id": i, "v": 1} for i in range(60)])
        plane.rescale_buckets(8)
        assert plane.table.options.write_only is True
        assert plane.table.options.get(
            CoreOptions.WRITE_RETRY_MAX_ATTEMPTS) == 8
        fresh = FileStoreTable.load(str(tmp_path / "t"))
        assert fresh.latest_snapshot().properties[
            OWNERSHIP_VERSION_PROP] == "2"
        assert resume_ownership_version(fresh) == 2
        plane.close()

    def test_cas_conflict_counted(self, tmp_path, monkeypatch):
        from paimon_tpu.metrics import (
            MULTIHOST_COMMIT_CONFLICTS, MULTIHOST_COMMIT_RETRIES,
            global_registry,
        )
        from paimon_tpu.snapshot import SnapshotManager
        t, p0, p1 = self._planes(
            tmp_path, extra={"commit.min-retry-wait": "1",
                             "commit.max-retry-wait": "2"})
        g = global_registry().multihost_metrics()
        conflicts = g.counter(MULTIHOST_COMMIT_CONFLICTS)
        retries = g.counter(MULTIHOST_COMMIT_RETRIES)
        c0, r0 = conflicts.count, retries.count
        real = SnapshotManager.try_commit
        lost = {"n": 0}

        def race_once(self, snap):
            if lost["n"] == 0:
                # an honest race: a concurrent peer lands the
                # contested id first, so THIS CAS genuinely loses and
                # the commit re-resolves against the new latest
                lost["n"] = 1
                wb = FileStoreTable.load(t.path) \
                    .new_batch_write_builder()
                wb.commit_user = "peer"
                with wb.new_write() as w:
                    w.write_dicts([{"id": 9999, "v": 9}])
                    wb.new_commit().commit(w.prepare_commit())
            return real(self, snap)

        monkeypatch.setattr(SnapshotManager, "try_commit", race_once)
        p0.write_dicts([{"id": i, "v": 0} for i in range(40)])
        assert p0.commit() is not None
        assert conflicts.count == c0 + 1
        assert retries.count == r0 + 1
        p0.close(), p1.close()

    def test_rejects_dynamic_bucket_tables(self, tmp_path):
        from paimon_tpu.parallel.distributed import OwnershipError
        t = FileStoreTable.create(str(tmp_path / "dyn"), _schema(4))
        t = t.copy({"bucket": "-1"})
        with pytest.raises(OwnershipError, match="fixed-bucket"):
            t.new_distributed_write(process_index=0, process_count=2)

    def test_rejects_append_only_tables(self, tmp_path):
        # the append writer has no buckets= route; accepting the
        # table would crash with TypeError on the FIRST write
        from paimon_tpu.parallel.distributed import OwnershipError
        schema = (Schema.builder()
                  .column("id", BigIntType(False))
                  .column("v", IntType())
                  .options({"bucket": "4", "bucket-key": "id"})
                  .build())
        t = FileStoreTable.create(str(tmp_path / "ao"), schema)
        with pytest.raises(OwnershipError, match="primary-key"):
            t.new_distributed_write(process_index=0, process_count=2)

    def test_rescale_empty_table_is_schema_change(self, tmp_path):
        # an empty drained table has nothing to rewrite: the rescale
        # is just the bucket schema change + handoff (previously a
        # misleading OwnershipError with the writer already closed) —
        # and the bumped generation is still STAMPED (forced empty
        # snapshot), so a restart resumes version 2, not 0/1
        from paimon_tpu.parallel.distributed import (
            resume_ownership_version,
        )
        t = FileStoreTable.create(str(tmp_path / "t"), _schema(4))
        plane = t.new_distributed_write(process_index=0,
                                        process_count=1)
        plane.rescale_buckets(8)
        assert plane.table.options.bucket == 8
        assert plane.ownership.version == 2
        assert resume_ownership_version(
            FileStoreTable.load(t.path)) == 2
        plane.write_dicts([{"id": 1, "v": 1}])
        plane.commit()
        plane.close()
        assert FileStoreTable.load(t.path).to_arrow().num_rows == 1

    def test_resume_bumps_version_on_topology_change(self, tmp_path):
        # a tip written by a 2-process map resumed by a 3-process
        # plane is a NEW ownership function: the version must bump,
        # never let one number denote two different maps
        t = FileStoreTable.create(str(tmp_path / "t"), _schema(4))
        p = t.new_distributed_write(process_index=0, process_count=2)
        p.write_dicts([{"id": i, "v": 0} for i in range(40)])
        p.commit()
        p.close()
        same = FileStoreTable.load(t.path).new_distributed_write(
            process_index=0, process_count=2)
        assert same.ownership.version == 1
        same.close()
        resized = FileStoreTable.load(t.path).new_distributed_write(
            process_index=0, process_count=3)
        assert resized.ownership.version == 2
        resized.close()

    def test_defaults_fill_before_ownership_hash(self, tmp_path):
        # fields.*.default-value on a nullable bucket-key column:
        # the plane must hash the DEFAULTED value like the
        # single-process path, or the row lands in (and is owned
        # via) a different bucket than the oracle's
        schema = (Schema.builder()
                  .column("id", BigIntType())
                  .column("v", IntType())
                  .primary_key("id")
                  .options({"bucket": "4",
                            "fields.id.default-value": "7"})
                  .build())
        FileStoreTable.create(str(tmp_path / "t"), schema)
        rows = [{"id": None, "v": 1}, {"id": 3, "v": 2}]
        # spmd routing: identical input on both fake processes
        planes = [FileStoreTable.load(
            str(tmp_path / "t"),
            dynamic_options={"multihost.write.routing": "spmd"})
            .new_distributed_write(process_index=i, process_count=2)
            for i in range(2)]
        for p in planes:
            p.write_dicts(rows)
            p.commit()
            p.close()
        # oracle
        ot = FileStoreTable.create(str(tmp_path / "oracle"), schema)
        wb = ot.new_batch_write_builder()
        with wb.new_write() as w:
            w.write_dicts(rows)
            wb.new_commit().commit(w.prepare_commit())
        final = FileStoreTable.load(
            str(tmp_path / "t")).to_arrow().sort_by("id")
        assert final.equals(ot.to_arrow().sort_by("id"))

    def test_rescale_partitioned_raises_before_any_barrier(
            self, tmp_path):
        # validation must raise identically on EVERY process before
        # the drain/barrier — a committer-only NotImplementedError
        # would strand the peers inside sync_global_devices
        from paimon_tpu.parallel.distributed import OwnershipError
        from paimon_tpu.types import VarCharType
        schema = (Schema.builder()
                  .column("part", VarCharType(nullable=False))
                  .column("id", BigIntType(False))
                  .column("v", IntType())
                  .partition_keys("part")
                  .primary_key("id", "part")
                  .options({"bucket": "2"}).build())
        t = FileStoreTable.create(str(tmp_path / "p"), schema)
        plane = t.new_distributed_write(process_index=1,
                                        process_count=2)
        plane.write_dicts([{"part": "a", "id": 1, "v": 1}])
        with pytest.raises(OwnershipError, match="partitioned"):
            plane.rescale_buckets(4)
        # the plane is still usable after the validation error
        plane.commit()
        plane.close()

    def test_rejects_unknown_modes(self, tmp_path):
        t = FileStoreTable.create(
            str(tmp_path / "t"),
            _schema(4, {"multihost.write.routing": "bogus"}))
        with pytest.raises(ValueError, match="routing"):
            t.new_distributed_write(process_index=0, process_count=2)
        t2 = FileStoreTable.load(
            t.path, dynamic_options={
                "multihost.write.routing": "spmd",
                "multihost.commit.arbitration": "bogus"})
        with pytest.raises(ValueError, match="arbitration"):
            t2.new_distributed_write(process_index=0, process_count=2)


# -- 2. real 2-process harnesses (tier-1) ------------------------------------

_PROLOG = r'''
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(dev)d"
import jax

pid = int(sys.argv[1]); port = sys.argv[2]; table_path = sys.argv[3]
sys.path.insert(0, sys.argv[4]); n_procs = int(sys.argv[5])

from paimon_tpu.parallel import multihost as MH

idx, count = MH.initialize(f"127.0.0.1:{port}", n_procs, pid)
assert (idx, count) == (pid, n_procs)

from paimon_tpu import Schema
from paimon_tpu.table import FileStoreTable
from paimon_tpu.types import BigIntType, IntType

def make_schema(buckets, extra):
    opts = {"bucket": str(buckets)}
    opts.update(extra)
    return (Schema.builder()
            .column("id", BigIntType(False))
            .column("v", IntType())
            .primary_key("id")
            .options(opts)
            .build())

def shared_table(buckets, extra):
    if pid == 0:
        t = FileStoreTable.create(table_path, make_schema(buckets, extra))
    MH.barrier("table-created")
    return FileStoreTable.load(table_path)
'''

_CAS_WORKER = _PROLOG % {"dev": 4} + r'''
ROWS = 400                      # global rows per checkpoint

t = shared_table(4, {"commit.min-retry-wait": "1",
                     "commit.max-retry-wait": "10"})
plane = t.new_distributed_write()
assert plane.routing == "exchange"
assert plane.commit_user == f"writer-p{pid}"

# disjoint input streams: process p ingests the ids of its parity;
# 'exchange' reroutes the share that hashes to the OTHER process's
# buckets over the mesh
for ckpt in (1, 2):
    base = (ckpt - 1) * ROWS
    mine = [{"id": base + i, "v": pid} for i in range(ROWS)
            if i % 2 == pid]
    plane.write_dicts(mine)
    sid = plane.commit(commit_identifier=ckpt)
    assert sid is not None

# snapshot-consistent cross-host scan: one pinned id, split shares
# disjoint-cover the table
sid, splits = plane.pinned_scan()
local = plane.scan_to_arrow()
counts = MH.allgather_bytes(f"{sid}:{local.num_rows}".encode())
sids = {c.decode().split(":")[0] for c in counts}
assert len(sids) == 1, f"pinned snapshot disagreement: {sids}"
total = sum(int(c.decode().split(":")[1]) for c in counts)
assert total == 2 * ROWS, total

# the multihost metric group must be live on the Prometheus endpoint
if pid == 0:
    from paimon_tpu.service.query_service import KvQueryServer
    srv = KvQueryServer(t).start()
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
    conn.request("GET", "/metrics")
    body = conn.getresponse().read().decode()
    srv.stop()
    for name in ("paimon_multihost_commit_conflicts",
                 "paimon_multihost_commit_retries",
                 "paimon_multihost_foreign_rows_routed",
                 "paimon_multihost_barrier_wait_ms"):
        assert name in body, f"missing {name} on /metrics"

plane.close()
print(f"proc {pid}: DIST-CAS-OK rows={local.num_rows} sid={sid}",
      flush=True)
'''

_COORD_WORKER = _PROLOG % {"dev": 4} + r'''
ROWS = 300

t = shared_table(4, {"multihost.write.routing": "spmd",
                     "multihost.commit.arbitration": "coordinator",
                     "write-only": "true"})
plane = t.new_distributed_write()
assert plane.commit_user == "writer-committer"

def batch(k):
    return [{"id": (k - 1) * ROWS + i, "v": k} for i in range(ROWS)]

# identical global batches on every process (SPMD); the elected
# committer gathers commit messages over the mesh and publishes ONE
# snapshot per checkpoint
for ckpt in (1, 2):
    plane.write_dicts(batch(ckpt))
    sid = plane.commit(commit_identifier=ckpt)
    assert sid == ckpt, (sid, ckpt)

# online rescale under live traffic: checkpoint 3's rows are still
# buffered when the rescale arrives — drain-and-handoff
plane.write_dicts(batch(3))
plane.rescale_buckets(8)
assert plane.table.options.bucket == 8
assert plane.ownership.version == 2

plane.write_dicts(batch(4))
plane.commit(commit_identifier=4)

sid, splits = plane.pinned_scan()
local = plane.scan_to_arrow()
counts = MH.allgather_bytes(str(local.num_rows).encode())
total = sum(int(c) for c in counts)
assert total == 4 * ROWS, total
plane.close()
print(f"proc {pid}: DIST-COORD-OK rows={local.num_rows}", flush=True)
'''


def test_distributed_cas_two_process(tmp_path):
    """ISSUE acceptance: both hosts write concurrently to disjoint
    owned buckets over a REAL 2-process gloo mesh, commit through CAS
    arbitration, and the result is byte-identical to the
    single-process oracle with a linear fsck-clean history."""
    table_path, outs = _run_workers(_CAS_WORKER, tmp_path, 2)
    for pid, out in enumerate(outs):
        assert f"proc {pid}: DIST-CAS-OK" in out, out[-2000:]

    t = FileStoreTable.load(table_path)
    rows = [{"id": i, "v": i % 2} for i in range(800)]
    oracle = _oracle(tmp_path, rows)
    final = t.to_arrow().sort_by("id")
    assert final.equals(oracle), "distributed result != oracle"
    _assert_linear_snapshots(t, {"writer-p0", "writer-p1"})
    report = t.fsck()
    assert report.ok, report.violations


def test_distributed_coordinator_and_rescale_two_process(tmp_path):
    """Coordinator arbitration publishes ONE snapshot per global
    checkpoint under the shared committer user, and an online rescale
    mid-traffic (drain-and-handoff) preserves every row."""
    table_path, outs = _run_workers(_COORD_WORKER, tmp_path, 2)
    for pid, out in enumerate(outs):
        assert f"proc {pid}: DIST-COORD-OK" in out, out[-2000:]

    t = FileStoreTable.load(table_path)
    assert t.options.bucket == 8
    rows = [{"id": (k - 1) * 300 + i, "v": k}
            for k in (1, 2, 3, 4) for i in range(300)]
    oracle = _oracle(tmp_path, rows, buckets=8)
    final = t.to_arrow().sort_by("id")
    assert final.equals(oracle), "distributed result != oracle"
    sm = t.snapshot_manager
    # ckpt1, ckpt2, rescale drain (ckpt3 rows), rescale overwrite,
    # ckpt4 — exactly one snapshot each, no CAS retries burned
    users = [sm.snapshot(s).commit_user
             for s in range(1, sm.latest_snapshot_id() + 1)]
    assert sm.latest_snapshot_id() == 5, users
    assert users.count("writer-committer") == 4
    report = t.fsck()
    assert report.ok, report.violations


# -- 3. slow 4-process soak --------------------------------------------------

_SOAK_WORKER = _PROLOG % {"dev": 2} + r'''
from paimon_tpu.fs import LocalFileIO
sys.path.insert(0, os.path.join(sys.argv[4], "tests"))
from failing_fileio import FailingFileIO

ROWS = 1200                     # global rows per checkpoint
CKPTS = 2

fio = FailingFileIO(LocalFileIO(), f"soak-p{pid}")
if pid == 0:
    FileStoreTable.create(
        table_path,
        make_schema(8, {"commit.min-retry-wait": "1",
                        "commit.max-retry-wait": "20",
                        "write.retry.max-attempts": "8",
                        "write.retry.backoff": "5"}))
MH.barrier("table-created")
t = FileStoreTable.load(table_path, file_io=fio)
plane = t.new_distributed_write()

for ckpt in (1, 2):
    base = (ckpt - 1) * ROWS
    mine = [{"id": base + i, "v": pid} for i in range(ROWS)
            if i % n_procs == pid]
    plane.write_dicts(mine)
    # bounded 503 storm right before the flush-heavy commit: the
    # write-retry ladder must absorb it (auto-disarms after 2 ops)
    FailingFileIO.reset(f"soak-p{pid}", fail_after=0, fail_times=2)
    sid = plane.commit(commit_identifier=ckpt)
    FailingFileIO.disarm(f"soak-p{pid}")
    assert sid is not None

# the pinned scan is the LAST collective: every process (victim
# included) participates, then the plane is done with the mesh
local = plane.scan_to_arrow()
plane.close()

dead_marker = table_path + ".victim-dead"
if pid == n_procs - 1:
    # victim: die MID-COMMIT — after prepare_commit uploaded data
    # files and the commit wrote its manifests, right AT the snapshot
    # CAS.  Everything staged must stay invisible and sweepable.
    from paimon_tpu.snapshot import SnapshotManager
    wb = t.new_batch_write_builder()
    wb.commit_user = "doomed"
    w = wb.new_write()
    w.write_dicts([{"id": 10_000 + i, "v": 99} for i in range(200)])
    msgs = w.prepare_commit()

    def die(self, snap):
        open(dead_marker, "w").close()
        os._exit(42)
    SnapshotManager.try_commit = die
    wb.new_commit().commit(msgs)
    raise AssertionError("unreachable: try_commit must have exited")

# survivors: wait for the victim's death (its doomed commit needs the
# coordination-service leader alive), then exit WITHOUT jax's
# distributed shutdown barrier — a dead peer makes that barrier abort
# the whole process (SIGABRT) even though all table work succeeded
import time
deadline = time.time() + 120
while not os.path.exists(dead_marker) and time.time() < deadline:
    time.sleep(0.1)
assert os.path.exists(dead_marker), "victim never reached its CAS"
print(f"proc {pid}: DIST-SOAK-OK rows={local.num_rows}", flush=True)
sys.stdout.flush()
os._exit(0)
'''


@pytest.mark.slow
def test_distributed_soak_four_process_kill_mid_commit(tmp_path):
    """4-process mesh under bounded 503 storms; the last process is
    killed mid-commit (manifests written, CAS never executed).
    Survivors' rows all land exactly once; the dead process's staged
    files never become visible and maintenance sweeps them."""
    n = 4
    table_path, outs = _run_workers(_SOAK_WORKER, tmp_path, n,
                                    expected_rc={n - 1: 42},
                                    timeout=540)
    for pid in range(n - 1):
        assert f"proc {pid}: DIST-SOAK-OK" in outs[pid], \
            outs[pid][-2000:]

    t = FileStoreTable.load(table_path)
    final = t.to_arrow().sort_by("id")
    # zero lost, zero dup from the surviving checkpoints; none of the
    # victim's doomed rows (ids >= 10_000) leaked in
    assert final.num_rows == 2 * 1200
    assert final.column("id").to_pylist() == list(range(2400))
    _assert_linear_snapshots(t, {f"writer-p{p}" for p in range(n)})
    assert t.fsck().ok

    # the kill left orphans (uploaded data files + manifests with no
    # snapshot): maintenance must SWEEP them without touching live
    # data (older_than_ms is the absolute cutoff — a far-future one
    # waives the in-flight-writer grace period for the test)
    future_ms = 2 ** 60
    swept = t.remove_orphan_files(older_than_ms=future_ms)
    assert swept, "expected the dead process's staged files as orphans"
    after = FileStoreTable.load(table_path)
    assert after.to_arrow().sort_by("id").equals(final)
    assert after.fsck().ok
