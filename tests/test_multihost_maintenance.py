"""Mesh-sharded maintenance plane over a REAL 2-process gloo mesh:
host-death-tolerant streaming daemons and the distributed rescale.

ISSUE acceptance layer (the in-process rehearsal lives in
tests/test_maintenance_plane.py):

- `test_multihost_soak_host_kill_two_process` — two gloo processes
  each run a distributed StreamDaemon (sharded ingest/compaction/
  serving, per-host commit users + consumers) over ONE table and the
  identical deterministic CDC stream; process 1 is killed abruptly
  (`os._exit`) mid-soak.  The survivor's lease detector declares it
  dead, adopts its buckets (backfill exactly-once, serve catch-up
  from the dead consumer's position) and keeps compacting.  The
  parent audits: final table byte-identical to the single-process
  oracle, merged changelog materialization equals the expected state
  (no lost or duplicated deliveries), per-user committed offsets
  strictly increasing, `maintenance_takeovers` > 0 with every bucket
  re-leased to the survivor, compaction progressed AFTER the kill,
  and fsck — ownership-consistency check included — is clean.

- `test_distributed_rescale_two_process_owned_buckets_only` — the
  rescale REWRITE is sharded: each host writes only the new buckets
  it will own under the bumped map (asserted in-worker and
  cross-checked over the mesh), the elected committer publishes ONE
  overwrite, and the result is byte-identical to the oracle.

- `test_multihost_soak_full` (slow) — longer stream, a 503 storm on
  the survivor riding the write-retry ladder, later kill.
"""

import json
import os
import socket
import subprocess
import sys
import time

import pytest

from paimon_tpu.schema import Schema
from paimon_tpu.table import FileStoreTable
from paimon_tpu.types import BigIntType

from tests.multihost_soak import expected_state, materialize

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_NO_CPU_COLLECTIVES = "Multiprocess computations aren't implemented"


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_workers(worker_src, tmp_path, n_procs, args=None,
                 expected_rc=None, timeout=420):
    port = _free_port()
    table_path = str(tmp_path / "t")
    worker_py = tmp_path / "worker.py"
    worker_py.write_text(worker_src)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
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
            f"proc {pid} rc={p.returncode} (want {want}):\n{out[-6000:]}"
    return table_path, outs


_PROLOG = r'''
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax

pid = int(sys.argv[1]); port = sys.argv[2]; table_path = sys.argv[3]
REPO = sys.argv[4]
sys.path.insert(0, REPO); n_procs = int(sys.argv[5])
sys.path.insert(0, os.path.join(REPO, "tests"))

from paimon_tpu.parallel import multihost as MH

# peer death is the EVENT UNDER TEST: widen the coordination
# service's missed-heartbeat budget so the survivor is governed by
# its leases (and the parent's timeout), not aborted by XLA ~100s
# after the victim's os._exit
idx, count = MH.initialize(f"127.0.0.1:{port}", n_procs, pid,
                           max_missing_heartbeats=360)
assert (idx, count) == (pid, n_procs)

from paimon_tpu import Schema
from paimon_tpu.table import FileStoreTable
from paimon_tpu.types import BigIntType

def make_schema(extra):
    return (Schema.builder()
            .column("id", BigIntType(False))
            .column("v", BigIntType())
            .primary_key("id")
            .options(extra)
            .build())

def shared_table(extra):
    if pid == 0:
        FileStoreTable.create(table_path, make_schema(extra))
    MH.barrier("table-created")
    return FileStoreTable.load(table_path)
'''


_SOAK_WORKER = _PROLOG + r'''
import json, time
from multihost_soak import (
    SOAK_TABLE_OPTIONS, gen_events,
)
from paimon_tpu.cdc.source import MemoryCdcSource
from paimon_tpu.metrics import (
    MULTIHOST_MAINTENANCE_TAKEOVERS, MULTIHOST_OWNED_BUCKETS,
    STREAM_COMPACTIONS, global_registry,
)
from paimon_tpu.parallel.maintenance_plane import MaintenancePlane
from paimon_tpu.service.stream_daemon import StreamDaemon

N_TOTAL = int(sys.argv[6])
KILL_AFTER = int(sys.argv[7])        # victim dies past this offset
STORM = int(sys.argv[8])             # survivor 503 storms (slow soak)
TICK_S = 0.025
PER_TICK = 6

t = shared_table(dict(SOAK_TABLE_OPTIONS))
fio = t.file_io
if STORM and pid == 0:
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from failing_fileio import FailingFileIO
    fio = FailingFileIO(t.file_io, f"mh-soak-p{pid}")
    t = FileStoreTable(fio, t.path, t.schema_manager.latest())

plane = MaintenancePlane(t, base_user="stream-daemon")
source = MemoryCdcSource()
daemon = StreamDaemon(t, source, commit_user="stream-daemon",
                      plane=plane).start()

rows_path = table_path + f".rows-p{pid}.jsonl"
rows_f = open(rows_path, "a")

def drain():
    while True:
        rows = daemon.poll_changelog(timeout=0.0)
        if not rows:
            rows_f.flush()
            return
        for r in rows:
            rows_f.write(json.dumps(r) + "\n")

g = global_registry()
emitted = 0
storms_done = 0
compactions_at_kill = None
marker = table_path + ".victim-dead"
while emitted < N_TOTAL:
    source.append(*gen_events(emitted, emitted + PER_TICK))
    emitted += PER_TICK
    drain()
    # sample the compaction counter the moment the victim's death is
    # visible: "compaction progressed AFTER the kill" must count the
    # work done on the post-kill two-thirds of the stream.  Sampling
    # after the emit loop raced — a compactor that caught up exactly
    # at stream end had nothing left to do, and the worker burned its
    # whole 120s progress window on an already-converged table
    if compactions_at_kill is None and os.path.exists(marker):
        compactions_at_kill = g.stream_metrics().counter(
            STREAM_COMPACTIONS).count
    if pid == n_procs - 1 and emitted >= KILL_AFTER:
        # HOST DEATH: no drain, no final checkpoint, no goodbye —
        # everything past the last committed checkpoint is lost and
        # must be re-ingested exactly-once by the survivor
        drain()
        rows_f.flush(); rows_f.close()
        open(marker, "w").close()
        os._exit(42)
    if STORM and pid == 0 and storms_done < STORM and \
            emitted >= (storms_done + 1) * N_TOTAL // (STORM + 2):
        # bounded 503 storm on the survivor: the write-retry ladder +
        # supervised loop restarts must absorb it
        FailingFileIO.reset(f"mh-soak-p{pid}", 0, fail_times=4)
        storms_done += 1
    time.sleep(TICK_S)

# survivor: converge on EVERYTHING (own share + adopted share)
deadline = time.time() + 240
while time.time() < deadline:
    drain()
    st = daemon.status()
    if compactions_at_kill is None and os.path.exists(marker):
        compactions_at_kill = g.stream_metrics().counter(
            STREAM_COMPACTIONS).count
    if st["offset_committed"] >= N_TOTAL - 1 and \
            st["distributed"]["adopted"] == [n_procs - 1]:
        break
    time.sleep(0.05)

st = daemon.status()
assert st["distributed"]["adopted"] == [n_procs - 1], st
assert st["offset_committed"] >= N_TOTAL - 1, st

# compaction must PROGRESS after the kill (the dead host's buckets
# are the survivor's problem now) — wait for at least one more run
deadline = time.time() + 120
while time.time() < deadline:
    if g.stream_metrics().counter(STREAM_COMPACTIONS).count > \
            (compactions_at_kill or 0):
        break
    time.sleep(0.1)
post_kill_compactions = g.stream_metrics().counter(
    STREAM_COMPACTIONS).count - (compactions_at_kill or 0)

daemon.stop(drain=True)
drain()
rows_f.close()

mh = g.multihost_metrics()
summary = {
    "takeovers": mh.counter(MULTIHOST_MAINTENANCE_TAKEOVERS).count,
    "owned_buckets": mh.gauge(MULTIHOST_OWNED_BUCKETS).value,
    "post_kill_compactions": post_kill_compactions,
    "offset_committed": daemon.status()["offset_committed"],
    "ownership_version": plane.ownership.version,
    "dead": sorted(plane.ownership.dead),
}
with open(table_path + ".summary.json", "w") as f:
    json.dump(summary, f)
print(f"proc {pid}: MH-SOAK-OK {json.dumps(summary)}", flush=True)
sys.stdout.flush()
os._exit(0)
'''


def _audit_soak(table_path, outs, n_total, n_procs=2):
    victim = n_procs - 1
    assert "MH-SOAK-OK" in outs[0], outs[0][-6000:]

    expected = expected_state(n_total)
    final = FileStoreTable.load(table_path)

    # byte-identity to the single-process oracle
    oracle_path = table_path + "-oracle"
    schema = (Schema.builder()
              .column("id", BigIntType(False))
              .column("v", BigIntType())
              .primary_key("id")
              .options({"bucket": "4"})
              .build())
    oracle = FileStoreTable.create(oracle_path, schema)
    wb = oracle.new_batch_write_builder()
    with wb.new_write() as w:
        w.write_dicts([{"id": k, "v": v}
                       for k, v in sorted(expected.items())])
        wb.new_commit().commit(w.prepare_commit())
    assert final.to_arrow().sort_by("id").equals(
        oracle.to_arrow().sort_by("id")), \
        "distributed daemon state != single-process oracle"

    # merged changelog materialization: the victim's stream first
    # (all its deliveries predate the takeover), then the survivor's
    streams = []
    for p in (victim, 0):
        rows = []
        with open(f"{table_path}.rows-p{p}.jsonl") as f:
            for line in f:
                rows.append(json.loads(line))
        streams.append(rows)
    assert materialize(streams) == expected, \
        "changelog deliveries lost or duplicated across the takeover"

    # per-user committed offsets strictly increasing; the survivor's
    # chain ends at the final offset
    offsets = {p: [] for p in range(n_procs)}
    for snap in final.snapshot_manager.snapshots():
        for p in range(n_procs):
            if snap.commit_user == f"stream-daemon-p{p}" and \
                    snap.properties and \
                    "stream.source.offset" in snap.properties:
                offsets[p].append(
                    int(snap.properties["stream.source.offset"]))
    for p in range(n_procs):
        assert offsets[p], f"user p{p} never checkpointed"
        assert offsets[p] == sorted(set(offsets[p])), \
            f"p{p} offsets not strictly increasing: {offsets[p]}"
    assert offsets[0][-1] == n_total - 1

    # the takeover is visible: buckets re-leased, compaction resumed
    with open(table_path + ".summary.json") as f:
        summary = json.load(f)
    assert summary["takeovers"] > 0
    assert summary["owned_buckets"] == 4          # every bucket mine
    assert summary["dead"] == [victim]
    assert summary["post_kill_compactions"] > 0, \
        "compaction stalled after the host kill"

    # ownership generation recorded, graph clean (ownership check on)
    from paimon_tpu.parallel.distributed import resume_ownership_map
    resumed = resume_ownership_map(final)
    assert resumed is not None and resumed.dead == frozenset({victim})
    report = final.fsck()
    assert report.ok, [v.to_dict() for v in report.violations]


def test_multihost_soak_host_kill_two_process(tmp_path):
    """ISSUE acceptance: a mid-soak host kill on a real 2-process
    gloo mesh loses no events, stalls no compaction, converges
    byte-identical to the single-process oracle, re-leases the dead
    host's buckets (maintenance_takeovers > 0) and stays
    fsck-clean."""
    n_total = 1080
    table_path, outs = _run_workers(
        _SOAK_WORKER, tmp_path, 2,
        args=[n_total, n_total // 3, 0],
        expected_rc={1: 42}, timeout=420)
    _audit_soak(table_path, outs, n_total)


@pytest.mark.slow
def test_multihost_soak_full(tmp_path):
    """Slow variant: longer stream, two bounded 503 storms on the
    survivor riding the write-retry ladder, a later kill."""
    n_total = 4200
    table_path, outs = _run_workers(
        _SOAK_WORKER, tmp_path, 2,
        args=[n_total, n_total // 2, 2],
        expected_rc={1: 42}, timeout=560)
    _audit_soak(table_path, outs, n_total)


# -- kill-two-then-rejoin chaos soak (ISSUE 17 tentpole) ----------------------

_REJOIN_SOAK_WORKER = _PROLOG + r'''
import json, time
from multihost_soak import SOAK_TABLE_OPTIONS, gen_events
from paimon_tpu.cdc.source import MemoryCdcSource
from paimon_tpu.metrics import (
    FLEET_GENERATIONS, FLEET_REJOINS,
    MULTIHOST_MAINTENANCE_TAKEOVERS, global_registry,
)
from paimon_tpu.parallel.maintenance_plane import MaintenancePlane
from paimon_tpu.service.stream_daemon import StreamDaemon

N_TOTAL = int(sys.argv[6])
KILL = int(sys.argv[7])       # pid 2 dies past this offset (abrupt)
KILL2 = int(sys.argv[8])      # pid 1 dies past this one, AT the CAS
STORM = int(sys.argv[9])      # survivor 503 storms (slow soak)
TICK_S = 0.025
PER_TICK = 6

t = shared_table(dict(SOAK_TABLE_OPTIONS))
if pid == 1 or (pid == 0 and STORM):
    from failing_fileio import FailingFileIO
    fio = FailingFileIO(t.file_io, f"mh-rejoin-p{pid}")
    t = FileStoreTable(fio, t.path, t.schema_manager.latest())

plane = MaintenancePlane(t, base_user="stream-daemon")
source = MemoryCdcSource()
daemon = StreamDaemon(t, source, commit_user="stream-daemon",
                      plane=plane).start()

rows_f = open(table_path + f".rows-p{pid}.jsonl", "a")
def drain():
    while True:
        rows = daemon.poll_changelog(timeout=0.0)
        if not rows:
            rows_f.flush(); return
        for r in rows:
            rows_f.write(json.dumps(r) + "\n")

g = global_registry()
adopted_marker = table_path + ".adopted-all"
emitted = 0
while emitted < N_TOTAL:
    source.append(*gen_events(emitted, emitted + PER_TICK))
    emitted += PER_TICK
    drain()
    if pid == 2 and emitted >= KILL:
        # abrupt host death mid-traffic: no drain, no goodbye
        rows_f.flush(); rows_f.close()
        os._exit(42)
    if pid == 1 and emitted >= KILL2:
        # die AT the snapshot CAS: every store op now fails
        # (InjectedIOError mid-upload), so the in-flight checkpoint
        # tears partway — then the host is gone.  Cascading: pid 2 is
        # already dead, so this victim's takeover floor must come
        # from the generation history, not the current dead set
        FailingFileIO.reset("mh-rejoin-p1", 0, fail_times=10000)
        time.sleep(0.4)
        rows_f.flush(); rows_f.close()
        os._exit(42)
    if pid == 0:
        if STORM and emitted in (KILL, KILL2):
            # 503 storm on the survivor exactly while it is trying
            # to adopt a victim: rides the commit retry ladder
            FailingFileIO.reset("mh-rejoin-p0", 0, fail_times=STORM)
        if not os.path.exists(adopted_marker):
            d = daemon.status()["distributed"]
            if sorted(d["adopted"]) == [1, 2]:
                open(adopted_marker, "w").close()  # parent: rejoins
    time.sleep(TICK_S)

# survivor: finish adopting both victims if the emission loop ended
# first, then publish the marker that lets the parent resurrect them
deadline = time.time() + 240
while not os.path.exists(adopted_marker):
    assert time.time() < deadline, daemon.status()
    drain()
    d = daemon.status()["distributed"]
    if sorted(d["adopted"]) == [1, 2]:
        open(adopted_marker, "w").close()
        break
    time.sleep(0.05)

# carry the fleet through both rejoins to convergence
deadline = time.time() + 240
done = False
while time.time() < deadline:
    drain()
    st = daemon.status()
    if st["offset_committed"] >= N_TOTAL - 1 and \
            not plane.ownership.dead and \
            os.path.exists(table_path + ".rejoined-p1") and \
            os.path.exists(table_path + ".rejoined-p2"):
        done = True
        break
    time.sleep(0.05)
assert done, daemon.status()

# release the rejoiners: they hold their daemons (and leases) alive
# until this marker so the all-alive observation above cannot race
# their teardown — an exited rejoiner's lease expires in ~1.5s and
# the detector would (correctly) declare it dead AGAIN
open(table_path + ".fleet-converged", "w").close()

daemon.stop(drain=True)
drain()
rows_f.close()

fleet = g.fleet_metrics()
summary = {
    "takeovers": g.multihost_metrics().counter(
        MULTIHOST_MAINTENANCE_TAKEOVERS).count,
    "rejoins": fleet.counter(FLEET_REJOINS).count,
    "generations": fleet.gauge(FLEET_GENERATIONS).value,
    "offset_committed": daemon.status()["offset_committed"],
    "ownership_version": plane.ownership.version,
    "dead": sorted(plane.ownership.dead),
}
with open(table_path + ".summary.json", "w") as f:
    json.dump(summary, f)
print(f"proc {pid}: MH-SOAK-OK {json.dumps(summary)}", flush=True)
sys.stdout.flush()
os._exit(0)
'''


# second incarnation of a killed host: NO mesh bring-up — rejoin is a
# store-only protocol, so the resurrected process needs nothing but
# the table path and its old process index
_REJOIN_WORKER = r'''
import os, sys, json, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

pid = int(sys.argv[1]); table_path = sys.argv[3]
REPO = sys.argv[4]; n_procs = int(sys.argv[5])
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))
N_TOTAL = int(sys.argv[6])

from multihost_soak import gen_events
from paimon_tpu.cdc.source import MemoryCdcSource
from paimon_tpu.parallel.maintenance_plane import MaintenancePlane
from paimon_tpu.service.stream_daemon import StreamDaemon
from paimon_tpu.table import FileStoreTable

t = FileStoreTable.load(table_path)
plane = MaintenancePlane(t, base_user="stream-daemon",
                         process_index=pid, process_count=n_procs)
assert plane.rejoining, \
    "restart of a dead-recorded host must enter the rejoining state"
source = MemoryCdcSource()
source.append(*gen_events(0, N_TOTAL))   # full replayable history
daemon = StreamDaemon(t, source, commit_user="stream-daemon",
                      plane=plane).start()

rows_f = open(table_path + f".rows-p{pid}.jsonl", "a")
def drain():
    while True:
        rows = daemon.poll_changelog(timeout=0.0)
        if not rows:
            rows_f.flush(); return
        for r in rows:
            rows_f.write(json.dumps(r) + "\n")

deadline = time.time() + 240
ok = False
while time.time() < deadline:
    drain()
    st = daemon.status()
    if not st["distributed"]["rejoining"] and \
            st["offset_committed"] >= N_TOTAL - 1:
        ok = True
        break
    time.sleep(0.05)
st = daemon.status()
assert ok, st
open(table_path + f".rejoined-p{pid}", "w").close()
summary = {"rejoin_replayed": st["distributed"]["rejoin_replayed"],
           "offset_committed": st["offset_committed"],
           "ownership_version": st["distributed"]["ownership_version"]}
with open(table_path + f".rejoin-summary-p{pid}.json", "w") as f:
    json.dump(summary, f)
# stay ALIVE (daemon heartbeating, lease fresh) until the survivor
# has observed the all-alive fleet — exiting now would expire this
# host's lease mid-observation and the detector would re-declare it
# dead, which the survivor's convergence wait could never recover
# from (a correct re-death, but not the lifecycle under test)
release = time.time() + 240
while not os.path.exists(table_path + ".fleet-converged") and \
        time.time() < release:
    drain()
    time.sleep(0.05)
daemon.stop(drain=True)
drain()
rows_f.close()
print(f"proc {pid}: MH-REJOIN-OK {json.dumps(summary)}", flush=True)
sys.stdout.flush()
os._exit(0)
'''


def _run_rejoin_soak(tmp_path, n_total, kill, kill2, storm=0,
                     timeout=420):
    port = _free_port()
    table_path = str(tmp_path / "t")
    worker_py = tmp_path / "worker.py"
    worker_py.write_text(_REJOIN_SOAK_WORKER)
    rejoin_py = tmp_path / "rejoin.py"
    rejoin_py.write_text(_REJOIN_WORKER)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)

    def spawn(py, pid, extra):
        return subprocess.Popen(
            [sys.executable, str(py), str(pid), str(port), table_path,
             REPO, "3"] + [str(a) for a in extra],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env)

    procs = {p: spawn(worker_py, p, [n_total, kill, kill2, storm])
             for p in range(3)}
    outs = {}
    try:
        for p in (2, 1):            # victims die first, in order
            outs[p], _ = procs[p].communicate(timeout=timeout)
            if _NO_CPU_COLLECTIVES in outs[p]:
                pytest.skip("jaxlib CPU backend lacks Gloo "
                            "cross-process collectives")
            assert procs[p].returncode == 42, \
                f"victim {p} rc={procs[p].returncode}:\n" \
                f"{outs[p][-6000:]}"
        # survivor adopts both; fsck mid-chaos (two hosts down)
        deadline = time.time() + timeout
        while not os.path.exists(table_path + ".adopted-all"):
            assert procs[0].poll() is None, \
                procs[0].communicate()[0][-6000:]
            assert time.time() < deadline, \
                "survivor never adopted both victims"
            time.sleep(0.1)
        mid = FileStoreTable.load(table_path).fsck()
        assert mid.ok, [v.to_dict() for v in mid.violations]
        # resurrect both victims — store-only rejoin, no mesh
        rejoiners = {p: spawn(rejoin_py, p, [n_total])
                     for p in (1, 2)}
        for p in (1, 2):
            out, _ = rejoiners[p].communicate(timeout=timeout)
            outs[f"rejoin{p}"] = out
            assert rejoiners[p].returncode == 0, \
                f"rejoiner {p}:\n{out[-6000:]}"
        outs[0], _ = procs[0].communicate(timeout=timeout)
        assert procs[0].returncode == 0, outs[0][-6000:]
    finally:
        for pr in procs.values():
            if pr.poll() is None:
                pr.kill()
    return table_path, outs


def _audit_rejoin_soak(table_path, outs, n_total):
    assert "MH-SOAK-OK" in outs[0], outs[0][-6000:]
    for p in (1, 2):
        assert "MH-REJOIN-OK" in outs[f"rejoin{p}"], \
            outs[f"rejoin{p}"][-6000:]

    expected = expected_state(n_total)
    final = FileStoreTable.load(table_path)

    # byte-identity to the single-process oracle
    schema = (Schema.builder()
              .column("id", BigIntType(False))
              .column("v", BigIntType())
              .primary_key("id")
              .options({"bucket": "4"})
              .build())
    oracle = FileStoreTable.create(table_path + "-oracle", schema)
    wb = oracle.new_batch_write_builder()
    with wb.new_write() as w:
        w.write_dicts([{"id": k, "v": v}
                       for k, v in sorted(expected.items())])
        wb.new_commit().commit(w.prepare_commit())
    assert final.to_arrow().sort_by("id").equals(
        oracle.to_arrow().sort_by("id")), \
        "post-rejoin fleet state != single-process oracle"

    # per-user committed offsets strictly increasing ACROSS both
    # incarnations of each victim, and every host drained to the end
    offsets = {p: [] for p in range(3)}
    for snap in final.snapshot_manager.snapshots():
        for p in range(3):
            if snap.commit_user == f"stream-daemon-p{p}" and \
                    snap.properties and \
                    "stream.source.offset" in snap.properties:
                offsets[p].append(
                    int(snap.properties["stream.source.offset"]))
    for p in range(3):
        assert offsets[p], f"user p{p} never checkpointed"
        assert offsets[p] == sorted(set(offsets[p])), \
            f"p{p} offsets not strictly increasing: {offsets[p]}"
        assert offsets[p][-1] == n_total - 1, \
            f"p{p} did not converge: {offsets[p][-1]}"

    # exactly-once cascading takeover + both rejoins, on /metrics
    with open(table_path + ".summary.json") as f:
        summary = json.load(f)
    assert summary["takeovers"] >= 2, summary
    assert summary["rejoins"] >= 2, summary
    assert summary["dead"] == [], summary
    assert summary["generations"] == summary["ownership_version"]
    for p in (1, 2):
        with open(f"{table_path}.rejoin-summary-p{p}.json") as f:
            rs = json.load(f)
        assert rs["rejoin_replayed"] > 0, \
            f"rejoiner {p} replayed no gap rows: {rs}"

    # the persisted generation history is exact: bring-up, both
    # deaths, both readmissions — versions strictly increasing,
    # the double-death generation present, nobody dead at the tip
    from paimon_tpu.parallel.distributed import (
        resume_generation_history,
    )
    hist = resume_generation_history(final)
    assert hist is not None
    versions = [m.version for m in hist.entries]
    assert versions == sorted(set(versions)), versions
    assert any(m.dead == frozenset({1, 2}) for m in hist.entries), \
        [(m.version, sorted(m.dead)) for m in hist.entries]
    assert hist.current().dead == frozenset()

    report = final.fsck()
    assert report.ok, [v.to_dict() for v in report.violations]


def test_multihost_soak_kill_two_then_rejoin(tmp_path):
    """ISSUE 17 acceptance (smoke scale): real 3-process gloo mesh,
    two hosts killed mid-traffic — one abruptly, one at the snapshot
    CAS under an injected IO storm (torn uploads) — cascading
    exactly-once takeover computed from the persisted generation
    history, then BOTH victims rejoin with no operator: readmitted by
    the elected survivor, offset gaps replayed, final table
    byte-identical to the single-process oracle, per-user offsets
    strictly increasing, fsck clean mid-chaos and after,
    `rejoins >= 2` and `maintenance_takeovers >= 2`."""
    n_total = 1080
    table_path, outs = _run_rejoin_soak(
        tmp_path, n_total, kill=360, kill2=480)
    _audit_rejoin_soak(table_path, outs, n_total)


@pytest.mark.slow
def test_multihost_soak_kill_two_then_rejoin_storm(tmp_path):
    """Storm variant: longer stream and a 503 storm armed on the
    SURVIVOR at both kill offsets, so each cascading adoption commit
    has to climb the write-retry ladder while the dying host's torn
    uploads are still on disk."""
    n_total = 2400
    table_path, outs = _run_rejoin_soak(
        tmp_path, n_total, kill=798, kill2=948, storm=4, timeout=560)
    _audit_rejoin_soak(table_path, outs, n_total)


_RESCALE_WORKER = _PROLOG + r'''
import json

t = shared_table({"bucket": "4",
                  "multihost.write.routing": "spmd",
                  "multihost.commit.arbitration": "coordinator"})
plane = t.new_distributed_write()

rows = [{"id": i, "v": i} for i in range(600)]
plane.write_dicts(rows)            # identical global batch (spmd)
plane.commit(commit_identifier=1)

plane.rescale_buckets(8)
assert plane.table.options.bucket == 8
assert plane.ownership.version == 2

# THE acceptance: this host wrote only the new buckets it will OWN
mine = plane.last_rescale_written_buckets
owned = {b for b in range(8)
         if plane.ownership.owner_of((), b) == pid}
assert mine, "host rewrote nothing — the rescale was not sharded"
assert set(mine) <= owned, (mine, sorted(owned))

# cross-check over the mesh: shares are disjoint and cover every
# routed bucket
payloads = MH.allgather_bytes(json.dumps(mine).encode())
shares = [json.loads(p) for p in payloads]
flat = [b for share in shares for b in share]
assert len(flat) == len(set(flat)), f"overlapping shares: {shares}"
assert sorted(flat) == list(range(8)), shares

plane.write_dicts([{"id": 1000 + i, "v": 1} for i in range(100)])
plane.commit(commit_identifier=2)
plane.close()
print(f"proc {pid}: MH-RESCALE-OK mine={sorted(mine)}", flush=True)
'''


def test_distributed_rescale_two_process_owned_buckets_only(tmp_path):
    """Each host of a real 2-process mesh rewrites only the buckets
    it will own under the bumped ownership version; the elected
    committer publishes ONE overwrite; the result is byte-identical
    to the oracle."""
    table_path, outs = _run_workers(_RESCALE_WORKER, tmp_path, 2)
    for pid, out in enumerate(outs):
        assert f"proc {pid}: MH-RESCALE-OK" in out, out[-4000:]

    t = FileStoreTable.load(table_path)
    assert t.options.bucket == 8
    schema = (Schema.builder()
              .column("id", BigIntType(False))
              .column("v", BigIntType())
              .primary_key("id")
              .options({"bucket": "8"})
              .build())
    oracle = FileStoreTable.create(str(tmp_path / "oracle"), schema)
    wb = oracle.new_batch_write_builder()
    with wb.new_write() as w:
        w.write_dicts([{"id": i, "v": i} for i in range(600)]
                      + [{"id": 1000 + i, "v": 1} for i in range(100)])
        wb.new_commit().commit(w.prepare_commit())
    assert t.to_arrow().sort_by("id").equals(
        oracle.to_arrow().sort_by("id"))
    report = t.fsck()
    assert report.ok, [v.to_dict() for v in report.violations]
