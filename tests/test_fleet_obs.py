"""Fleet observability end to end (ISSUE 20 acceptance layer).

Cross-process trace propagation: a REAL 2-process gloo maintenance
soak and a router + 2-subprocess-replica serving rig each spool their
spans to a shared `trace.export.dir`; the parent stitches ONE Perfetto
file with obs/merge.py and PARSES it — per-process tracks, spans, and
flow arrows across every process boundary (store-carried
`trace.context` links for the soak, X-Parent-Span serving hops for the
rig).

Black-box flight recorder: an injected stream-daemon loop crash dumps
the ring (triggering event + the operational events recorded BEFORE
it), and `paimon table debug-bundle` round-trips the same ring through
the CLI.  A SIGTERM'd daemon subprocess leaves both its trace spool
and a flight dump behind (the signal handler flushes BEFORE draining).

SLO plane: an injected 504 storm flips the multi-window burn-rate
alert — visible at the replica's /slo, the router's fleet aggregate,
and the `slo` Prometheus group — and a healthy loadgen run recovers
it.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from paimon_tpu.obs import flight
from paimon_tpu.obs.merge import export_merged, read_spools
from paimon_tpu.obs.trace import (
    disable_tracing, enable_tracing, reset_spool, set_export_dir,
    set_replica_id, spool_flush, take_spans,
)
from paimon_tpu.schema import Schema
from paimon_tpu.service import KvQueryClient, KvQueryServer, ReplicaRouter
from paimon_tpu.table import FileStoreTable
from paimon_tpu.types import BigIntType

from tests.test_multihost_maintenance import _PROLOG, _run_workers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _obs_reset():
    yield
    disable_tracing()
    set_export_dir(None)
    set_replica_id(None)
    take_spans(clear=True)
    reset_spool()
    rec = flight.recorder()
    rec.clear()
    rec.dump_dir = None
    rec.enabled = True


def _child_env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    return env


# -- merged-trace parsing (the acceptance bar: a test that PARSES the
# export, not one that trusts the stats dict) --------------------------------

def _load_merged(path):
    """(procs, spans, flows): procs maps chrome pid -> process label;
    spans are the "X" events; flows are resolved (s_event, f_event)
    pairs joined on the flow id."""
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    spans = [e for e in events if e.get("ph") == "X"]
    starts, ends = {}, {}
    for e in events:
        if e.get("cat") != "flow":
            continue
        (starts if e["ph"] == "s" else ends)[e["id"]] = e
    flows = [(starts[i], ends[i]) for i in sorted(starts) if i in ends]
    return procs, spans, flows


def _os_pid_of(procs):
    """chrome pid -> OS pid parsed from the 'host/pid [replica]'
    process_name label."""
    return {p: int(name.split("/", 1)[1].split(" ")[0])
            for p, name in procs.items()}


# -- leg 1a: gloo soak, store-carried context --------------------------------

_OBS_SOAK_WORKER = _PROLOG + r'''
import time
from multihost_soak import SOAK_TABLE_OPTIONS, gen_events
from paimon_tpu.cdc.source import MemoryCdcSource
from paimon_tpu.obs.trace import spool_flush
from paimon_tpu.parallel.maintenance_plane import MaintenancePlane
from paimon_tpu.service.stream_daemon import StreamDaemon

N_TOTAL = int(sys.argv[6])
KILL_AFTER = int(sys.argv[7])        # victim dies past this offset
SPOOL = sys.argv[8]
TICK_S = 0.02
PER_TICK = 6

opts = dict(SOAK_TABLE_OPTIONS)
opts["trace.enabled"] = "true"
opts["trace.export.dir"] = SPOOL
t = shared_table(opts)

plane = MaintenancePlane(t, base_user="stream-daemon")
source = MemoryCdcSource()
daemon = StreamDaemon(t, source, commit_user="stream-daemon",
                      plane=plane).start()

def drain():
    while daemon.poll_changelog(timeout=0.0):
        pass

emitted = 0
while emitted < N_TOTAL:
    source.append(*gen_events(emitted, emitted + PER_TICK))
    emitted += PER_TICK
    drain()
    if pid == n_procs - 1 and emitted >= KILL_AFTER:
        # HOST DEATH — but the black box made it to disk first: the
        # spool holds every checkpoint span recorded so far, so the
        # parent can stitch the dead host's track into the fleet trace
        spool_flush()
        os._exit(42)
    time.sleep(TICK_S)

# survivor: converge on everything (own share + adopted share)
deadline = time.time() + 240
while time.time() < deadline:
    drain()
    st = daemon.status()
    if st["offset_committed"] >= N_TOTAL - 1 and \
            st["distributed"]["adopted"] == [n_procs - 1]:
        break
    time.sleep(0.05)

st = daemon.status()
assert st["distributed"]["adopted"] == [n_procs - 1], st
assert st["offset_committed"] >= N_TOTAL - 1, st
daemon.stop(drain=True)
drain()
spool_flush()
print(f"proc {pid}: OBS-SOAK-OK", flush=True)
os._exit(0)
'''


def test_fleet_trace_merge_gloo_maintenance_soak(tmp_path):
    """Two gloo daemon processes + the auditing parent = three
    processes in ONE merged Perfetto file, tied together by
    store-carried trace.context flow arrows across BOTH worker
    boundaries, with the survivor's takeover span on its track."""
    spool = tmp_path / "spool"
    spool.mkdir()
    n_total, kill_after = 300, 120
    table_path, outs = _run_workers(
        _OBS_SOAK_WORKER, tmp_path, 2,
        args=[n_total, kill_after, str(spool)],
        expected_rc={1: 42}, timeout=300)
    assert "OBS-SOAK-OK" in outs[0], outs[0][-6000:]

    # every checkpoint/takeover commit carried its committer's context
    final = FileStoreTable.load(table_path)
    by_tag = {}
    for snap in final.snapshot_manager.snapshots():
        ctx = (snap.properties or {}).get("trace.context")
        if ctx:
            by_tag.setdefault(ctx.rsplit(":", 1)[0], []).append(snap)
    assert len(by_tag) >= 2, \
        f"want traced snapshots from both workers, got {list(by_tag)}"

    # the parent consumes one EARLY snapshot per worker (early = its
    # committer span was certainly spooled before any kill) — plan()
    # emits the plan.link boundary span that the merge resolves into a
    # worker-track -> parent-track flow arrow
    enable_tracing()
    set_export_dir(str(spool))
    scan = final.new_read_builder().new_scan()
    for _tag, snaps in sorted(by_tag.items()):
        scan.plan(snapshot_id=min(s.id for s in snaps))
    spool_flush()
    disable_tracing()

    out = str(tmp_path / "fleet-trace.json")
    stats = export_merged(str(spool), out)
    assert stats["processes"] == 3, stats
    assert stats["flows"] >= 2, stats
    assert stats["out"] == out

    procs, spans, flows = _load_merged(out)
    assert len(procs) == 3
    me = [p for p, o in _os_pid_of(procs).items()
          if o == os.getpid()]
    assert len(me) == 1, procs
    me = me[0]
    worker_pids = set(procs) - {me}
    # every process contributed spans to its own track
    assert worker_pids <= {s["pid"] for s in spans}
    # both worker boundaries have a RESOLVED store-carried arrow into
    # the parent's plan.link span
    link_srcs = {s_ev["pid"] for s_ev, f_ev in flows
                 if f_ev["pid"] == me and s_ev["name"] == "link"}
    assert worker_pids <= link_srcs, (link_srcs, worker_pids)
    by_pid_names = {}
    for s in spans:
        by_pid_names.setdefault(s["pid"], set()).add(s["name"])
    # the arrows land on checkpoint commits, and the survivor's
    # takeover of the dead host is on the merged timeline
    assert any("stream.checkpoint" in by_pid_names[p]
               for p in worker_pids), by_pid_names
    assert any("stream.takeover" in by_pid_names.get(p, set())
               for p in worker_pids), by_pid_names
    assert any(s["name"] == "plan.link" and s["pid"] == me
               for s in spans)


# -- leg 1b: serving rig, header-carried context -----------------------------

_REPLICA_CHILD = r'''
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
rid = int(sys.argv[1]); table_path = sys.argv[2]; spool = sys.argv[3]
sys.path.insert(0, sys.argv[4])
import pyarrow as pa
pa.set_cpu_count(2); pa.set_io_thread_count(2)
from paimon_tpu.table import FileStoreTable
from paimon_tpu.service import KvQueryServer

table = FileStoreTable.load(table_path, dynamic_options={
    "trace.enabled": "true",
    "trace.export.dir": spool,
    "service.lookup.refresh-interval": "1000"})
server = KvQueryServer(table, replica_id=rid)
server.server.start()           # no registry write: parent routes
print("ADDR %d %s" % (rid, server.address), flush=True)
sys.stdin.read()                # parent closes the pipe to stop us
server.server.stop()
from paimon_tpu.obs.trace import spool_flush
spool_flush()
os._exit(0)
'''


def _serving_table(path, rows=64):
    schema = (Schema.builder()
              .column("id", BigIntType(False))
              .column("v", BigIntType())
              .primary_key("id")
              .options({"bucket": "2", "write-only": "true"})
              .build())
    t = FileStoreTable.create(path, schema)
    wb = t.new_batch_write_builder()
    with wb.new_write() as w:
        w.write_dicts([{"id": i, "v": i} for i in range(rows)])
        wb.new_commit().commit(w.prepare_commit())
    return t


def test_fleet_trace_merge_serving_rig(tmp_path):
    """Client -> router -> 2 replica PROCESSES: the X-Parent-Span hop
    headers become remote_parent flow arrows from the router's track
    into EACH replica's serve.request span in the merged trace."""
    t = _serving_table(str(tmp_path / "t"))
    spool = tmp_path / "spool"
    spool.mkdir()
    child = tmp_path / "replica_child.py"
    child.write_text(_REPLICA_CHILD)
    procs, addrs = [], {}
    try:
        for rid in range(2):
            procs.append(subprocess.Popen(
                [sys.executable, str(child), str(rid), t.path,
                 str(spool), REPO],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                text=True, env=_child_env()))
        for p in procs:
            line = p.stdout.readline().strip()
            assert line.startswith("ADDR "), line
            _tag, rid, addr = line.split(" ", 2)
            addrs[int(rid)] = addr

        enable_tracing()
        set_export_dir(str(spool))
        router = ReplicaRouter(addresses=addrs, table_name="t")
        router.server.start()
        try:
            # distinct tenants spread the consistent-hash ring over
            # both replicas; every request runs client.request ->
            # router serve.request -> replica serve.request
            for i in range(24):
                with KvQueryClient(address=router.address,
                                   tenant=f"tn-{i}",
                                   follow_topology=False) as c:
                    assert c.lookup_row({"id": i % 16})["v"] == i % 16
        finally:
            router.server.stop()
            for pool in router._remote.values():
                pool.close()
    finally:
        for p in procs:
            if p.stdin:
                p.stdin.close()
        for p in procs:
            p.wait(timeout=60)
    spool_flush()
    disable_tracing()

    out = str(tmp_path / "serve-trace.json")
    stats = export_merged(str(spool), out)
    assert stats["processes"] == 3, stats

    procs_map, spans, flows = _load_merged(out)
    pid_map = _os_pid_of(procs_map)
    me = [p for p, o in pid_map.items() if o == os.getpid()]
    assert len(me) == 1, procs_map
    me = me[0]
    replica_pids = set(procs_map) - {me}
    assert {pid_map[p] for p in replica_pids} == \
        {p.pid for p in procs}
    # replica tracks carry the replica id in their labels
    assert {procs_map[p].split("[")[-1].rstrip("]")
            for p in replica_pids} == {"r0", "r1"}
    # parent track: the originating client spans
    assert any(s["name"] == "client.request" and s["pid"] == me
               for s in spans)
    # EACH replica process serves with an adopted remote parent, and
    # the hop resolves to an arrow leaving the parent's track
    for rp in sorted(replica_pids):
        served = [s for s in spans
                  if s["pid"] == rp and s["name"] == "serve.request"]
        assert served, (rp, procs_map)
        assert all(s["args"].get("remote_parent") for s in served)
        arrows = [(s_ev, f_ev) for s_ev, f_ev in flows
                  if f_ev["pid"] == rp
                  and s_ev["name"] == "remote_parent"]
        assert arrows, f"no flow arrow into replica track {rp}"
        assert all(s_ev["pid"] == me for s_ev, _f in arrows)


# -- leg 2: flight recorder + debug bundle -----------------------------------

def _wait(cond, timeout=30.0, step=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(step)
    return False


def test_daemon_crash_dumps_flight_ring_and_debug_bundle(
        tmp_path, capsys):
    """An ingest loop that dies past its restart budget dumps the
    flight ring: the terminal loop.crash WITH the operational events
    recorded before it (here: a retried transient fault), and
    `paimon table debug-bundle` round-trips the same ring."""
    from paimon_tpu.cdc.source import MemoryCdcSource
    from paimon_tpu.parallel.fault import BucketRetryPolicy
    from paimon_tpu.service.stream_daemon import StreamDaemon

    dumps = tmp_path / "flight"

    # organic preceding context: a transient fault rides the retry
    # ladder, which records EV_RETRY into the always-on ring
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            raise ConnectionError("injected blip")
        return "ok"

    assert BucketRetryPolicy(max_attempts=3).retry_call(flaky) == "ok"

    class BoomSource(MemoryCdcSource):
        def poll(self, after_offset, max_events):
            raise RuntimeError("boom: injected source failure")

    schema = (Schema.builder()
              .column("id", BigIntType(False))
              .column("v", BigIntType())
              .primary_key("id")
              .options({"bucket": "2",
                        "stream.ingest.poll-interval": "10",
                        "stream.restart.backoff": "10",
                        "stream.restart.backoff.cap": "40",
                        "stream.restart.max-restarts": "1",
                        "obs.flight.dump.dir": str(dumps)})
              .build())
    table = FileStoreTable.create(str(tmp_path / "t"), schema)
    daemon = StreamDaemon(table, BoomSource(), compact=False,
                          serve=False).start()
    try:
        assert _wait(
            lambda: daemon.status()["loops"]["ingest"]["failed"])
    finally:
        daemon.kill()

    dump_files = sorted(dumps.glob("flight-*.json"))
    assert dump_files, "terminal loop failure left no flight dump"
    docs = [json.loads(p.read_text()) for p in dump_files]
    doc = next(d for d in docs
               if any(e["kind"] == "loop.crash" for e in d["events"]))
    assert doc["pid"] == os.getpid()
    kinds = [e["kind"] for e in doc["events"]]
    crash = [e for e in doc["events"] if e["kind"] == "loop.crash"][-1]
    assert crash["loop"] == "ingest"
    assert crash["why"] == "max_restarts"
    assert "boom" in str(crash["error"])
    # the ring kept what came BEFORE the trigger
    assert "retry" in kinds
    assert kinds.index("retry") < kinds.index("loop.crash")

    # CLI round trip: the bundle carries the same ring + table context
    from paimon_tpu.cli import main
    wh = str(tmp_path / "wh")
    assert main(["-w", wh, "db", "create", "d1"]) == 0
    assert main(["-w", wh, "table", "create", "d1.t",
                 "--column", "id:BIGINT NOT NULL",
                 "--column", "v:DOUBLE",
                 "--primary-key", "id",
                 "--option", "bucket=2"]) == 0
    assert main(["-w", wh, "sql",
                 "INSERT INTO d1.t VALUES (1, 1.5), (2, 2.5)"]) == 0
    out_path = str(tmp_path / "bundle.json")
    capsys.readouterr()
    assert main(["-w", wh, "table", "debug-bundle", "d1.t",
                 "--out", out_path]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["out"] == out_path
    assert summary["flight_events"] >= 2
    with open(out_path) as f:
        bundle = json.load(f)
    assert bundle["table"]
    assert str(os.getpid()) in bundle["process"]
    bundle_kinds = [e["kind"] for e in bundle["flight"]["events"]]
    assert "loop.crash" in bundle_kinds and "retry" in bundle_kinds
    assert bundle["options"]["bucket"]["value"] == "2"
    assert any(r["group"] == "commit" for r in bundle["metrics"])


# -- leg 2b (satellite): SIGTERM'd daemon leaves the black box ---------------

_SIGTERM_DAEMON_CHILD = r'''
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
table_path = sys.argv[1]; spool = sys.argv[2]; dumps = sys.argv[3]
sys.path.insert(0, sys.argv[4])
from paimon_tpu.cdc.source import MemoryCdcSource
from paimon_tpu.schema import Schema
from paimon_tpu.service.stream_daemon import StreamDaemon
from paimon_tpu.table import FileStoreTable
from paimon_tpu.types import BigIntType

schema = (Schema.builder()
          .column("id", BigIntType(False))
          .column("v", BigIntType())
          .primary_key("id")
          .options({"bucket": "2",
                    "stream.checkpoint.interval": "50",
                    "stream.ingest.poll-interval": "10",
                    "trace.enabled": "true",
                    "trace.export.dir": spool,
                    "obs.flight.dump.dir": dumps})
          .build())
table = FileStoreTable.create(table_path, schema)
src = MemoryCdcSource([{"op": "c", "after": {"id": i, "v": i}}
                       for i in range(40)])
daemon = StreamDaemon(table, src, compact=False, serve=False)
daemon.install_signal_handlers()
daemon.start()
while daemon.status()["offset_committed"] < 39:
    time.sleep(0.02)
print("READY", flush=True)
status = daemon.run_forever()
assert not any(l["failed"] for l in status["loops"].values()), status
print("STOPPED", flush=True)
'''


def test_sigtermed_daemon_leaves_spool_and_flight_dump(tmp_path):
    """Satellite regression: the daemon's signal handler flushes the
    trace spool AND dumps the flight ring BEFORE starting the drain —
    a killed daemon still contributes its track to the fleet trace."""
    spool = tmp_path / "spool"
    dumps = tmp_path / "flight"
    spool.mkdir()
    child = tmp_path / "daemon_child.py"
    child.write_text(_SIGTERM_DAEMON_CHILD)
    p = subprocess.Popen(
        [sys.executable, str(child), str(tmp_path / "t"), str(spool),
         str(dumps), REPO],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=_child_env())
    try:
        line = p.stdout.readline().strip()
        assert line == "READY", line
        os.kill(p.pid, signal.SIGTERM)
        out, _ = p.communicate(timeout=120)
    except Exception:
        p.kill()
        raise
    assert p.returncode == 0, out[-4000:]
    assert "STOPPED" in out, out[-4000:]

    spools = read_spools(str(spool))
    assert len(spools) == 1
    assert spools[0]["meta"]["pid"] == p.pid
    names = {s["name"] for s in spools[0]["spans"]}
    assert "stream.checkpoint" in names, names

    dump_files = sorted(dumps.glob("flight-*.json"))
    assert dump_files, "signal handler left no flight dump"
    docs = [json.loads(f.read_text()) for f in dump_files]
    doc = next(d for d in docs
               if any(e["kind"] == "sigterm" for e in d["events"]))
    assert doc["pid"] == p.pid
    ev = next(e for e in doc["events"] if e["kind"] == "sigterm")
    assert ev["signum"] == signal.SIGTERM


# -- leg 3: SLO burn-rate plane ----------------------------------------------

def _prom_value(text, name, table):
    """The sample of `name` for `table` in a Prometheus exposition: the
    registry is the process's, and other tests' servers leave their own
    `slo` series in it."""
    vals = [float(line.rsplit(" ", 1)[1])
            for line in text.splitlines()
            if line.startswith(name + '{table="' + table + '"}')]
    assert len(vals) == 1, f"{name} of {table!r}: {vals}"
    return vals[0]


def test_slo_storm_flips_alert_and_recovers(tmp_path):
    """An injected 504 storm burns the availability budget above the
    threshold in BOTH windows -> alert on, visible at /slo, the router
    aggregate, and the `slo` Prometheus group; after the bad events
    age out of the fast window, a healthy loadgen run shows it clear.
    The evaluator's windows run on a clock the test marches: on the
    wall clock a loaded machine spread the storm over more than the
    1-s window (the failures at PRs 30, 34 and 35)."""
    from benchmarks.loadgen import run_loadgen
    from paimon_tpu.obs.export import render_prometheus

    t = _serving_table(str(tmp_path / "slo_storm"), rows=64)
    t = FileStoreTable.load(t.path, dynamic_options={
        "service.slo.fast-window-s": "1.0",
        "service.slo.slow-window-s": "5.0",
        "service.slo.burn-threshold": "2.0"})
    now = [100.0]
    server = KvQueryServer(t, slo_clock=lambda: now[0]).start()
    router = ReplicaRouter(servers=[server])
    router.server.start()
    try:
        with KvQueryClient(address=server.address,
                           follow_topology=False) as c:
            for i in range(5):
                assert c.lookup_row({"id": i})["v"] == i
            baseline = c.slo()
        assert baseline["enabled"] and not baseline["alert"]

        # storm: a zero-budget deadline turns every request into a
        # deterministic 504 — each one feeds the evaluator as a bad
        # availability event
        with KvQueryClient(address=server.address, timeout_ms=0,
                           follow_topology=False) as bad:
            for i in range(40):
                try:
                    bad.lookup_row({"id": i % 16})
                except Exception:
                    pass
        with KvQueryClient(address=server.address,
                           follow_topology=False) as c:
            stormed = c.slo()
        av = stormed["objectives"]["availability"]
        assert stormed["alert"] is True
        assert av["alert"] is True
        assert av["burn_fast"] >= stormed["burn_threshold"]
        assert av["burn_slow"] >= stormed["burn_threshold"]
        assert stormed["bad_events"] >= 40

        # the same state through the router's fleet rollup ...
        with KvQueryClient(address=router.address,
                           follow_topology=False) as rc:
            agg = rc.slo()
        assert agg["alert"] is True
        assert "0" in agg["per_replica"]
        assert agg["objectives"]["availability"]["burn_fast"] >= 2.0
        assert agg["unreachable"] == []

        # ... and through the `slo` Prometheus group (the /slo render
        # above refreshed the gauges)
        text = render_prometheus()
        assert _prom_value(text, "paimon_slo_alert", t.name) == 1.0
        assert _prom_value(
            text, "paimon_slo_availability_burn_fast", t.name) >= 2.0

        # recovery: let the storm age past the fast window, then
        # serve a healthy loadgen run — the fast leg cools and the
        # multi-window AND clears the alert
        now[0] += 1.1
        res = run_loadgen(server.address, rows=64, seconds=1.0,
                          procs=1, threads=4)
        assert res["qps"] > 0
        with KvQueryClient(address=server.address,
                           follow_topology=False) as c:
            healed = c.slo()
        assert healed["alert"] is False
        assert healed["objectives"]["availability"]["burn_fast"] < 2.0
        assert healed["good_events"] > stormed["good_events"]
        text = render_prometheus()
        assert _prom_value(text, "paimon_slo_alert", t.name) == 0.0
    finally:
        router.server.stop()
        server.stop()
