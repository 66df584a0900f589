"""Native serving hot path (PR 18).

* BUILD SMOKE: `native/*.c` compiles fresh in a temp dir and the
  resulting `.so` exports EVERY symbol python binds; the library the
  process loads is named after a hash of those sources, so a binary
  built from other sources can never be picked up.
* PROBE PARITY: the batched C probe (`sst_probe_batch`) against the
  python bloom+searchsorted oracle — identical hits and rows across
  tombstones, empty SSTs, equal-key runs spanning blocks, partitioned
  batches, and misses.
* FALLBACK: with no native library the probe degrades per-call to the
  python path, counted by `lookup.native_fallbacks`, answers
  unchanged.
* CONCURRENT SERVING: /lookup batches through the native probe under
  live commits and full compaction — no torn batches, SSTs for
  compacted-away files dropped and rebuilt once.
* WARM BOOT: persisted serving state restores with reader_builds == 0.
* REMOTE REPLICAS: POST /register joins the ring, the health loop
  suspends an unreachable replica after two failures and re-admits on
  the first success, /deregister leaves cleanly.
"""

import ctypes
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pyarrow as pa
import pytest

from paimon_tpu import native
from paimon_tpu.lookup.sst import (
    BlockCache, SstReader, SstWriter, force_python_probe, pack_lanes,
)
from paimon_tpu.metrics import (
    LOOKUP_NATIVE_FALLBACKS, LOOKUP_NATIVE_PROBES, LOOKUP_READER_BUILDS,
    global_registry,
)
from paimon_tpu.schema import Schema
from paimon_tpu.table import FileStoreTable
from paimon_tpu.types import BigIntType, IntType, VarCharType

_HAS_NATIVE = native.load() is not None

needs_probe = pytest.mark.skipif(
    not _HAS_NATIVE, reason="native sst_probe_batch unavailable")


def _counter(name):
    return global_registry().lookup_metrics().counter(name)


def _pk_table(path, buckets=2, extra_opts=None, partition=False):
    opts = {"bucket": str(buckets), "write-only": "true"}
    opts.update(extra_opts or {})
    b = (Schema.builder()
         .column("id", BigIntType(False))
         .column("name", VarCharType.string_type()))
    if partition:
        b = b.column("p", IntType(False)).partition_keys("p") \
             .primary_key("p", "id")
    else:
        b = b.primary_key("id")
    return FileStoreTable.create(path, b.options(opts).build())


def _commit(table, rows, kinds=None):
    wb = table.new_batch_write_builder()
    with wb.new_write() as w:
        w.write_dicts(rows, row_kinds=kinds)
        wb.new_commit().commit(w.prepare_commit())


# -- build smoke -------------------------------------------------------------


@pytest.mark.skipif(not _HAS_NATIVE, reason="no C compiler available")
class TestNativeBuildSmoke:
    def test_fresh_build_exports_every_bound_symbol(self, tmp_path):
        """Compile native/*.c from scratch; the .so must export every
        symbol the python side binds — the build-level guard that a
        new symbol actually made it into the artifact."""
        so = native.build_fresh(str(tmp_path))
        lib = ctypes.CDLL(so)
        for sym in native.REQUIRED_SYMBOLS:
            assert hasattr(lib, sym), f"fresh .so missing {sym}"

    def test_loaded_lib_is_named_after_its_sources(self):
        """The library the process serves with is the one whose name
        carries the hash of the tracked sources + flags."""
        native.load()
        assert os.path.basename(native.loaded_path()) == \
            native.lib_name()

    def test_name_follows_the_sources_not_file_times(
            self, tmp_path, monkeypatch):
        """A binary built from other sources is never loaded, whatever
        its mtime says (a tree copy resets file times): edit a source
        and the expected name moves off the planted file; a build of
        the edited sources lands under the new name."""
        srcs = []
        for src in native._SRCS:
            dst = tmp_path / os.path.basename(src)
            dst.write_bytes(open(src, "rb").read())
            srcs.append(str(dst))
        monkeypatch.setattr(native, "_DIR", str(tmp_path))
        monkeypatch.setattr(native, "_SRCS", tuple(srcs))
        assert native.lib_name() == os.path.basename(
            native.loaded_path())            # same bytes, same name
        stale = tmp_path / native.lib_name()
        stale.write_bytes(b"not a library")
        with open(srcs[0], "a") as f:
            f.write("\n/* edited */\n")
        future = time.time() + 3600
        os.utime(stale, (future, future))    # newer than every source
        assert native.lib_name() != stale.name
        built = native._build(native._compiler())
        assert os.path.basename(built) == native.lib_name()
        assert built != str(stale)
        ctypes.CDLL(built)                   # a real library


# -- probe parity ------------------------------------------------------------


def _probe_both(reader, queries):
    """(native hits/rows, python hits/rows) for one query batch, as
    comparable (sorted hit list, sorted row tuples)."""
    def norm(res):
        hit, rows = res
        if rows is None:
            return sorted(hit.tolist()), []
        keep = [c for c in rows.column_names]
        body = list(zip(hit.tolist(),
                        *[rows.column(c).to_pylist() for c in keep]))
        return sorted(hit.tolist()), sorted(body)
    n = norm(reader.probe(queries))
    with force_python_probe():
        p = norm(reader.probe(queries))
    return n, p


@needs_probe
class TestProbeParity:
    def _sorted(self, n, num_lanes=2, seed=0, dupes=None):
        rng = np.random.default_rng(seed)
        hi = max((n // dupes) if dupes else 1 << 32, 1)
        lanes = rng.integers(0, hi, (n, num_lanes),
                             dtype=np.uint64).astype(np.uint32)
        order = np.argsort(pack_lanes(lanes), kind="stable")
        t = pa.table({"v": pa.array(np.arange(n), pa.int64())})
        return lanes[order], t.take(order)

    @pytest.mark.parametrize("block_rows", [64, 512])
    def test_random_hits_and_misses(self, tmp_path, block_rows):
        lanes, t = self._sorted(5_000, seed=1)
        path = str(tmp_path / "f.sst")
        SstWriter(block_rows=block_rows).write(path, lanes, t)
        r = SstReader(path, BlockCache())
        rng = np.random.default_rng(2)
        queries = np.concatenate([
            lanes[rng.integers(0, len(lanes), 300)],
            rng.integers(0, 1 << 32, (300, 2),
                         dtype=np.uint64).astype(np.uint32)])
        n, p = _probe_both(r, queries)
        assert n == p

    def test_equal_key_runs_spanning_blocks(self, tmp_path):
        """A run of equal packed keys crossing block boundaries (lanes
        prefix-truncate long string keys) must yield EVERY row of the
        run on both paths."""
        lanes, t = self._sorted(4_000, seed=3, dupes=40)  # ~100 each
        path = str(tmp_path / "f.sst")
        SstWriter(block_rows=64).write(path, lanes, t)
        r = SstReader(path, BlockCache())
        queries = lanes[::97]
        n, p = _probe_both(r, queries)
        assert n == p
        assert len(n[1]) > len(queries)      # runs actually probed

    def test_empty_sst(self, tmp_path):
        lanes = np.zeros((0, 2), np.uint32)
        t = pa.table({"v": pa.array([], pa.int64())})
        path = str(tmp_path / "e.sst")
        SstWriter().write(path, lanes, t)
        r = SstReader(path, BlockCache())
        hit, rows = r.probe(np.zeros((3, 2), np.uint32))
        assert len(hit) == 0 and rows is None

    def test_lookup_oracle_with_tombstones(self, tmp_path):
        """End to end through LocalTableQuery: updates + deletes, the
        native answers identical to python AND to the merged scan."""
        from paimon_tpu.lookup import LocalTableQuery
        t = _pk_table(str(tmp_path / "t"), buckets=2)
        _commit(t, [{"id": i, "name": f"a{i}"} for i in range(300)])
        _commit(t, [{"id": i, "name": f"b{i}"}
                    for i in range(0, 300, 3)])
        from paimon_tpu.types import RowKind
        _commit(t, [{"id": i, "name": "x"} for i in range(0, 300, 5)],
                kinds=[RowKind.DELETE] * len(range(0, 300, 5)))
        oracle = {r["id"]: r["name"]
                  for r in t.to_arrow().to_pylist()}
        q = LocalTableQuery(t, cache_dir=str(tmp_path / "c"))
        keys = [{"id": i} for i in range(-5, 310)]
        native_probes0 = _counter(LOOKUP_NATIVE_PROBES).count
        got_native = q.lookup(keys)
        assert _counter(LOOKUP_NATIVE_PROBES).count > native_probes0
        with force_python_probe():
            got_python = q.lookup(keys)
        assert got_native == got_python
        for k, row in zip(keys, got_native):
            exp = oracle.get(k["id"])
            if exp is None:
                assert row is None, (k, row)
            else:
                assert row == {"id": k["id"], "name": exp}

    def test_lookup_partitioned_batches(self, tmp_path):
        """Per-partition batches against a partitioned pk table (and
        multiple buckets inside each): the native probe resolves each
        partition's sub-batches identically to python, including a
        partition that does not exist."""
        from paimon_tpu.lookup import LocalTableQuery
        t = _pk_table(str(tmp_path / "t"), buckets=2, partition=True)
        rows = [{"p": p, "id": i, "name": f"p{p}-{i}"}
                for p in range(3) for i in range(100)]
        _commit(t, rows)
        q = LocalTableQuery(t, cache_dir=str(tmp_path / "c"))
        for p in range(4):                    # p=3 does not exist
            keys = [{"p": p, "id": i} for i in range(0, 110, 7)]
            got = q.lookup(keys, partition=(p,))
            with force_python_probe():
                exp = q.lookup(keys, partition=(p,))
            assert got == exp
            for k, row in zip(keys, got):
                if p < 3 and k["id"] < 100:
                    assert row["name"] == f"p{p}-{k['id']}"
                else:
                    assert row is None


# -- fallback ----------------------------------------------------------------


@needs_probe
class TestNativeFallback:
    def test_missing_library_degrades_per_call(self, tmp_path,
                                               monkeypatch):
        """native.sst_probe returning None (no native library) must
        fall back to python per call, count `lookup.native_fallbacks`,
        and answer identically.  The raw pointer prepared path is
        disabled up front (without a library no prep context
        resolves), so every probe routes through sst_probe — the
        per-call degradation gate under test."""
        from paimon_tpu.lookup import LocalTableQuery
        monkeypatch.setattr(native, "sst_probe_prepare",
                            lambda *a, **k: None)
        t = _pk_table(str(tmp_path / "t"), buckets=1)
        _commit(t, [{"id": i, "name": f"n{i}"} for i in range(100)])
        q = LocalTableQuery(t, cache_dir=str(tmp_path / "c"))
        keys = [{"id": i} for i in range(0, 100, 3)] + [{"id": 999}]
        expected = q.lookup(keys)
        fallbacks0 = _counter(LOOKUP_NATIVE_FALLBACKS).count
        native0 = _counter(LOOKUP_NATIVE_PROBES).count
        monkeypatch.setattr(native, "sst_probe",
                            lambda *a, **k: None)
        assert q.lookup(keys) == expected
        assert _counter(LOOKUP_NATIVE_FALLBACKS).count > fallbacks0
        assert _counter(LOOKUP_NATIVE_PROBES).count == native0
        monkeypatch.undo()
        fallbacks1 = _counter(LOOKUP_NATIVE_FALLBACKS).count
        assert q.lookup(keys) == expected      # healed: native again
        assert _counter(LOOKUP_NATIVE_FALLBACKS).count == fallbacks1


# -- concurrent serving through the native probe -----------------------------


@needs_probe
class TestConcurrentNativeServing:
    def test_lookups_under_live_commits_and_compaction(self, tmp_path):
        """Concurrent /lookup batches through the native probe while
        commits land and a full compaction rewrites the files: every
        batch is torn-free (all rows from ONE snapshot's state: the
        old name generation or the new, never a mix), zero fallbacks,
        and the compacted-away files' SSTs are dropped then rebuilt
        exactly once per new file."""
        from paimon_tpu.service import KvQueryClient, KvQueryServer
        t = _pk_table(str(tmp_path / "t"), buckets=2, extra_opts={
            "service.lookup.refresh-interval": "20"})
        n = 200
        _commit(t, [{"id": i, "name": f"g0-{i}"} for i in range(n)])
        server = KvQueryServer(t).start()
        fallbacks0 = _counter(LOOKUP_NATIVE_FALLBACKS).count
        stop = threading.Event()
        errors = []

        def reader(seed):
            rng = np.random.default_rng(seed)
            try:
                with KvQueryClient(t, tenant=f"t{seed}") as c:
                    while not stop.is_set():
                        ids = sorted(
                            int(k) for k in rng.integers(0, n, 8))
                        rows = c.lookup([{"id": i} for i in ids])
                        gens = set()
                        for i, row in zip(ids, rows):
                            assert row is not None, (i, "missing row")
                            gen, rest = row["name"].split("-", 1)
                            assert int(rest) == i, row
                            gens.add(gen)
                        # batch coherence: one generation per batch
                        assert len(gens) == 1, f"torn batch: {gens}"
            except Exception as e:      # noqa: BLE001
                errors.append(repr(e))

        threads = [threading.Thread(target=reader, args=(i,))
                   for i in range(4)]
        try:
            [x.start() for x in threads]
            for g in range(1, 4):
                time.sleep(0.15)
                _commit(t, [{"id": i, "name": f"g{g}-{i}"}
                            for i in range(n)])
            t.copy({"write-only": "false"}).compact(full=True)
            time.sleep(0.3)
            stop.set()
            [x.join() for x in threads]
            # post-compaction: the query's live SSTs reference only
            # files that still exist (dropped generations evicted,
            # rebuilt against the compacted files)
            q = server._query
            assert q is not None
            for key in q.store.keys():
                r = q.store.get(key)
                assert r is None or os.path.exists(r.path), key
        finally:
            stop.set()
            [x.join() for x in threads]
            server.stop()
        assert errors == []
        assert _counter(LOOKUP_NATIVE_FALLBACKS).count == fallbacks0


# -- warm boot ---------------------------------------------------------------


@needs_probe
class TestWarmBoot:
    def test_restore_serves_with_zero_reader_builds(self, tmp_path):
        """The r12 warm-boot proof: persist a warm query's state, then
        a FRESH query restores it and serves correct answers without
        building a single SST (reader_builds delta == 0)."""
        from paimon_tpu.core.plan_cache import reset_plan_caches
        from paimon_tpu.lookup import LocalTableQuery
        from paimon_tpu.service import warmboot
        t = _pk_table(str(tmp_path / "t"), buckets=2)
        _commit(t, [{"id": i, "name": f"n{i}"} for i in range(200)])
        q1 = LocalTableQuery(t, cache_dir=str(tmp_path / "c1"))
        keys = [{"id": i} for i in range(200)]
        expected = q1.lookup(keys)
        dest = str(tmp_path / "warm")
        meta = warmboot.persist_serving_state(q1, dest)
        assert meta["ssts"] >= 2 and meta["plan"]
        q1.close()
        reset_plan_caches()
        q2 = LocalTableQuery(t, cache_dir=str(tmp_path / "c2"))
        restored = warmboot.restore_serving_state(q2, dest)
        assert restored["ssts"] == meta["ssts"] and restored["plan"]
        builds0 = _counter(LOOKUP_READER_BUILDS).count
        assert q2.lookup(keys) == expected
        assert _counter(LOOKUP_READER_BUILDS).count == builds0, \
            "warm boot rebuilt SSTs it should have adopted"
        q2.close()

    def test_server_persists_on_shutdown_and_restores(self, tmp_path):
        """KvQueryServer wiring: with service.warmboot.enabled a
        server persists on shutdown and the next server (same SSD
        tier) boots from it — reader_builds frozen across the second
        server's first lookups."""
        from paimon_tpu.core.plan_cache import reset_plan_caches
        from paimon_tpu.service import KvQueryClient, KvQueryServer
        t = _pk_table(str(tmp_path / "t"), buckets=2, extra_opts={
            "cache.disk.dir": str(tmp_path / "ssd"),
            "service.warmboot.enabled": "true"})
        _commit(t, [{"id": i, "name": f"n{i}"} for i in range(100)])
        keys = [{"id": i} for i in range(100)]
        s1 = KvQueryServer(t)
        s1.server.start()
        with KvQueryClient(address=s1.address) as c:
            expected = c.lookup(keys)
        s1.shutdown()                       # persists the warm state
        reset_plan_caches()
        s2 = KvQueryServer(t)
        s2.server.start()
        try:
            builds0 = _counter(LOOKUP_READER_BUILDS).count
            with KvQueryClient(address=s2.address) as c:
                assert c.lookup(keys) == expected
            assert _counter(LOOKUP_READER_BUILDS).count == builds0
            assert s2.last_warm_restore["ssts"] >= 2
        finally:
            s2.shutdown()

    def test_missing_state_degrades_to_cold(self, tmp_path):
        from paimon_tpu.lookup import LocalTableQuery
        from paimon_tpu.service import warmboot
        t = _pk_table(str(tmp_path / "t"), buckets=1)
        _commit(t, [{"id": 1, "name": "a"}])
        q = LocalTableQuery(t, cache_dir=str(tmp_path / "c"))
        out = warmboot.restore_serving_state(
            q, str(tmp_path / "nowhere"))
        assert out == {"ssts": 0, "plan": False}
        assert q.lookup_row({"id": 1})["name"] == "a"


# -- remote replica registration ---------------------------------------------


class TestRouterRegistration:
    def _serving_table(self, tmp_path, interval="100 ms"):
        t = _pk_table(str(tmp_path / "t"), buckets=2, extra_opts={
            "service.replicas.health-interval": interval})
        _commit(t, [{"id": i, "name": f"n{i}"} for i in range(50)])
        return t

    def _get(self, address, path):
        with urllib.request.urlopen(address + path, timeout=10) as r:
            return json.loads(r.read())

    def _lookup_via(self, address, tenant, key):
        from paimon_tpu.service import KvQueryClient
        with KvQueryClient(address=address, tenant=tenant,
                           follow_topology=False) as c:
            row = c.lookup([{"id": key}])[0]
            return row, c.last_replica

    def test_register_joins_ring_and_serves(self, tmp_path):
        from paimon_tpu.service import KvQueryServer
        from paimon_tpu.service.router import ReplicaRouter
        t = self._serving_table(tmp_path)
        s0 = KvQueryServer(t, replica_id=0)
        s0.server.start()
        s1 = KvQueryServer(t, replica_id=1)
        s1.server.start()
        router = ReplicaRouter(servers=[s0]).start()
        try:
            out = s1.register_with_router(router.address)
            assert out == {"registered": 1, "replica_count": 2}
            top = self._get(router.address, "/topology")
            assert [e["id"] for e in top["replicas"]] == [0, 1]
            seen = set()
            for ten in range(16):
                row, rep = self._lookup_via(router.address,
                                            f"t{ten}", 3)
                assert row == {"id": 3, "name": "n3"}
                seen.add(rep)
            assert seen == {"0", "1"}, \
                "registered replica never served"
            # re-register with a new address wins (restart case)
            s1.register_with_router(router.address)
            assert len(self._get(router.address,
                                 "/topology")["replicas"]) == 2
        finally:
            router.stop()
            s1.shutdown()
            s0.shutdown()

    def test_health_loop_suspends_and_readmits(self, tmp_path):
        from paimon_tpu.service import KvQueryServer
        from paimon_tpu.service.router import ReplicaRouter, _UpstreamPool
        t = self._serving_table(tmp_path)
        s0 = KvQueryServer(t, replica_id=0)
        s0.server.start()
        s1 = KvQueryServer(t, replica_id=1)
        s1.server.start()
        router = ReplicaRouter(servers=[s0]).start()
        try:
            s1.register_with_router(router.address)

            def wait_for(pred, timeout=5.0):
                deadline = time.monotonic() + timeout
                while time.monotonic() < deadline:
                    if pred():
                        return True
                    time.sleep(0.02)
                return False

            # black-hole the replica's pool: two consecutive failed
            # probes must suspend it out of the ring
            pool = router._remote[1]
            real_request = _UpstreamPool.request

            def dead(self, *a, **k):
                if self is pool:
                    raise ConnectionError("injected outage")
                return real_request(self, *a, **k)

            _UpstreamPool.request = dead
            try:
                assert wait_for(lambda: self._get(
                    router.address, "/topology")["suspended"] == [1])
                h = self._get(router.address, "/healthz")
                assert h["status"] == "degraded"
                assert h["replicas"]["1"] == {"suspended": True}
                # every tenant still answered by the survivor
                for ten in range(12):
                    row, rep = self._lookup_via(router.address,
                                                f"t{ten}", 7)
                    assert row == {"id": 7, "name": "n7"}
                    assert rep == "0"
            finally:
                _UpstreamPool.request = real_request
            # first healthy probe re-admits
            assert wait_for(lambda: self._get(
                router.address, "/topology")["suspended"] == [])
            seen = {self._lookup_via(router.address, f"t{i}", 3)[1]
                    for i in range(16)}
            assert seen == {"0", "1"}
        finally:
            router.stop()
            s1.shutdown()
            s0.shutdown()

    def test_deregister_leaves_cleanly(self, tmp_path):
        from paimon_tpu.service import KvQueryServer
        from paimon_tpu.service.router import ReplicaRouter
        t = self._serving_table(tmp_path)
        s0 = KvQueryServer(t, replica_id=0)
        s0.server.start()
        s1 = KvQueryServer(t, replica_id=1)
        s1.server.start()
        router = ReplicaRouter(servers=[s0]).start()
        try:
            s1.register_with_router(router.address)
            req = urllib.request.Request(
                router.address + "/deregister",
                data=json.dumps({"id": 1}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=10) as r:
                assert json.loads(r.read()) == {
                    "deregistered": 1, "replica_count": 1}
            for ten in range(12):
                row, rep = self._lookup_via(router.address,
                                            f"t{ten}", 3)
                assert row == {"id": 3, "name": "n3"}
                assert rep == "0"
            # unknown / in-process ids refused
            for rid, code in ((1, 404), (0, 404)):
                req = urllib.request.Request(
                    router.address + "/deregister",
                    data=json.dumps({"id": rid}).encode(),
                    headers={"Content-Type": "application/json"})
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(req, timeout=10)
                assert ei.value.code == code
        finally:
            router.stop()
            s1.shutdown()
            s0.shutdown()
