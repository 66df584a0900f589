"""Tail-tolerance chaos benchmark (ISSUE 9 acceptance, BENCH_r08).

Four scenarios against a latency/fault-injected object store, each
proving one leg of the tail-tolerance plane:

1. **tail_p99** — 1% of store GETs injected at 20x base latency
   (`LatencyInjectingObjectStoreBackend` tail mode); the same seeded
   schedule is scanned hedged vs unhedged.  Acceptance: hedged scan
   p99 >= 3x better, rows byte-identical throughout.
2. **breaker_fast_fail** — a backend forced sick trips the breaker;
   subsequent calls through the full RetryingObjectStoreBackend
   ladder must fail in <10ms with ZERO store traffic (vs riding the
   ladder's backoff, also measured), then recover through the
   half-open probe once healed.
3. **deadline_504** — every store op hangs 250ms, the request budget
   is 100ms: the 504 (DeadlineExceededError) must surface within
   deadline + small grace (grace is bounded by ONE in-flight op —
   measured at the serving plane over HTTP and at the table API).
4. **chaos_ingest_fsck** — ingest under Pareto-tailed latency + 503
   storms + ambiguous PUTs with hedging and breakers armed; the
   table must contain exactly the written rows and a post-chaos
   `fsck` must be clean (hedges cause no duplicate side effects, no
   orphaned partial commits).

Usage:
    python -m benchmarks.chaos_bench        # prints one JSON line

Env: CHAOS_ROWS (default 40_000), CHAOS_SCANS (default 150).
CPU-only like micro.py — bench.py owns the TPU.
"""

import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROWS = int(os.environ.get("CHAOS_ROWS", "40000"))
SCANS = int(os.environ.get("CHAOS_SCANS", "150"))
BUCKETS = 4

_SCHEMES = [0]


def _schema(extra=None):
    from paimon_tpu.schema import Schema
    from paimon_tpu.types import BigIntType, DoubleType, IntType
    opts = {"bucket": str(BUCKETS),
            # the footer cache is process-global: disabled so the
            # second mode cannot ride the first mode's warm metadata
            "read.cache.footer": "false"}
    opts.update(extra or {})
    return (Schema.builder()
            .column("id", BigIntType(False))
            .column("g", IntType())
            .column("v", DoubleType())
            .primary_key("id")
            .options(opts).build())


def _fill(table, n, start=0):
    import numpy as np
    import pyarrow as pa
    wb = table.new_batch_write_builder()
    w = wb.new_write()
    ids = np.arange(start, start + n, dtype=np.int64)
    w.write_arrow(pa.table({
        "id": ids, "g": (ids % 97).astype("int32"),
        "v": ids.astype("float64") * 0.5}))
    wb.new_commit().commit(w.prepare_commit())
    w.close()


def _percentile(vals, p):
    vals = sorted(vals)
    return vals[min(len(vals) - 1, int(p / 100.0 * len(vals)))]


def _scan_ms(table, n, warmup=0):
    """Per-query wall times over a CACHED plan — the serving plane's
    steady-state shape (lookup/local_query.py caches the plan per
    snapshot; a production query's store traffic is the DATA reads,
    not a fresh manifest walk per request).  `warmup` queries run
    first unmeasured, warming the hedge latency model and its rate
    budget identically in both modes."""
    rb = table.new_read_builder()
    splits = rb.new_scan().plan().splits
    read = rb.new_read()
    for _ in range(warmup):
        read.to_arrow(splits)
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        read.to_arrow(splits)
        out.append((time.perf_counter() - t0) * 1000.0)
    return out


def bench_tail_p99(tmp):
    """1%-of-GETs-20x tail: hedged vs unhedged scan p99."""
    from paimon_tpu.fs.object_store import (
        LatencyInjectingObjectStoreBackend, LocalObjectStoreBackend,
        ObjectStoreFileIO,
    )
    from paimon_tpu.fs.resilience import (
        LatencyTracker, ResilientObjectStoreBackend,
    )
    from paimon_tpu.table import FileStoreTable

    _SCHEMES[0] += 1
    scheme = f"chaos{_SCHEMES[0]}"
    store = LocalObjectStoreBackend(os.path.join(tmp, "tail"))
    plain = ObjectStoreFileIO(store, scheme=f"{scheme}://")
    t0 = FileStoreTable.create(f"{scheme}://t", _schema(),
                               file_io=plain)
    _fill(t0, ROWS)
    _fill(t0, ROWS // 4, start=ROWS)         # second run: merge work
    expected = t0.to_arrow().sort_by("id")

    results = {}
    rows_identical = True
    for mode in ("unhedged", "hedged"):
        # SAME seed for both modes: identical injected tail schedule
        lat = LatencyInjectingObjectStoreBackend(
            store, base_ms=8.0, jitter_ms=1.0, seed=42,
            tail_rate=0.01, tail_multiplier=20.0)
        fio = ObjectStoreFileIO(lat, scheme=f"{scheme}://")
        dyn = {"read.cache.footer": "false"}
        if mode == "hedged":
            dyn.update({"read.hedge.enabled": "true",
                        "read.hedge.min-delay": "2"})
        table = FileStoreTable.load(f"{scheme}://t", file_io=fio,
                                    dynamic_options=dyn)
        res = None
        if mode == "hedged":
            res = table.file_io.backend
            assert isinstance(res, ResilientObjectStoreBackend)
            res.tracker = LatencyTracker(min_samples=10)
        got = table.to_arrow().sort_by("id")     # identity check
        rows_identical &= got.equals(expected)
        samples = _scan_ms(table, SCANS, warmup=20)
        results[mode] = {
            "p50_ms": round(_percentile(samples, 50), 2),
            "p95_ms": round(_percentile(samples, 95), 2),
            "p99_ms": round(_percentile(samples, 99), 2),
            "mean_ms": round(sum(samples) / len(samples), 2),
            "tail_hits": lat.stats["tail_hits"],
        }
        if res is not None:
            results[mode]["hedges_issued"] = res._hedges
            results[mode]["hedgeable_ops"] = res._ops
            results[mode]["hedge_load_ratio"] = round(
                res._hedges / max(1, res._ops), 4)
            res.close()
    speedup = results["unhedged"]["p99_ms"] / \
        max(0.001, results["hedged"]["p99_ms"])
    return {"modes": results,
            "hedged_p99_speedup": round(speedup, 2),
            "rows_identical": rows_identical}


def bench_breaker_fast_fail(tmp):
    """Sick backend: breaker-open calls fail fast vs riding the retry
    ladder; half-open probe recovers once healed."""
    from paimon_tpu.fs.object_store import (
        CircuitOpenError, LocalObjectStoreBackend,
        RetryingObjectStoreBackend, TransientStoreError,
    )
    from paimon_tpu.fs.resilience import (
        CircuitBreaker, ResilientObjectStoreBackend,
    )

    class Sick(LocalObjectStoreBackend):
        sick = False
        calls = 0

        def get(self, key, offset=0, length=None):
            type(self).calls += 1
            if self.sick:
                raise TransientStoreError("injected sick store")
            return super().get(key, offset, length)

    store = Sick(os.path.join(tmp, "sick"))
    store.put("k", b"payload")
    breaker = CircuitBreaker("bench-sick", failure_threshold=5,
                             open_ms=400.0)
    res = ResilientObjectStoreBackend(store, name="bench-sick",
                                      breaker=breaker)
    ladder = RetryingObjectStoreBackend(res, max_attempts=6,
                                        backoff_s=0.05)
    # no breaker: the same sickness rides the full backoff ladder
    bare = RetryingObjectStoreBackend(
        ResilientObjectStoreBackend(Sick(os.path.join(tmp, "sick2")),
                                    name="bench-sick2"),
        max_attempts=6, backoff_s=0.05)
    Sick.sick = True
    t0 = time.perf_counter()
    try:
        bare.get("k")
    except TransientStoreError:
        pass
    ladder_ms = (time.perf_counter() - t0) * 1000.0

    try:
        ladder.get("k")                     # trips the breaker inside
    except TransientStoreError:
        pass
    assert breaker.state == "open"
    calls_before = Sick.calls
    fast = []
    for _ in range(50):
        t0 = time.perf_counter()
        try:
            ladder.get("k")
        except CircuitOpenError:
            pass
        fast.append((time.perf_counter() - t0) * 1000.0)
    zero_traffic = Sick.calls == calls_before
    # heal; after open-ms the half-open probe re-closes
    Sick.sick = False
    time.sleep(0.45)
    recovered = ladder.get("k") == b"payload" and \
        breaker.state == "closed"
    res.close()
    return {"ladder_unbroken_ms": round(ladder_ms, 1),
            "breaker_open_max_ms": round(max(fast), 2),
            "breaker_open_mean_ms": round(sum(fast) / len(fast), 3),
            "zero_store_traffic_while_open": zero_traffic,
            "recovered_after_open_ms": recovered}


def bench_deadline_504(tmp):
    """Stuck store (250ms hangs per op), 100ms budget: the 504 must
    land within deadline + one-op grace, at the table API and over
    HTTP at the serving plane."""
    from paimon_tpu.fs.object_store import (
        LatencyInjectingObjectStoreBackend, LocalObjectStoreBackend,
        ObjectStoreFileIO,
    )
    from paimon_tpu.service.query_service import KvQueryClient, KvQueryServer
    from paimon_tpu.table import FileStoreTable
    from paimon_tpu.utils.deadline import DeadlineExceededError

    _SCHEMES[0] += 1
    scheme = f"chaos{_SCHEMES[0]}"
    store = LocalObjectStoreBackend(os.path.join(tmp, "stuck"))
    lat = LatencyInjectingObjectStoreBackend(store, base_ms=0.0, seed=7)
    fio = ObjectStoreFileIO(lat, scheme=f"{scheme}://")
    t = FileStoreTable.create(f"{scheme}://t", _schema(),
                              file_io=fio)
    _fill(t, 5000)
    deadline_ms, stuck_ms = 100.0, 250.0

    lat.stuck_rate, lat.stuck_ms = 1.0, stuck_ms
    t_api = t.copy({"request.timeout": str(int(deadline_ms))})
    t0 = time.perf_counter()
    try:
        t_api.to_arrow()
        api_elapsed = None                  # finished?! (cached)
    except DeadlineExceededError:
        api_elapsed = (time.perf_counter() - t0) * 1000.0
    lat.stuck_rate = 0.0

    srv = KvQueryServer(t.copy({"service.cache.shared": "false"})).start()
    try:
        lat.stuck_rate = 1.0
        client = KvQueryClient(address=srv.address,
                               timeout_ms=deadline_ms)
        t0 = time.perf_counter()
        try:
            client.scan(limit=500)
            http_elapsed = None
        except DeadlineExceededError:
            http_elapsed = (time.perf_counter() - t0) * 1000.0
        lat.stuck_rate = 0.0
    finally:
        lat.stuck_rate = 0.0
        srv.stop()
    grace = stuck_ms + 150.0                # one in-flight op + slack
    return {"deadline_ms": deadline_ms, "stuck_op_ms": stuck_ms,
            "api_504_ms": None if api_elapsed is None
            else round(api_elapsed, 1),
            "http_504_ms": None if http_elapsed is None
            else round(http_elapsed, 1),
            "within_grace": all(
                e is not None and e <= deadline_ms + grace
                for e in (api_elapsed, http_elapsed))}


def bench_chaos_ingest_fsck(tmp):
    """Ingest under Pareto tail + 503 storms + ambiguous PUTs with
    hedging/breaker armed: rows exact, fsck clean."""
    from paimon_tpu.fs.object_store import (
        FlakyObjectStoreBackend, LatencyInjectingObjectStoreBackend,
        LocalObjectStoreBackend, ObjectStoreFileIO,
        RetryingObjectStoreBackend,
    )
    from paimon_tpu.maintenance.fsck import fsck
    from paimon_tpu.table import FileStoreTable

    _SCHEMES[0] += 1
    scheme = f"chaos{_SCHEMES[0]}"
    store = LocalObjectStoreBackend(os.path.join(tmp, "ingest"))
    lat = LatencyInjectingObjectStoreBackend(
        store, base_ms=0.5, seed=13, tail_rate=0.03, pareto_alpha=1.3)
    flaky = FlakyObjectStoreBackend(lat, seed=17, fail_rate=0.03,
                                    ambiguous_rate=0.01)
    fio = ObjectStoreFileIO(RetryingObjectStoreBackend(flaky),
                            scheme=f"{scheme}://")
    t = FileStoreTable.create(
        f"{scheme}://t",
        _schema({"read.hedge.enabled": "true",
                 "store.breaker.enabled": "true",
                 # a 3% 503 storm is weather, not sickness: the rate
                 # trip wire must not open on it (threshold well above)
                 "store.breaker.error-rate": "0.6",
                 "store.breaker.failure-threshold": "8"}),
        file_io=fio)
    n, commits = 20_000, 3
    t0 = time.perf_counter()
    for c in range(commits):
        _fill(t, n, start=c * n)
    ingest_s = time.perf_counter() - t0
    got = t.to_arrow()
    ids = got.column("id").to_pylist()
    rows_exact = (got.num_rows == n * commits and
                  len(set(ids)) == n * commits)
    report = fsck(t)
    return {"rows": n * commits, "commits": commits,
            "ingest_s": round(ingest_s, 2),
            "injected_503s": flaky.stats["injected"],
            "ambiguous_puts": flaky.stats["ambiguous"],
            "pareto_tail_hits": lat.stats["tail_hits"],
            "rows_exact": rows_exact,
            "fsck_clean": report.ok,
            "fsck_violations": [v.kind for v in report.violations]}


def measure(emit=print):
    tmp = tempfile.mkdtemp(prefix="chaos_bench_")
    out = {"rows": ROWS, "scans": SCANS, "scenarios": {}}
    out["scenarios"]["tail_p99"] = bench_tail_p99(tmp)
    out["scenarios"]["breaker"] = bench_breaker_fast_fail(tmp)
    out["scenarios"]["deadline"] = bench_deadline_504(tmp)
    out["scenarios"]["ingest"] = bench_chaos_ingest_fsck(tmp)
    s = out["scenarios"]
    out["acceptance"] = {
        "hedged_p99_speedup": s["tail_p99"]["hedged_p99_speedup"],
        "hedged_p99_speedup_ok":
            s["tail_p99"]["hedged_p99_speedup"] >= 3.0,
        "rows_identical": s["tail_p99"]["rows_identical"],
        "breaker_fast_fail_ok":
            s["breaker"]["breaker_open_max_ms"] < 10.0 and
            s["breaker"]["zero_store_traffic_while_open"],
        "deadline_504_within_grace": s["deadline"]["within_grace"],
        "post_chaos_fsck_clean":
            s["ingest"]["fsck_clean"] and s["ingest"]["rows_exact"],
    }
    import shutil
    shutil.rmtree(tmp, ignore_errors=True)
    if emit:
        emit(json.dumps(out))
    return out


if __name__ == "__main__":
    measure()
    sys.exit(0)
