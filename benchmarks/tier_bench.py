"""Tiered host-SSD storage benchmarks: cold scan / warm re-scan /
ingest against a latency-injected object store, serial vs tiered.

The point of the tier (ISSUE 8): cold-scan re-reads and ingest
throughput should be independent of object-store latency — the SSD
cache answers warm reads, staged uploads take the PUT round trips off
the flush pipeline's critical path.  Each scenario runs at injected
per-op latencies of 0ms / 10ms / 50ms, untiered vs tiered
(cache.disk.dir + write.stage.dir), with row identity asserted between
the two paths at every latency.

Usage:
    python -m benchmarks.tier_bench [name ...]   # default: all
Prints ONE JSON line per (benchmark, latency) like micro.py.

Env: TIER_ROWS (default 200_000), TIER_LATENCIES_MS (default
"0,10,50"), TIER_BUCKETS (default 4).  CPU-only like micro.py —
bench.py owns the TPU.
"""

import json
import os
import shutil
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402

ROWS = int(os.environ.get("TIER_ROWS", "300000"))
INGEST_ROWS = int(os.environ.get("TIER_INGEST_ROWS", "10000000"))
LATENCIES = [int(x) for x in
             os.environ.get("TIER_LATENCIES_MS", "0,10,50").split(",")]
BUCKETS = int(os.environ.get("TIER_BUCKETS", "4"))

_SCHEMES = [0]


def make_table(tmp, latency_ms, extra=None):
    """A pk table on a LOCAL object-store emulation wrapped in the
    latency injector — every backend round trip pays `latency_ms`
    like a real S3/GCS request would."""
    from paimon_tpu.fs.object_store import (
        LatencyInjectingObjectStoreBackend, LocalObjectStoreBackend,
        ObjectStoreFileIO,
    )
    from paimon_tpu.schema import Schema
    from paimon_tpu.table import FileStoreTable
    from paimon_tpu.types import BigIntType, DoubleType, IntType

    _SCHEMES[0] += 1
    scheme = f"tier{_SCHEMES[0]}"
    backend = LocalObjectStoreBackend(
        os.path.join(tmp, f"bucket_{scheme}"))
    if latency_ms:
        backend = LatencyInjectingObjectStoreBackend(
            backend, base_ms=float(latency_ms), jitter_ms=0.0, seed=7)
    fio = ObjectStoreFileIO(backend, scheme=f"{scheme}://")
    options = {"bucket": str(BUCKETS), "write-only": "true",
               "parquet.enable.dictionary": "false",
               "write-buffer-size": "48 kb"}
    options.update(extra or {})
    schema = (Schema.builder()
              .column("id", BigIntType(False))
              .column("v1", BigIntType())
              .column("v2", DoubleType())
              .column("v3", IntType())
              .primary_key("id")
              .options(options)
              .build())
    return FileStoreTable.create(f"{scheme}://t", schema, file_io=fio)


def _data(rows, seed=7):
    rng = np.random.default_rng(seed)
    ids = rng.permutation(rows)
    return pa.table({
        "id": pa.array(ids, pa.int64()),
        "v1": pa.array(rng.integers(0, 1 << 40, rows), pa.int64()),
        "v2": pa.array(rng.random(rows), pa.float64()),
        "v3": pa.array(rng.integers(0, 100, rows).astype(np.int32),
                       pa.int32()),
    })


def ingest(table, data, chunks=8):
    wb = table.new_batch_write_builder()
    per = data.num_rows // chunks
    t0 = time.perf_counter()
    with wb.new_write() as w:
        for i in range(chunks):
            w.write_arrow(data.slice(i * per, per))
        wb.new_commit().commit(w.prepare_commit())
    return time.perf_counter() - t0


def scan_cold_then_warm(table):
    """(cold_s, warm_s, rows) — cold plans AND reads (every store round
    trip paid); warm re-reads the SAME plan through a fresh TableRead,
    the serving-plane shape (lookup/local_query.py caches the plan per
    snapshot), so it isolates the data RE-READ the SSD tier absorbs."""
    rb = table.new_read_builder()
    t0 = time.perf_counter()
    splits = rb.new_scan().plan().splits
    read = rb.new_read()
    cold_t = pa.concat_tables(
        [t for _, _, t in read.iter_splits(splits)],
        promote_options="none")
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    read = rb.new_read()
    warm_t = pa.concat_tables(
        [t for _, _, t in read.iter_splits(splits)],
        promote_options="none")
    warm = time.perf_counter() - t0
    assert warm_t.num_rows == cold_t.num_rows
    return cold, warm, cold_t.sort_by("id")


def _emit(name, rows, seconds, **extra):
    out = {"benchmark": name, "value": round(rows / seconds, 1),
           "unit": "rows/s", "rows": rows,
           "best_seconds": round(seconds, 6)}
    out.update(extra)
    print(json.dumps(out), flush=True)


def measure(rows=ROWS, ingest_rows=INGEST_ROWS, latencies=LATENCIES,
            emit=_emit):
    """The full matrix; returns a dict bench.py embeds.  Tiered config:
    host-SSD cache tier + staged uploads (a wide upload pool — staged
    PUTs are independent); untiered: same store, no local tiers.  Row
    identity asserted tiered-vs-untiered per latency.

    Two table shapes, because the two acceptance criteria stress
    different costs: the SCAN tables use many small files (a scan's
    store cost must be dominated by the data-file GETs the SSD tier
    absorbs — real tables have far more files than the ~6 uncacheable
    snapshot-chain reads a cold plan pays), while the INGEST tables
    use production-sized files at larger volume (so the commit
    metadata chain — snapshot probes + manifest writes + CAS, which
    staging deliberately does NOT touch — amortizes the way it does in
    a real ingest batch)."""
    from paimon_tpu.fs.caching import reset_disk_tiers

    scan_data = _data(rows)
    ingest_data = _data(ingest_rows, seed=11)
    results = {"rows": rows, "ingest_rows": ingest_rows,
               "buckets": BUCKETS, "latencies": {}}
    zero_ingest = None
    for lat in latencies:
        tmp = tempfile.mkdtemp(prefix="tier-bench-")
        try:
            tiered_opts = {
                "cache.disk.dir": os.path.join(tmp, "ssd"),
                "write.stage.dir": os.path.join(tmp, "stage"),
                "write.stage.parallelism": "32",
            }
            ingest_shape = {"write-buffer-size": "1 mb"}

            # -- ingest acceptance (production-sized files) ----------
            # best-of-2 into fresh tables: a single-pass ingest timing
            # is noisy enough to swing the acceptance ratio
            def timed_ingest(extra):
                best, table = float("inf"), None
                for _ in range(2):
                    table = make_table(tmp, lat, extra=extra)
                    best = min(best,
                               ingest(table, ingest_data, chunks=16))
                return best, table

            dt_plain_ingest, plain = timed_ingest(ingest_shape)
            dt_tiered_ingest, tiered = timed_ingest(
                {**ingest_shape, **tiered_opts})
            ingest_identical = bool(
                plain.to_arrow().sort_by("id").equals(
                    tiered.to_arrow().sort_by("id")))

            # -- scan acceptance (many small files) ------------------
            plain = make_table(tmp, lat)
            ingest(plain, scan_data, chunks=32)
            dt_plain_cold, dt_plain_warm, plain_rows = \
                scan_cold_then_warm(plain)

            tiered = make_table(tmp, lat, extra=tiered_opts)
            ingest(tiered, scan_data, chunks=32)
            # the staged uploads SEEDED the SSD tier: the first scan
            # after ingest reads data without a single store GET —
            # record it, then CLEAR the tier AND the process footer
            # cache (warmed by the seeded scan) so cold is honestly
            # cold against the untiered pair
            t0 = time.perf_counter()
            tiered.to_arrow()
            dt_tiered_seeded = time.perf_counter() - t0
            tiered.file_io.state.disk.clear()
            from paimon_tpu.fs.caching import global_footer_cache
            global_footer_cache().clear()
            dt_tiered_cold, dt_tiered_warm, tiered_cold = \
                scan_cold_then_warm(tiered)

            identical = bool(plain_rows.equals(tiered_cold)) and \
                ingest_identical
            if not identical:
                raise AssertionError(
                    f"tiered rows diverged at {lat}ms")
            if lat == 0:
                zero_ingest = dt_plain_ingest
            if emit is not None:
                emit(f"tier_ingest_untiered_{lat}ms", ingest_rows,
                     dt_plain_ingest)
                emit(f"tier_ingest_tiered_{lat}ms", ingest_rows,
                     dt_tiered_ingest, identical=identical)
                emit(f"tier_cold_scan_untiered_{lat}ms", rows,
                     dt_plain_cold)
                emit(f"tier_cold_scan_tiered_{lat}ms", rows,
                     dt_tiered_cold)
                emit(f"tier_warm_scan_untiered_{lat}ms", rows,
                     dt_plain_warm)
                emit(f"tier_warm_scan_tiered_{lat}ms", rows,
                     dt_tiered_warm,
                     warm_vs_cold=round(
                         dt_tiered_cold / dt_tiered_warm, 2))
                emit(f"tier_seeded_scan_tiered_{lat}ms", rows,
                     dt_tiered_seeded)
            results["latencies"][str(lat)] = {
                "ingest_untiered_s": round(dt_plain_ingest, 4),
                "ingest_tiered_s": round(dt_tiered_ingest, 4),
                "seeded_scan_tiered_s": round(dt_tiered_seeded, 4),
                "cold_scan_untiered_s": round(dt_plain_cold, 4),
                "cold_scan_tiered_s": round(dt_tiered_cold, 4),
                "warm_scan_untiered_s": round(dt_plain_warm, 4),
                "warm_scan_tiered_s": round(dt_tiered_warm, 4),
                "warm_vs_cold_tiered": round(
                    dt_tiered_cold / dt_tiered_warm, 2),
                "identical": identical,
            }
        finally:
            reset_disk_tiers()
            shutil.rmtree(tmp, ignore_errors=True)
    # headline acceptance ratios (ISSUE 8), each at the >=10ms point
    # that stresses what it measures: the warm-re-scan speedup at the
    # HIGHEST injected latency (per-GET round trips are what the SSD
    # absorbs; at low latency the ratio floors on the latency-
    # independent decode CPU both paths pay), the ingest ratio at the
    # LOWEST >=10ms point (staging takes the per-file PUTs off the
    # critical path; the residual is the commit metadata chain —
    # snapshot probes + manifest writes + CAS — which durability
    # forbids staging and which amortizes with batch size, not
    # latency)
    lat_keys = [k for k in results["latencies"] if int(k) >= 10]
    if lat_keys:
        k_hi = max(lat_keys, key=int)
        k_lo = min(lat_keys, key=int)
        results["acceptance"] = {
            "warm_rescan_at_ms": int(k_hi),
            "warm_rescan_speedup":
                results["latencies"][k_hi]["warm_vs_cold_tiered"],
            "ingest_at_ms": int(k_lo),
            "ingest_vs_zero_latency": (
                round(results["latencies"][k_lo]["ingest_tiered_s"]
                      / zero_ingest, 3) if zero_ingest else None),
        }
    return results


BENCHES = {"matrix": lambda: measure()}


def main(argv):
    names = argv or list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        sys.stderr.write(f"unknown benchmarks {unknown}; "
                         f"available: {sorted(BENCHES)}\n")
        return 1
    for n in names:
        BENCHES[n]()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
