"""Micro-benchmarks mirroring the reference's JUnit micro-bench suite
(paimon-micro-benchmarks: TableReadBenchmark.java:43 — 1M-row scans per
format ± projection, TableWriterBenchmark, LookupReaderBenchmark /
LookupWriterBenchmark, bitmap index benchmarks).

Usage:
    python -m benchmarks.micro [name ...]       # default: all
Prints ONE JSON line per benchmark:
    {"benchmark": ..., "value": ..., "unit": "rows/s", ...}

Holds itself to the CPU backend through JAX_PLATFORMS unless the caller
set it: these are host-clock micro-benches, and a chip belongs to one
process at a time.
"""

import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402

# self-describing records: the DETECTED backend + device kind ride
# every BENCH_MICRO line (an accelerator run is visible without
# trusting the cpu-forcing preamble above to have worked)
_DEV0 = jax.devices()[0]
_PLATFORM = _DEV0.platform
_DEVICE_KIND = _DEV0.device_kind

ROWS = int(os.environ.get("MICRO_ROWS", str(1_000_000)))
RUNS = int(os.environ.get("MICRO_RUNS", "3"))
# sub-millisecond best-times are dominated by timer/dispatch noise and
# produce absurd throughputs (the 18.5B rows/s bitmap_index_probe
# artifact); _best auto-scales repetitions until one timed batch takes
# at least this long, then reports per-call time
MIN_SECONDS = float(os.environ.get("MICRO_MIN_SECONDS", "0.010"))


def _schema(file_format: str):
    from paimon_tpu.schema import Schema
    from paimon_tpu.types import BigIntType, DoubleType, IntType, VarCharType
    return (Schema.builder()
            .column("id", BigIntType(False))
            .column("v1", BigIntType())
            .column("v2", DoubleType())
            .column("v3", IntType())
            .column("s", VarCharType())
            .primary_key("id")
            .options({"bucket": "1", "write-only": "true",
                      "file.format": file_format})
            .build())


def _data(rows: int, seed: int = 7) -> pa.Table:
    rng = np.random.default_rng(seed)
    ids = rng.permutation(rows)
    return pa.table({
        "id": pa.array(ids, pa.int64()),
        "v1": pa.array(rng.integers(0, 1 << 40, rows), pa.int64()),
        "v2": pa.array(rng.random(rows), pa.float64()),
        "v3": pa.array(rng.integers(0, 100, rows).astype(np.int32),
                       pa.int32()),
        "s": pa.array(np.char.add("val-", (ids % 1000).astype(str))),
    })


def _build_table(tmp: str, file_format: str, rows: int):
    from paimon_tpu.table import FileStoreTable
    table = FileStoreTable.create(os.path.join(tmp, f"t_{file_format}"),
                                  _schema(file_format))
    wb = table.new_batch_write_builder()
    w = wb.new_write()
    w.write_arrow(_data(rows))
    wb.new_commit().commit(w.prepare_commit())
    w.close()
    return table


def _best(fn, runs: int = RUNS):
    """Best per-call seconds over `runs` batches, auto-scaling the batch
    (calls per timed measurement) until the best batch takes at least
    MIN_SECONDS — refuses to report a sub-threshold raw timing.
    Always returns (per-call seconds, reps) so callers can't mistake a
    batched per-call time for a raw measurement."""
    reps = 1
    while True:
        best = float("inf")
        for _ in range(runs):
            t0 = time.perf_counter()
            for _ in range(reps):
                fn()
            best = min(best, time.perf_counter() - t0)
        if best >= MIN_SECONDS:
            return best / reps, reps
        # overshoot by 25% so one more round normally suffices
        grow = max(2, int(MIN_SECONDS / max(best, 1e-9) * 1.25) + 1)
        reps *= grow


def _emit(name: str, rows: int, seconds, **extra):
    reps = 1
    if isinstance(seconds, tuple):       # _best auto-scaled: per-call
        seconds, reps = seconds          # time over a >=10ms batch
    out = {"benchmark": name, "value": round(rows / seconds, 1),
           "unit": "rows/s", "rows": rows,
           "best_seconds": round(seconds, 9),
           "platform": _PLATFORM, "device_kind": _DEVICE_KIND}
    if reps > 1:
        out["timed_reps"] = reps
    out.update(extra)                    # extra may override unit
    print(json.dumps(out), flush=True)


# -- benchmarks (reference TableReadBenchmark.java:43) ---------------------

def bench_read(fmt: str):
    with tempfile.TemporaryDirectory() as tmp:
        table = _build_table(tmp, fmt, ROWS)
        _emit(f"table_read_{fmt}", ROWS,
              _best(lambda: table.to_arrow()))
        _emit(f"table_read_{fmt}_projection", ROWS,
              _best(lambda: table.to_arrow(projection=["id"])),
              projection=["id"])


def bench_write(fmt: str = "parquet"):
    """reference TableWriterBenchmark.java (write + commit loop), plus
    the pipelined-vs-serial ingest comparison (full matrix in
    benchmarks/write_bench.py; this keeps the write trajectory in
    every micro run, auto-scaled to >=10ms best-times like the scan
    entry)."""
    data = _data(ROWS)
    from paimon_tpu.table import FileStoreTable

    def run():
        with tempfile.TemporaryDirectory() as tmp:
            table = FileStoreTable.create(os.path.join(tmp, "t"),
                                          _schema(fmt))
            wb = table.new_batch_write_builder()
            with wb.new_write() as w:
                w.write_arrow(data)
                wb.new_commit().commit(w.prepare_commit())

    _emit(f"table_write_{fmt}", ROWS, _best(run))
    from benchmarks.write_bench import measure_ingest
    measure_ingest()


def bench_lookup():
    """reference LookupReaderBenchmark/LookupWriterBenchmark: build the
    SST-backed point-lookup state, then random point probes."""
    from paimon_tpu.lookup import LocalTableQuery
    rows = min(ROWS, 1_000_000)
    with tempfile.TemporaryDirectory() as tmp:
        table = _build_table(tmp, "parquet", rows)
        q = LocalTableQuery(table, cache_dir=os.path.join(tmp, "cache"))
        t0 = time.perf_counter()
        q.lookup([{"id": 0}])                    # build spilled state
        _emit("lookup_build_sst", rows, time.perf_counter() - t0)
        rng = np.random.default_rng(3)
        keys = [{"id": int(k)} for k in rng.integers(0, rows, 10_000)]
        probes = _best(lambda: q.lookup(keys))
        _emit("lookup_probe", len(keys), probes, unit="probes/s")


def bench_probe():
    """SST probe kernel, native C vs python (PR-18 tentpole): the SAME
    warm readers and key batch probed through `sst_probe_batch` and
    again forced onto the python bloom+searchsorted path — the honest
    per-key cost pair of the serving hot path's innermost loop."""
    from paimon_tpu.lookup import LocalTableQuery
    from paimon_tpu.lookup.sst import force_python_probe
    rows = min(ROWS, 1_000_000)
    with tempfile.TemporaryDirectory() as tmp:
        table = _build_table(tmp, "parquet", rows)
        q = LocalTableQuery(table, cache_dir=os.path.join(tmp, "cache"))
        rng = np.random.default_rng(3)
        keys = [{"id": int(k)} for k in rng.integers(0, rows, 10_000)]
        q.lookup(keys)                           # build + warm SSTs
        native = _best(lambda: q.lookup(keys))
        with force_python_probe():
            python = _best(lambda: q.lookup(keys))
        ratio = round(python[0] / max(native[0], 1e-12), 2)
        _emit("probe_native", len(keys), native, unit="probes/s",
              native_vs_python=ratio)
        _emit("probe_python", len(keys), python, unit="probes/s")


def bench_bitmap():
    """reference bitmap index benchmarks: build + predicate filter."""
    from paimon_tpu.index.bitmap import BitmapIndex
    rows = ROWS
    rng = np.random.default_rng(5)
    col = pa.chunked_array([pa.array(rng.integers(0, 64, rows),
                                     pa.int64())])
    built = BitmapIndex.build(col)
    _emit("bitmap_index_build", rows,
          _best(lambda: BitmapIndex.build(col)))
    blob = built.serialize()
    idx = BitmapIndex.deserialize(blob)
    _emit("bitmap_index_probe", rows,
          _best(lambda: idx.eval("eq", 7)),
          blob_bytes=len(blob))


def bench_merge():
    """the flagship segmented merge on host (ops/merge.py), isolated
    from file IO — the CPU analog of the kernel the TPU runs."""
    from paimon_tpu.ops.merge import merge_runs
    from paimon_tpu.ops.normkey import NormalizedKeyEncoder
    rows = ROWS
    rng = np.random.default_rng(11)
    runs = []
    per = rows // 10
    for r in range(10):
        ids = np.sort(rng.integers(0, rows // 2, per))
        runs.append(pa.table({
            "_KEY_id": pa.array(ids, pa.int64()),
            "_SEQUENCE_NUMBER": pa.array(
                np.arange(r * per, (r + 1) * per), pa.int64()),
            "_VALUE_KIND": pa.array(np.zeros(per, np.int8), pa.int8()),
            "v": pa.array(rng.random(per), pa.float64()),
        }))
    enc = NormalizedKeyEncoder([pa.int64()], nullable=[False])
    _emit("merge_dedup_10runs", per * 10,
          _best(lambda: merge_runs(runs, ["_KEY_id"],
                                   key_encoder=enc).take()))


def bench_scan():
    """Pipelined vs serial merge-on-read scan + footer-cache re-scan
    (full matrix in benchmarks/scan_bench.py; this entry keeps the
    scan trajectory in every micro run)."""
    from benchmarks.scan_bench import bench_engine, bench_footer_cache
    bench_engine("deduplicate")
    bench_footer_cache()


def bench_obs():
    """Observability overhead on the scan hot path: the same table
    scanned four ways —

      * no-instrumentation baseline: trace AND metrics off, so every
        span() call is one flag check returning the shared no-op;
      * disabled (the DEFAULT): trace off, metrics on (stage latency
        histograms record);
      * enabled: full span collection into the ring;
      * fleet: enabled PLUS the cross-process plane — flight recorder
        on and a trace.export.dir spool flushed after every scan (the
        worst-case per-operation flush cadence; real daemons flush on
        export/drain).

    Reports best-of times plus overhead percentages; the tier-1 test
    asserts obs_overhead_disabled_pct < 2.  Overheads are measured over
    `OBS_TRIALS` interleaved rounds and the minimum is kept — the true
    disabled overhead is ~0.1%, so any excess is timer noise and the
    min is the honest estimate."""
    from paimon_tpu import obs
    from paimon_tpu.obs import flight
    from paimon_tpu.obs.trace import set_export_dir, spool_flush

    rows = min(ROWS, 200_000)
    trials = int(os.environ.get("OBS_TRIALS", "3"))
    with tempfile.TemporaryDirectory() as tmp:
        table = _build_table(tmp, "parquet", rows)
        table.to_arrow()                    # warm footer/page caches
        spool_dir = os.path.join(tmp, "spool")

        def scan():
            table.to_arrow()

        def scan_fleet():
            table.to_arrow()
            flight.record("bench.scan", rows=rows)
            spool_flush()

        was_tracing = obs.tracing_enabled()
        was_metrics = obs.metrics_enabled()
        try:
            best = {"base": float("inf"), "disabled": float("inf"),
                    "enabled": float("inf"), "fleet": float("inf")}
            over_disabled = over_enabled = over_fleet = float("inf")
            for _ in range(max(1, trials)):
                obs.disable_tracing()
                obs.set_metrics_enabled(False)
                base, _ = _best(scan)
                obs.set_metrics_enabled(True)
                disabled, _ = _best(scan)
                obs.enable_tracing()
                enabled, _ = _best(scan)
                set_export_dir(spool_dir)
                fleet, _ = _best(scan_fleet)
                set_export_dir(None)
                obs.disable_tracing()
                best["base"] = min(best["base"], base)
                best["disabled"] = min(best["disabled"], disabled)
                best["enabled"] = min(best["enabled"], enabled)
                best["fleet"] = min(best["fleet"], fleet)
                over_disabled = min(over_disabled,
                                    max(0.0, disabled / base - 1))
                over_enabled = min(over_enabled,
                                   max(0.0, enabled / base - 1))
                over_fleet = min(over_fleet,
                                 max(0.0, fleet / base - 1))
        finally:
            set_export_dir(None)
            obs.set_metrics_enabled(was_metrics)
            (obs.enable_tracing if was_tracing
             else obs.disable_tracing)()
        _emit("obs_scan_noinstr", rows, best["base"])
        _emit("obs_scan_trace_disabled", rows, best["disabled"])
        _emit("obs_scan_trace_enabled", rows, best["enabled"])
        _emit("obs_scan_fleet", rows, best["fleet"])
        for name, pct in (("obs_overhead_disabled_pct", over_disabled),
                          ("obs_overhead_enabled_pct", over_enabled),
                          ("obs_overhead_fleet_pct", over_fleet)):
            print(json.dumps({"benchmark": name,
                              "value": round(pct * 100, 3),
                              "unit": "pct", "rows": rows,
                              "trials": trials}), flush=True)


def bench_serve():
    """Serving-plane trajectory in every micro run (full 64-client
    matrix in benchmarks/serve_bench.py; this entry keeps cold/warm
    point-get latency, the engine probe rate and a smaller mixed-load
    QPS in the micro record)."""
    from benchmarks.serve_bench import measure_serving
    measure_serving(rows=min(ROWS, 200_000), clients=16, seconds=2.0)


def bench_tier():
    """Tiered host-SSD storage trajectory (full 0/10/50ms matrix in
    benchmarks/tier_bench.py; this entry keeps the 10ms point — warm
    SSD re-scan vs cold, staged vs inline ingest — in the micro
    record)."""
    from benchmarks.tier_bench import measure
    measure(rows=min(ROWS, 100_000), ingest_rows=min(ROWS, 400_000),
            latencies=[0, 10])


def bench_plan():
    """Incremental metadata plane trajectory (full 10k/100k/1M matrix
    in benchmarks/plan_bench.py via bench.py's metadata_plane block;
    this entry keeps a 20k-file cold-vs-delta-applied plan comparison
    plus the bucket-prune legs in the micro record)."""
    from benchmarks.plan_bench import measure_plan
    files = min(max(ROWS // 50, 5_000), 20_000)
    r = measure_plan(scales=(files,), delta_reps=3)
    s = r["scales"][0]
    for name, value, unit in (
            ("plan_cold_ms", s["cold_plan_ms"], "ms"),
            ("plan_delta_ms", s["delta_plan_ms"], "ms"),
            ("plan_cold_vs_delta", s["cold_vs_delta"], "x"),
            ("plan_prune_speedup",
             round(s["prune_off_ms"] / max(s["prune_on_ms"], 1e-6), 2),
             "x")):
        print(json.dumps({"benchmark": name, "value": value,
                          "unit": unit, "files": s["files"],
                          "platform": _PLATFORM,
                          "device_kind": _DEVICE_KIND}), flush=True)


def bench_multihost():
    """Multi-host write-plane trajectory (full 1M-row matrix in
    benchmarks/multihost_bench.py via bench.py's multihost_write
    block; this entry keeps a smaller 1-proc vs 2-proc-gloo-mesh
    ingest comparison — rows asserted identical to the oracle — in
    the micro record)."""
    from benchmarks.multihost_bench import measure
    measure(rows=min(ROWS, 200_000))


def bench_fsck():
    """Incremental fsck trajectory (full 10k/100k/1M matrix in
    benchmarks/fsck_bench.py; this entry keeps a 20k-file
    full-vs-incremental verification comparison in the micro
    record)."""
    from benchmarks.fsck_bench import measure_fsck
    files = min(max(ROWS // 50, 5_000), 20_000)
    r = measure_fsck(scales=(files,))["scales"][0]
    for name, value, unit in (
            ("fsck_full_ms", r["full_fsck_ms"], "ms"),
            ("fsck_incremental_ms", r["inc_fsck_ms"], "ms"),
            ("fsck_inc_vs_full_pct", r["inc_vs_full_pct"], "%")):
        print(json.dumps({"benchmark": name, "value": value,
                          "unit": unit, "files": r["files"],
                          "platform": _PLATFORM,
                          "device_kind": _DEVICE_KIND}), flush=True)


BENCHES = {
    "read_parquet": lambda: bench_read("parquet"),
    "read_orc": lambda: bench_read("orc"),
    "read_avro": lambda: bench_read("avro"),
    "write": bench_write,
    "lookup": bench_lookup,
    "probe": bench_probe,
    "bitmap": bench_bitmap,
    "merge": bench_merge,
    "scan": bench_scan,
    "obs": bench_obs,
    "serve": bench_serve,
    "tier": bench_tier,
    "multihost": bench_multihost,
    "plan": bench_plan,
    "fsck": bench_fsck,
}


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
