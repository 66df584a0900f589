"""Merge-on-read scan benchmarks: serial vs pipelined split reading.

Counterpart of `benchmarks/micro.py` for the scan path (the second of
the two BASELINE hot paths): builds a primary-key table with 8 buckets
x several overlapping L0 runs, then measures `to_arrow()` — download +
Arrow decode + device merge per split — with the pipelined executor
(parallel/scan_pipeline.py) against the serial single-thread baseline
(scan.split.parallelism=1, Arrow pinned to one thread), for the
deduplicate and aggregation merge engines.  Also records the
footer-cache re-scan effect (`read.cache.footer`): cold = footer cache
cleared before every scan, warm = second scan onward.

Usage:
    python -m benchmarks.scan_bench [name ...]   # default: all
Prints ONE JSON line per benchmark (same shape as micro.py), each
timed via micro's `_best` auto-scaling (>=10ms per timed batch).

Env: SCAN_ROWS (default MICRO_ROWS or 1_000_000), SCAN_POOL (default
8), MICRO_RUNS.  CPU-only like micro.py — bench.py owns the TPU.
"""

import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402

from benchmarks.micro import _best, _emit  # noqa: E402

ROWS = int(os.environ.get("SCAN_ROWS",
                          os.environ.get("MICRO_ROWS", "1000000")))
POOL = int(os.environ.get("SCAN_POOL", "8"))
BUCKETS = int(os.environ.get("SCAN_BUCKETS", "8"))
COMMITS = int(os.environ.get("SCAN_COMMITS", "5"))


def build_scan_table(path: str, engine: str, rows: int,
                     buckets: int = BUCKETS, commits: int = COMMITS):
    """Write-only pk table: every commit leaves an overlapping L0 run
    in each of `buckets` buckets, so the plan has `buckets` merge
    splits of `commits` sorted runs each."""
    from paimon_tpu.schema import Schema
    from paimon_tpu.table import FileStoreTable
    from paimon_tpu.types import BigIntType, DoubleType, IntType

    options = {"bucket": str(buckets), "write-only": "true",
               "merge-engine": engine,
               "parquet.enable.dictionary": "false"}
    if engine == "aggregation":
        options.update({"fields.v1.aggregate-function": "sum",
                        "fields.v2.aggregate-function": "max"})
    schema = (Schema.builder()
              .column("id", BigIntType(False))
              .column("v1", BigIntType())
              .column("v2", DoubleType())
              .column("v3", IntType())
              .primary_key("id")
              .options(options)
              .build())
    table = FileStoreTable.create(path, schema)
    rng = np.random.default_rng(7)
    per_run = rows // commits
    for _ in range(commits):
        ids = rng.integers(0, rows // 2, per_run)
        data = pa.table({
            "id": pa.array(ids, pa.int64()),
            "v1": pa.array(rng.integers(0, 1 << 40, per_run), pa.int64()),
            "v2": pa.array(rng.random(per_run), pa.float64()),
            "v3": pa.array(rng.integers(0, 100, per_run)
                           .astype(np.int32), pa.int32()),
        })
        wb = table.new_batch_write_builder()
        w = wb.new_write()
        w.write_arrow(data)
        wb.new_commit().commit(w.prepare_commit())
        w.close()
    return table


class _single_thread:
    """Pin Arrow's compute + IO pools to one thread — the honest
    serial denominator (same discipline as bench.py's vectorized-1T)."""

    def __enter__(self):
        pa.set_cpu_count(1)
        pa.set_io_thread_count(1)
        return self

    def __exit__(self, *exc):
        pa.set_cpu_count(os.cpu_count() or 4)
        pa.set_io_thread_count(os.cpu_count() or 4)
        return False


def measure_engine(table, engine: str, rows: int, pool: int = POOL,
                   emit=_emit):
    """Serial-1T vs pipelined scans of one table + row-identity check.
    Returns (serial_s, pipelined_s)."""
    serial = table.copy({"scan.split.parallelism": "1"})
    piped = table.copy({"scan.split.parallelism": str(pool)})
    tag = {"deduplicate": "dedup", "aggregation": "agg"}.get(engine,
                                                             engine)
    table.to_arrow()       # warm page + footer caches for both sides
    with _single_thread():
        s = _best(lambda: serial.to_arrow())
    p = _best(lambda: piped.to_arrow())
    identical = serial.to_arrow().sort_by("id") \
        .equals(piped.to_arrow().sort_by("id"))
    emit(f"merge_on_read_scan_serial_{tag}", rows, s)
    s_sec = s[0] if isinstance(s, tuple) else s
    p_sec = p[0] if isinstance(p, tuple) else p
    emit(f"merge_on_read_scan_pipelined_{tag}", rows, p,
         pool=pool, vs_serial=round(s_sec / p_sec, 3),
         identical=bool(identical))
    if not identical:
        raise AssertionError(
            f"pipelined scan diverged from serial ({engine})")
    return s_sec, p_sec


def bench_engine(engine: str):
    with tempfile.TemporaryDirectory() as tmp:
        table = build_scan_table(os.path.join(tmp, f"t_{engine}"),
                                 engine, ROWS)
        measure_engine(table, engine, ROWS)


def bench_footer_cache():
    """Footer-cache re-scan effect: cold clears the parsed-footer LRU
    before every scan, warm reuses it; the emitted line carries the
    speedup and the warm hit rate."""
    from paimon_tpu.fs.caching import global_footer_cache
    cache = global_footer_cache()
    with tempfile.TemporaryDirectory() as tmp:
        table = build_scan_table(os.path.join(tmp, "t_fc"),
                                 "deduplicate", ROWS)

        def cold():
            cache.clear()
            table.to_arrow()

        c = _best(cold)
        table.to_arrow()                       # warm the cache
        h0, m0 = cache.hits, cache.misses
        w = _best(lambda: table.to_arrow())
        hits, misses = cache.hits - h0, cache.misses - m0
        c_sec = c[0] if isinstance(c, tuple) else c
        w_sec = w[0] if isinstance(w, tuple) else w
        _emit("scan_footer_cache_rescan", ROWS, w,
              cold_seconds=round(c_sec, 6),
              speedup=round(c_sec / w_sec, 4),
              hit_rate=round(hits / max(1, hits + misses), 4))


BENCHES = {
    "scan_dedup": lambda: bench_engine("deduplicate"),
    "scan_agg": lambda: bench_engine("aggregation"),
    "footer_cache": bench_footer_cache,
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
