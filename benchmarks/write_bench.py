"""Write/ingest benchmarks: serial vs pipelined bucket flushing.

Counterpart of `benchmarks/scan_bench.py` for the write path (ISSUE 4's
hot path): generates a fixed-seed batch stream, ingests it into a
primary-key table with 8 buckets — hash/group-by on the caller thread,
per-bucket sort + parquet encode + upload on the flush pool
(parallel/write_pipeline.py) — and times the whole
write()+prepare_commit()+commit() ingest with the pipelined executor
against the serial single-thread baseline (write.flush.parallelism=1,
Arrow pinned to one thread).  The two ingests must produce tables whose
full merge-on-read scans are row-identical; the benchmark asserts it.

Usage:
    python -m benchmarks.write_bench [name ...]   # default: all
Prints ONE JSON line per benchmark (same shape as micro.py), each
timed via micro's `_best` auto-scaling (>=10ms per timed batch).

Env: WRITE_ROWS (default MICRO_ROWS or 1_000_000), WRITE_POOL (default
8), WRITE_BUCKETS (default 8), WRITE_CHUNKS (default 16), MICRO_RUNS.
CPU-only like micro.py — bench.py owns the TPU.
"""

import os
import shutil
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402

from benchmarks.micro import _best, _emit  # noqa: E402
from benchmarks.scan_bench import _single_thread  # noqa: E402

ROWS = int(os.environ.get("WRITE_ROWS",
                          os.environ.get("MICRO_ROWS", "1000000")))
POOL = int(os.environ.get("WRITE_POOL", "8"))
BUCKETS = int(os.environ.get("WRITE_BUCKETS", "8"))
CHUNKS = int(os.environ.get("WRITE_CHUNKS", "16"))


def build_batches(rows: int, chunks: int = CHUNKS, seed: int = 7):
    """A fixed-seed batch stream (the ingest's input, built once so
    generation cost is outside the timed region)."""
    rng = np.random.default_rng(seed)
    per = rows // chunks
    out = []
    for _ in range(chunks):
        ids = rng.integers(0, rows // 2, per)
        out.append(pa.table({
            "id": pa.array(ids, pa.int64()),
            "v1": pa.array(rng.integers(0, 1 << 40, per), pa.int64()),
            "v2": pa.array(rng.random(per), pa.float64()),
            "v3": pa.array(rng.integers(0, 100, per).astype(np.int32),
                           pa.int32()),
        }))
    return out


def _schema(parallelism: int, buckets: int = BUCKETS,
            extra=None):
    from paimon_tpu.schema import Schema
    from paimon_tpu.types import BigIntType, DoubleType, IntType
    options = {"bucket": str(buckets), "write-only": "true",
               "parquet.enable.dictionary": "false",
               "write.flush.parallelism": str(parallelism),
               # ~8 flushes per bucket at the 1M default so the pool
               # actually pipelines instead of one flush per bucket
               "write-buffer-size": "8 mb"}
    options.update(extra or {})
    return (Schema.builder()
            .column("id", BigIntType(False))
            .column("v1", BigIntType())
            .column("v2", DoubleType())
            .column("v3", IntType())
            .primary_key("id")
            .options(options)
            .build())


def ingest(path: str, batches, parallelism: int, extra=None):
    """One full ingest: create + write every batch + commit + close.
    Returns the table (left on disk for the identity check)."""
    from paimon_tpu.table import FileStoreTable
    table = FileStoreTable.create(path, _schema(parallelism,
                                                extra=extra))
    wb = table.new_batch_write_builder()
    with wb.new_write() as w:
        for b in batches:
            w.write_arrow(b)
        wb.new_commit().commit(w.prepare_commit())
    return table


def measure_ingest(rows: int = ROWS, pool: int = POOL, emit=_emit,
                   extra=None, tag=""):
    """Serial-1T vs pipelined ingest + row-identity check.
    Returns (serial_s, pipelined_s)."""
    batches = build_batches(rows)
    with tempfile.TemporaryDirectory() as tmp:
        n = [0]

        def run(par):
            path = os.path.join(tmp, f"t{par}_{n[0]}")
            n[0] += 1
            ingest(path, batches, par, extra=extra)
            return path

        def timed(par):
            # the tmp dir cleanup rides inside the timed region for
            # BOTH sides equally (each repetition needs a fresh table)
            shutil.rmtree(run(par), ignore_errors=True)

        with _single_thread():
            s = _best(lambda: timed(1))
        p = _best(lambda: timed(pool))
        # identity: one ingest per side is kept and scanned
        from paimon_tpu.table import FileStoreTable
        serial_t = FileStoreTable.load(run(1))
        piped_t = FileStoreTable.load(run(pool))
        identical = serial_t.to_arrow().sort_by("id") \
            .equals(piped_t.to_arrow().sort_by("id"))
    s_sec = s[0] if isinstance(s, tuple) else s
    p_sec = p[0] if isinstance(p, tuple) else p
    emit(f"write_ingest_serial{tag}", rows, s)
    emit(f"write_ingest_pipelined{tag}", rows, p, pool=pool,
         vs_serial=round(s_sec / p_sec, 3), identical=bool(identical))
    if not identical:
        raise AssertionError("pipelined ingest diverged from serial")
    return s_sec, p_sec


def bench_ingest():
    measure_ingest()


def bench_ingest_spill():
    """The spillable buffer variant: sorted runs spill locally and
    merge into L0 at the prepare-commit barrier, all on the pool.  The
    spill threshold is sized so each bucket actually spills several
    runs at the configured scale (a threshold above the per-bucket
    volume would silently measure the plain path)."""
    measure_ingest(extra={"write-buffer-spillable": "true",
                          "sort-spill-buffer-size": "512 kb",
                          "write-buffer-size": "4 mb"},
                   tag="_spill")


BENCHES = {
    "ingest": bench_ingest,
    "ingest_spill": bench_ingest_spill,
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
