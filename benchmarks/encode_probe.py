"""Host probe of a rolled file's Parquet encode (format/parquet_stitch.py):
where the time is inside the `encode` span, and what a commit's gather
pays for the memory the encode before it gave back.

No chip and no rate: it places host work, on whatever host it runs on (a
fresh page costs several times more on the chip's host than here, so run
it there: `chiprun -- python3 -m benchmarks.encode_probe`).  The table
is `agg_ingest`'s flushed file, 7 columns by 2.98M rows, zstd, dictionary
off, 1Mi-row row groups.

Usage:
    python -m benchmarks.encode_probe            # the span, decomposed
    python -m benchmarks.encode_probe commits [serial|stitched] [n]
Prints ONE JSON line.

`commits` runs n commits' `take` (the flush's gather, 140 MB out) and
encode, each on a thread of its own with the gap a commit leaves, as a
new `TableWrite`'s flush pool gives them: Arrow's allocator hands the
gather recycled pages or fresh ones, and which depends on the encode
(PERF.md §6, PR 37; ROADMAP S0-alloc).
"""

import io
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402
import pyarrow.parquet as pq  # noqa: E402

from paimon_tpu.format import parquet_stitch as ps  # noqa: E402
from paimon_tpu.parallel.executors import spawn_thread  # noqa: E402

ROWS, FILE_ROWS, ROW_GROUP = 3_200_000, 2_980_000, 1 << 20
ARGS = dict(compression="zstd", compression_level=None,
            use_dictionary=False, write_statistics=True)


def flush_table(rows: int, sort: bool = True) -> pa.Table:
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 16_000_000, rows)
    if sort:
        keys.sort()
    return pa.table({
        "_KEY_id": keys, "_SEQUENCE_NUMBER": np.arange(rows),
        "_VALUE_KIND": np.zeros(rows, np.int8), "id": keys,
        "v1": rng.integers(0, 1000, rows), "v2": rng.random(rows),
        "v3": rng.integers(0, 100, rows).astype(np.int32)})


def serial(table: pa.Table, native: bool = False, **over) -> bytes:
    sink = pa.BufferOutputStream() if native else io.BytesIO()
    pq.write_table(table, sink, row_group_size=ROW_GROUP,
                   **{**ARGS, **over})
    return sink.getvalue()


def _best(fn, reps: int = 4):
    """(least, median) seconds of `reps` calls."""
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        out.append(time.perf_counter() - t0)
    return round(min(out), 4), round(sorted(out)[len(out) // 2], 4)


def decompose() -> dict:
    table = flush_table(FILE_ROWS)
    out = {"cores": os.cpu_count(), "pyarrow": pa.__version__,
           "serial": _best(lambda: serial(table)),
           "serial_native_sink": _best(lambda: serial(table, native=True)),
           "serial_no_compression":
               _best(lambda: serial(table, compression="none")),
           "serial_no_statistics":
               _best(lambda: serial(table, write_statistics=False))}
    for name in table.column_names:
        one = table.select([name])
        out["column_" + name] = _best(
            lambda: serial(one, native=True), 3)
    data, pieces = ps.encode_table(table, ROW_GROUP, ARGS)
    plan = ps.plan_pieces(table, ROW_GROUP)
    parts = ps._run_pieces(table, plan, ARGS)
    shell = ps._written(table.schema.empty_table(), None, ARGS)
    out.update(
        same_bytes=data == serial(table), pieces=pieces,
        file_mb=round(len(data) / 1e6, 1),
        stitched=_best(lambda: ps.encode_table(table, ROW_GROUP, ARGS), 6),
        pieces_only=_best(lambda: ps._run_pieces(table, plan, ARGS)),
        stitch_only=_best(lambda: ps._stitch(shell, plan, parts)))
    return out


def commits(mode: str, n: int, gap_s: float = 0.3) -> dict:
    table = flush_table(ROWS, sort=False)
    order = pa.array(np.argsort(table["_KEY_id"].to_numpy(), kind="stable"))
    encode = serial if mode == "serial" else \
        (lambda t: ps.encode_table(t, ROW_GROUP, ARGS)[0])
    gather, encoded = [], []

    def commit():
        planes = [np.ones(4 << 20, np.uint32) for _ in range(5)]
        time.sleep(gap_s / 2)
        t0 = time.perf_counter()
        merged = table.take(order)
        t1 = time.perf_counter()
        files = [encode(merged.slice(0, FILE_ROWS)),
                 encode(merged.slice(FILE_ROWS))]
        gather.append(round(t1 - t0, 3))
        encoded.append(round(time.perf_counter() - t1, 3))
        del merged, files, planes

    for _ in range(n):
        spawn_thread(commit, name="probe-flush").join()
        time.sleep(gap_s / 2)
    return {"mode": mode, "allocator": pa.default_memory_pool().backend_name,
            "gather_s": gather, "encode_s": encoded}


def main(argv) -> int:
    if argv[:1] == ["commits"]:
        out = commits(argv[1] if len(argv) > 1 else "stitched",
                      int(argv[2]) if len(argv) > 2 else 16)
    else:
        out = decompose()
    print("[encode_probe] " + json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
