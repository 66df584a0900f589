"""Multichip dryrun: sharded write -> end-to-end mesh compaction ->
all_to_all bucket rescale, at >= 1M rows.

This is the library path the driver's `dryrun_multichip` exercises: a
real multi-bucket primary-key table is written through the normal
write/commit plane, then

1. `compact_table_mesh` (parallel/mesh_engine.py) runs EVERY bucket's
   full compaction in one streamed mesh program (skew-aware bucket ->
   lane packing, engine-dispatched [B, window] kernels) and commits
   the COMPACT snapshot;
2. `rescale_table_buckets` re-routes every row to 2x the buckets with
   the all_to_all dispatch collective and commits the overwrite;
3. the read-back after both is checked against the pre-compaction
   merge-on-read state.

`run_engines` is the round-6 multichip benchmark entry: deduplicate +
aggregation full compactions through the mesh engine at >= 10M rows,
rows/s recorded to MULTICHIP_r06.json by the slow pytest entry
(tests/test_mesh_engine.py::test_dryrun_multichip_engines).

Scale: DRYRUN_ROWS rows (default 1,000,000) so the dryrun proves
meaningful data volumes, not just compilation.
"""

from __future__ import annotations

import os
from typing import Optional


def run(n_devices: int) -> None:
    # A VIRTUAL mesh: n CPU devices, chosen by environment before jax is
    # imported (the caller must not have initialized a backend yet).
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import tempfile

    import numpy as np
    import pyarrow as pa

    from paimon_tpu.parallel import (
        bucket_mesh, compact_table_mesh, rescale_table_buckets,
    )
    from paimon_tpu.schema import Schema
    from paimon_tpu.table import FileStoreTable
    from paimon_tpu.types import BigIntType, DoubleType

    n_buckets = n_devices
    # write-path flush pre-merges duplicate keys, so size the keyspace
    # so that >= 1M rows survive into the sharded compaction itself
    total_rows = int(os.environ.get("DRYRUN_ROWS", "1300000"))

    with tempfile.TemporaryDirectory() as tmp:
        schema = (Schema.builder()
                  .column("id", BigIntType(False))
                  .column("v", DoubleType())
                  .primary_key("id")
                  .options({"bucket": str(n_buckets),
                            "write-only": "true"})
                  .build())
        table = FileStoreTable.create(os.path.join(tmp, "t"), schema)
        rng = np.random.default_rng(0)
        # two commits -> two overlapping L0 runs per bucket
        for _ in range(2):
            ids = rng.integers(0, total_rows, total_rows // 2)
            data = pa.table({
                "id": pa.array(ids, pa.int64()),
                "v": pa.array(rng.random(len(ids)), pa.float64()),
            })
            wb = table.new_batch_write_builder()
            with wb.new_write() as w:
                w.write_arrow(data)
                wb.new_commit().commit(w.prepare_commit())

        expected = table.to_arrow().num_rows   # merge-on-read truth
        n_input = sum(
            f.row_count for s in
            table.new_read_builder().new_scan().plan().splits
            for f in s.data_files)

        mesh = bucket_mesh(n_devices)
        stats = compact_table_mesh(table, mesh)
        assert stats.snapshot_id is not None
        assert stats.buckets == n_buckets, (stats.buckets, n_buckets)
        assert stats.output_rows == expected, (stats.output_rows,
                                               expected)
        assert table.latest_snapshot().commit_kind == "COMPACT"

        sid = rescale_table_buckets(table, 2 * n_buckets, mesh=mesh)
        assert sid is not None
        table2 = FileStoreTable.load(table.path)
        assert table2.options.bucket == 2 * n_buckets
        after = table2.to_arrow().num_rows
        assert after == expected, (after, expected)

        print(f"dryrun_multichip OK: {n_devices} devices, "
              f"{n_buckets}->{2 * n_buckets} buckets, "
              f"{n_input} input rows -> {expected} merged rows "
              f"(mesh-engine compact + all_to_all rescale on mesh)")


def run_engines(n_devices: int = 8, rows: int = 10_000_000,
                mesh=None, out_path: Optional[str] = None) -> dict:
    """Mesh-engine multichip benchmark: deduplicate + aggregation full
    compactions at `rows` input rows each, on an already-initialized
    CPU mesh backend (tests/conftest.py or run() set one up).  Returns
    (and optionally JSON-writes) per-engine rows/s plus the engine's
    window/packing observability counters."""
    import json
    import tempfile
    import time

    import numpy as np
    import pyarrow as pa

    from paimon_tpu.parallel import bucket_mesh, compact_table_mesh
    from paimon_tpu.schema import Schema
    from paimon_tpu.table import FileStoreTable
    from paimon_tpu.types import BigIntType, DoubleType

    if mesh is None:
        mesh = bucket_mesh(n_devices)
    # record the geometry actually measured, not the requested one
    n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    record = {"devices": n_dev, "requested_rows": rows,
              "backend": "cpu-mesh", "engines": {}}
    for engine in ("deduplicate", "aggregation"):
        opts = {"bucket": str(n_dev), "write-only": "true",
                "merge-engine": engine}
        if engine == "aggregation":
            opts["fields.v.aggregate-function"] = "sum"
        schema = (Schema.builder()
                  .column("id", BigIntType(False))
                  .column("v", DoubleType())
                  .primary_key("id")
                  .options(opts)
                  .build())
        with tempfile.TemporaryDirectory() as tmp:
            table = FileStoreTable.create(
                os.path.join(tmp, engine.replace("-", "_")), schema)
            rng = np.random.default_rng(6)

            def scanned_rows():
                return sum(
                    f.row_count for s in
                    table.new_read_builder().new_scan().plan().splits
                    for f in s.data_files)

            # two commits minimum (two overlapping L0 runs per bucket),
            # then keep committing until >= `rows` survive into the
            # compaction input: the write-path flush pre-merges
            # duplicate keys, so a fixed write count undershoots
            commits = 0
            while commits < 2 or scanned_rows() < rows:
                ids = rng.integers(0, rows, rows // 2)
                wb = table.new_batch_write_builder()
                with wb.new_write() as w:
                    w.write_arrow(pa.table({
                        "id": pa.array(ids, pa.int64()),
                        "v": pa.array(rng.random(len(ids)), pa.float64()),
                    }))
                    wb.new_commit().commit(w.prepare_commit())
                commits += 1
            t0 = time.perf_counter()
            stats = compact_table_mesh(table, mesh)
            dt = time.perf_counter() - t0
            assert stats.snapshot_id is not None
            after = table.to_arrow().num_rows
            assert stats.output_rows == after, (stats.output_rows, after)
            record["engines"][engine] = {
                "input_rows": stats.input_rows,
                "output_rows": stats.output_rows,
                "buckets": stats.buckets,
                "windows": stats.windows,
                "peak_window_rows": stats.peak_window_rows,
                "peak_buffered_rows": stats.peak_buffered_rows,
                "packing_skew": round(stats.skew, 4),
                "seconds": round(dt, 3),
                "rows_per_sec": round(stats.input_rows / dt, 1),
            }
            print(f"run_engines {engine}: {stats.input_rows} rows in "
                  f"{dt:.2f}s = {stats.input_rows / dt:,.0f} rows/s "
                  f"({stats.windows} windows, skew {stats.skew:.2f})")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
            f.write("\n")
    return record
