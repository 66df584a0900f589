"""Generate the committed golden wire-format fixture.

Run ONCE (python -m tests.make_golden) to freeze a tiny warehouse —
snapshot JSON, schema JSON, manifest avro bytes, data files, deletion
vectors, Iceberg metadata — under tests/fixtures/golden_v1/.  The
fixture bytes are committed; tests/test_golden.py then asserts forever
that today's code still reads them and that re-serialization is stable,
so the on-disk format can never silently drift (role of reference
JavaPyE2ETest.java: cross-version/cross-impl read compatibility).
"""

import json
import os
import shutil

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "golden_v1")


def build(root: str) -> dict:
    """Create the fixture warehouse at `root`; returns expected
    contents for the sidecar JSON."""
    import pyarrow as pa

    from paimon_tpu import predicate as P
    from paimon_tpu.schema import Schema
    from paimon_tpu.table import FileStoreTable
    from paimon_tpu.types import (
        BigIntType, DoubleType, IntType, VarCharType,
    )

    path = os.path.join(root, "golden_pk")
    schema = (Schema.builder()
              .column("pt", IntType(False))
              .column("id", BigIntType(False))
              .column("name", VarCharType.string_type())
              .column("score", DoubleType())
              .partition_keys("pt")
              .primary_key("pt", "id")
              .options({"bucket": "2", "write-only": "true",
                        "file-index.bloom-filter.columns": "id",
                        "changelog-producer": "input"})
              .build())
    table = FileStoreTable.create(path, schema)

    def commit(rows, kinds=None):
        wb = table.new_batch_write_builder()
        w = wb.new_write()
        w.write_dicts(rows, row_kinds=kinds)
        sid = wb.new_commit().commit(w.prepare_commit())
        w.close()
        return sid

    commit([{"pt": p, "id": i, "name": f"n{p}-{i}",
             "score": p * 10.0 + i}
            for p in (0, 1) for i in range(5)])
    commit([{"pt": 0, "id": 2, "name": "updated", "score": -2.0}])
    from paimon_tpu.types import RowKind
    commit([{"pt": 1, "id": 4, "name": "x", "score": 0.0}],
           kinds=[RowKind.DELETE])
    table.compact(full=True)
    table.create_tag("golden-tag")
    table.sync_iceberg()

    expected_rows = sorted(table.to_arrow().to_pylist(),
                           key=lambda r: (r["pt"], r["id"]))

    # append table with row tracking + DVs for the append wire surface
    apath = os.path.join(root, "golden_append")
    aschema = (Schema.builder()
               .column("id", BigIntType(False))
               .column("v", DoubleType())
               .options({"bucket": "-1",
                         "row-tracking.enabled": "true"})
               .build())
    at = FileStoreTable.create(apath, aschema)
    wb = at.new_batch_write_builder()
    w = wb.new_write()
    w.write_dicts([{"id": i, "v": float(i)} for i in range(8)])
    wb.new_commit().commit(w.prepare_commit())
    w.close()
    at.delete_where(P.in_("id", [1, 6]))

    expected_append = sorted(at.to_arrow(with_row_ids=True).to_pylist(),
                             key=lambda r: r["id"])
    return {"pk_rows": expected_rows, "append_rows": expected_append}


def main():
    import tempfile

    if os.path.exists(FIXTURE):
        raise SystemExit(f"{FIXTURE} already exists; golden fixtures "
                         f"are append-only — create golden_v2 instead")
    with tempfile.TemporaryDirectory() as tmp:
        expected = build(tmp)
        os.makedirs(os.path.dirname(FIXTURE), exist_ok=True)
        shutil.copytree(tmp, FIXTURE)
    with open(os.path.join(FIXTURE, "expected.json"), "w") as f:
        json.dump(expected, f, indent=1, sort_keys=True)
    n = sum(len(fs) for _, _, fs in os.walk(FIXTURE))
    print(f"golden fixture written: {FIXTURE} ({n} files)")


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    main()
