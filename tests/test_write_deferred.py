"""The write route's selections: a batch of several (partition, bucket)
groups hands each bucket writer the key columns and kinds taken at its
rows, and leaves the value columns in the caller's batch until the
flush gathers them by the composed index.

Held to the route without selections: the same rows written pre-split
per bucket, each call one group (handed on whole, no take), leave data
files equal byte for byte, file names aside — under every merge engine,
several calls a commit (plain, chunked and sliced batches),
`changelog-producer=input`, a sequence field, buffers that flush and
buffers that spill mid-commit; read back against the store oracle's
model.  Then the bytes a selection accounts (`taken_nbytes` against
`table.take(idx).nbytes`), the batches the writer keeps alive (weak
references, a skewed layout), and a selection taken early: the
`write.take` span and the `write` / `deferred_gather_rows` counter
(tests/test_write_route.py has them for a routed batch).
"""

import gc
import os
import weakref

import numpy as np
import pyarrow as pa
import pytest

from paimon_tpu import obs
from paimon_tpu.core.bucket import bucket_of
from paimon_tpu.core.write import taken_nbytes
from paimon_tpu.metrics import (
    WRITE_DEFERRED_GATHER_ROWS, WRITE_ROUTE_ROWS, global_registry,
)
from paimon_tpu.obs.trace import metrics_enabled
from paimon_tpu.schema import Schema
from paimon_tpu.table import FileStoreTable
from paimon_tpu.types import (
    BigIntType, DoubleType, IntType, RowKind, VarCharType,
)
from tests.store_oracle import OracleModel, _rows_equal
from tests.test_string_keys import PREFIX, _fnv_keys


@pytest.fixture(autouse=True)
def _clean_tracer():
    was_tracing = obs.tracing_enabled()
    obs.collector().clear()
    yield
    (obs.enable_tracing if was_tracing else obs.disable_tracing)()
    obs.collector().clear()


def _write_counts():
    group = global_registry().write_metrics()
    return (group.counter(WRITE_DEFERRED_GATHER_ROWS).count,
            group.counter(WRITE_ROUTE_ROWS).count)


# -- the tables and their batches ---------------------------------------------

_ORACLE_ARROW = pa.schema([("pt", pa.int32()), ("id", pa.int64()),
                           ("v1", pa.int32()), ("v2", pa.float64()),
                           ("name", pa.string())])


def _oracle_table(path, engine, buckets, extra=None):
    """The store oracle's schema (tests/store_oracle.py), partitioned."""
    opts = {"bucket": str(buckets), "write-only": "true",
            "merge-engine": engine}
    if engine == "aggregation":
        opts["fields.v1.aggregate-function"] = "sum"
        opts["fields.v2.aggregate-function"] = "max"
    opts.update(extra or {})
    return FileStoreTable.create(
        str(path), Schema.builder()
        .column("pt", IntType(False)).column("id", BigIntType(False))
        .column("v1", IntType()).column("v2", DoubleType())
        .column("name", VarCharType.string_type())
        .partition_keys("pt").primary_key("pt", "id")
        .options(opts).build())


def _oracle_batch(rng, n, key_space=90):
    names = np.array(["a", "b", "c", "longer-value", "é" * 9], dtype=object)
    name = names[rng.integers(0, len(names), n)]
    name[rng.random(n) < 0.2] = None
    v1 = rng.integers(0, 1000, n)
    v2 = np.round(rng.uniform(0, 100, n), 6)
    return pa.table({
        "pt": pa.array(rng.integers(0, 2, n), pa.int32()),
        "id": pa.array(rng.integers(0, key_space, n), pa.int64()),
        "v1": pa.array(v1, pa.int32(), mask=rng.random(n) < 0.1),
        "v2": pa.array(v2, pa.float64(), mask=rng.random(n) < 0.1),
        "name": pa.array(name.tolist(), pa.string())},
        schema=_ORACLE_ARROW)


_YCSB_FIELDS = [f"field{i}" for i in range(4)]


def _ycsb_table(path, buckets, extra=None):
    string = VarCharType(VarCharType.MAX_LENGTH)
    builder = Schema.builder().column(
        "YCSB_KEY", VarCharType(VarCharType.MAX_LENGTH, False))
    for f in _YCSB_FIELDS:
        builder = builder.column(f, string)
    opts = {"bucket": str(buckets), "merge-engine": "partial-update",
            "write-only": "true"}
    opts.update(extra or {})
    return FileStoreTable.create(
        str(path), builder.primary_key("YCSB_KEY").options(opts).build())


def _ycsb_batch(rng, n):
    """YCSB's update: 18-23-byte keys (and keys that share the lanes'
    16-byte prefix), one field of 100 bytes set, the others null."""
    pool = _fnv_keys(np.arange(300)) + [PREFIX + s for s in
                                        ("", "0", "00", "1", "\x00")]
    keys = [pool[r] for r in np.minimum(rng.zipf(1.3, n) - 1,
                                        len(pool) - 1)]
    which = rng.integers(0, len(_YCSB_FIELDS), n)
    cols = {"YCSB_KEY": pa.array(keys, pa.string())}
    for j, f in enumerate(_YCSB_FIELDS):
        values = [chr(33 + int(v) % 90) * 100 if w == j else None
                  for v, w in zip(rng.integers(0, 1 << 30, n), which)]
        cols[f] = pa.array(values, pa.string())
    return pa.table(cols)


def _calls(make, rng, engine):
    """One commit's write_arrow calls: a plain batch, a chunked one and
    a slice of a larger one; kinds with deletes where the engine keeps
    them apart."""
    plain = make(rng, 70)
    chunked = pa.concat_tables([make(rng, 33), make(rng, 41)])
    sliced = make(rng, 120).slice(17, 80)
    out = []
    for batch in (plain, chunked, sliced):
        kinds = np.zeros(batch.num_rows, dtype=np.int8)
        if engine == "deduplicate":
            kinds[rng.random(batch.num_rows) < 0.15] = RowKind.DELETE
        out.append((batch, kinds))
    return out


# -- pre-split per bucket -----------------------------------------------------

def _buckets(table, batch):
    rt = table.schema.logical_row_type()
    keys = table.schema.bucket_keys()
    types = [rt.get_field(k).type for k in keys]
    cols = [batch.column(k).to_pylist() for k in keys]
    return np.array([bucket_of(list(row), types, table.options.bucket)
                     for row in zip(*cols)], dtype=np.int32)


def _commit(table, calls, pre_split):
    wb = table.new_batch_write_builder()
    with wb.new_write() as w:
        for batch, kinds in calls:
            if not pre_split:
                w.write_arrow(batch, row_kinds=kinds)
                continue
            buckets = _buckets(table, batch)
            parts = table.schema.partition_keys
            groups = {}
            for i, b in enumerate(buckets.tolist()):
                part = tuple(batch.column(k)[i].as_py() for k in parts)
                groups.setdefault((b, part), []).append(i)
            for key in sorted(groups):
                idx = np.array(groups[key])
                w.write_arrow(batch.take(pa.array(idx)),
                              row_kinds=kinds[idx])
        wb.new_commit().commit(w.prepare_commit())


def _data_files(path):
    """{bucket directory: sorted bytes of its data and changelog files}."""
    out = {}
    for root, _, files in os.walk(path):
        if "bucket-" not in os.path.basename(root):
            continue
        out[os.path.relpath(root, path)] = sorted(
            open(os.path.join(root, f), "rb").read() for f in files)
    return out


def _oracle_rows(model, calls_by_commit):
    for calls in calls_by_commit:
        for batch, kinds in calls:
            for row, kind in zip(batch.to_pylist(), kinds.tolist()):
                model.apply((row["pt"], row["id"]),
                            {f: row[f] for f in ("v1", "v2", "name")},
                            kind)
    return model.rows()


CASES = {
    "deduplicate": ("deduplicate", {}),
    "partial-update": ("partial-update", {}),
    "aggregation": ("aggregation", {}),
    "first-row": ("first-row", {}),
    "changelog-input": ("deduplicate", {"changelog-producer": "input"}),
    "sequence-field": ("deduplicate", {"sequence.field": "v1"}),
    "flush-mid-commit": ("partial-update", {"write-buffer-size": "6kb"}),
    "spill": ("deduplicate", {"write-buffer-spillable": "true",
                              "sort-spill-buffer-size": "5kb"}),
    "serial": ("aggregation", {"write.flush.parallelism": "1"}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_routed_commit_writes_the_files_of_pre_split_batches(
        tmp_path, case):
    engine, extra = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case) + 40)
    commits = [_calls(_oracle_batch, rng, engine) for _ in range(2)]
    routed = _oracle_table(tmp_path / "routed", engine, 4, extra)
    split = _oracle_table(tmp_path / "split", engine, 4, extra)
    before = _write_counts()
    for calls in commits:
        _commit(routed, calls, pre_split=False)
    deferred = _write_counts()[0] - before[0]
    for calls in commits:
        _commit(split, calls, pre_split=True)
    got = _data_files(routed.path)
    assert got == _data_files(split.path)
    assert sum(len(v) for v in got.values()) >= 4
    if metrics_enabled():
        assert deferred > 0
    actual = sorted(routed.to_arrow().to_pylist(),
                    key=lambda r: (r["pt"], r["id"]))
    if case == "sequence-field":
        # the model knows no sequence field: the twin's read is the check
        assert actual == sorted(split.to_arrow().to_pylist(),
                                key=lambda r: (r["pt"], r["id"]))
    else:
        assert _rows_equal(actual, _oracle_rows(OracleModel(engine),
                                                commits)) is None


def test_a_ycsb_shaped_commit_writes_the_files_of_pre_split_batches(
        tmp_path):
    rng = np.random.default_rng(39)
    commits = [_calls(_ycsb_batch, rng, "partial-update")
               for _ in range(2)]
    routed = _ycsb_table(tmp_path / "routed", 4)
    split = _ycsb_table(tmp_path / "split", 4)
    for calls in commits:
        _commit(routed, calls, pre_split=False)
        _commit(split, calls, pre_split=True)
    assert _data_files(routed.path) == _data_files(split.path)
    want = {}
    for calls in commits:
        for batch, _ in calls:
            for row in batch.to_pylist():
                state = want.setdefault(row["YCSB_KEY"], {})
                state.update({f: v for f, v in row.items()
                              if f != "YCSB_KEY" and v is not None})
    got = routed.to_arrow().sort_by("YCSB_KEY").to_pylist()
    assert [r["YCSB_KEY"] for r in got] == sorted(want, key=str.encode)
    for r in got:
        assert {f: r[f] for f in _YCSB_FIELDS} == {
            f: want[r["YCSB_KEY"]].get(f) for f in _YCSB_FIELDS}


# -- the bytes a selection accounts -------------------------------------------

def _with_null_bytes():
    """A string column whose null slots hold bytes (offsets advance
    under them), as buffers built by hand can have."""
    valid = np.packbits(np.array([1, 0, 1, 1, 0, 0, 1, 0, 1, 1, 0, 1],
                                 dtype=bool), bitorder="little")
    offsets = np.array([0, 2, 5, 7, 7, 9, 10, 13, 14, 16, 17, 20, 22],
                       dtype=np.int32)
    return pa.Array.from_buffers(
        pa.string(), 12, [pa.py_buffer(valid), pa.py_buffer(offsets),
                          pa.py_buffer(b"abcdefghijklmnopqrstuv")])


def _columns(n, rng):
    import datetime
    import decimal
    mask = rng.random(n) < 0.3
    words = [None if m else "w" * int(k) for m, k in
             zip(mask, rng.integers(0, 30, n))]
    return {
        "s": pa.array(words, pa.string()),
        "s_full": pa.array(["x" * int(k) for k in rng.integers(0, 9, n)],
                           pa.string()),
        "b": pa.array([None if w is None else w.encode() for w in words],
                      pa.binary()),
        "ls": pa.array(words, pa.large_string()),
        "lb": pa.array([w and w.encode() for w in words], pa.large_binary()),
        "i64": pa.array(rng.integers(0, 1 << 40, n), pa.int64()),
        "i32n": pa.array(rng.integers(0, 99, n), pa.int32(), mask=mask),
        "f": pa.array(rng.random(n)),
        "flag": pa.array(rng.random(n) < 0.5),
        "flagn": pa.array(rng.random(n) < 0.5, mask=mask),
        "dec": pa.array([decimal.Decimal(int(v)) / 100
                         for v in rng.integers(0, 10**6, n)],
                        pa.decimal128(12, 2)),
        "day": pa.array([datetime.date(2020, 1, 1)] * n, pa.date32()),
        "ts": pa.array(rng.integers(0, 1 << 50, n), pa.timestamp("us")),
        "fsb": pa.array([b"abcd"] * n, pa.binary(4)),
    }


def _selections(n, rng, k=5):
    codes = rng.integers(0, k, n)
    return [np.flatnonzero(codes == c) for c in range(k)
            if (codes == c).any()]


@pytest.mark.parametrize("shape", ["plain", "sliced", "chunked",
                                   "chunked-and-sliced"])
def test_a_selections_bytes_are_those_its_take_would_hold(shape):
    rng = np.random.default_rng(len(shape))
    n = 203
    if shape == "plain":
        table = pa.table(_columns(n, rng))
    elif shape == "sliced":
        table = pa.table(_columns(n + 20, rng)).slice(13, n)
    else:
        table = pa.concat_tables([pa.table(_columns(m, rng))
                                  for m in (61, 0, 45, 97)])
        if shape == "chunked-and-sliced":
            table = table.slice(11, 170)
    sel = _selections(table.num_rows, rng)
    got = taken_nbytes(table, sel)
    assert got == [table.take(pa.array(idx)).nbytes for idx in sel]


@pytest.mark.parametrize("offset", [0, 3, 8])
def test_null_slots_that_hold_bytes_are_not_counted(offset):
    arr = _with_null_bytes()
    col = arr.slice(offset)
    table = pa.table({"s": col})
    sel = [np.array([0, 1, 3]), np.arange(len(col)), np.array([2])]
    sel = [i[i < len(col)] for i in sel]
    assert taken_nbytes(table, sel) == \
        [table.take(pa.array(i)).nbytes for i in sel]
    chunked = pa.table({"s": pa.chunked_array([col, arr])})
    sel = [np.array([0, len(col), len(col) + 1, len(col) + 4])]
    assert taken_nbytes(chunked, sel) == [chunked.take(pa.array(sel[0]))
                                          .nbytes]


def test_a_type_whose_bytes_are_not_read_off_is_taken_whole(tmp_path):
    """A nested column: the route takes each group whole, as before;
    no flush gathers from the caller's batch, the rows read back."""
    assert taken_nbytes(pa.table({"l": pa.array([[1, 2], None, [3]])}),
                        [np.array([0, 2])]) is None
    from paimon_tpu.types import ArrayType
    table = FileStoreTable.create(
        str(tmp_path / "t"), Schema.builder()
        .column("id", BigIntType(False))
        .column("l", ArrayType(BigIntType()))
        .primary_key("id").options({"bucket": "4",
                                    "write-only": "true"}).build())
    n = 200
    batch = pa.table({
        "id": pa.array(np.arange(n), pa.int64()),
        "l": pa.array([None if i % 7 == 0 else list(range(i % 5))
                       for i in range(n)], pa.list_(pa.int64()))})
    obs.enable_tracing(max_spans=10_000)
    before = _write_counts()
    wb = table.new_batch_write_builder()
    with wb.new_write() as w:
        w.write_arrow(batch)
        wb.new_commit().commit(w.prepare_commit())
    routes = [s for s in obs.take_spans() if s.name == "write.route"]
    assert [s.attrs["copied_rows"] for s in routes] == [n]
    if metrics_enabled():
        assert tuple(a - b for a, b in zip(_write_counts(), before)) \
            == (0, n)
    assert table.to_arrow().sort_by("id").to_pylist() == batch.to_pylist()


# -- the batches the writer keeps ---------------------------------------------

def test_pinned_batches_stay_within_the_buffers_bytes_plus_one(tmp_path):
    """One bucket gets most rows of every batch and flushes often; the
    other a few rows of each batch, and would pin every batch.  After
    each batch the batches alive hold at most the buffers' bytes plus
    the newest batch's; selections past that were taken whole."""
    table = _oracle_table(tmp_path / "t", "deduplicate", 2, {
        "write-buffer-size": "40kb", "write.flush.parallelism": "1"})
    rt = table.schema.logical_row_type()
    types = [rt.get_field(k).type for k in table.schema.bucket_keys()]
    ids = np.arange(4000)
    in_one = np.array([bucket_of([int(i)], types, 2) for i in ids]) == 1
    hot, cold = ids[~in_one], ids[in_one]
    rng = np.random.default_rng(5)
    model = OracleModel("deduplicate")
    wb = table.new_batch_write_builder()
    refs = []
    with wb.new_write() as w:
        store = w._write
        for step in range(30):
            n = 600
            keys = rng.choice(hot, n)
            keys[:3] = rng.choice(cold, 3)
            batch = _oracle_batch(rng, n).set_column(
                0, "pt", pa.array(np.zeros(n), pa.int32())).set_column(
                1, "id", pa.array(keys, pa.int64()))
            for row in batch.to_pylist():
                model.apply((0, row["id"]), row, RowKind.INSERT)
            refs.append((weakref.ref(batch), batch.nbytes))
            w.write_arrow(batch)
            newest = batch.nbytes
            del batch
            gc.collect()
            alive = sum(nbytes for ref, nbytes in refs if ref() is not None)
            buffered = sum(wr.buffered_bytes
                           for wr in store._writers.values())
            assert alive == store._pinned_bytes, step
            assert alive <= buffered + newest, step
        # each batch left rows in the cold bucket's buffer: without the
        # bound, all thirty would be alive here
        assert sum(ref() is not None for ref, _ in refs) <= 5
        wb.new_commit().commit(w.prepare_commit())
        assert store._pins == {} and store._pinned_bytes == 0
    actual = sorted(table.to_arrow().to_pylist(),
                    key=lambda r: (r["pt"], r["id"]))
    assert _rows_equal(actual, model.rows()) is None


# -- a selection taken early --------------------------------------------------

def _pk_table(path, buckets):
    return FileStoreTable.create(
        str(path), Schema.builder()
        .column("id", BigIntType(False)).column("v", DoubleType())
        .column("s", VarCharType.string_type())
        .primary_key("id").options({"bucket": str(buckets),
                                    "write-only": "true"}).build())


def test_a_selection_taken_early_lowers_the_deferred_share(tmp_path):
    """A delta listener reads each batch's rows as it is buffered: every
    selection is taken there, under `write.take`, and no flush gathers
    from the caller's batch."""
    table = _pk_table(tmp_path / "t", 8)
    n = 300
    batch = pa.table({"id": pa.array(np.arange(n), pa.int64()),
                      "v": pa.array(np.arange(n) * 0.5),
                      "s": pa.array([f"r{i}" for i in range(n)])})
    seen = []
    obs.enable_tracing(max_spans=10_000)
    before = _write_counts()
    wb = table.new_batch_write_builder()
    with wb.new_write() as w:
        w.set_delta_listener(
            lambda part, bucket, rows, kinds, seqs: seen.append(rows))
        w.write_arrow(batch)
        wb.new_commit().commit(w.prepare_commit())
    takes = [s for s in obs.take_spans() if s.name == "write.take"]
    assert len(takes) == 1 + len(seen) and len(seen) == 8
    assert sum(t.num_rows for t in seen) == n
    assert pa.concat_tables(seen).sort_by("id").equals(batch)
    if metrics_enabled():
        deferred, routed = (a - b for a, b in zip(_write_counts(), before))
        assert (deferred, routed) == (0, n)
    assert table.to_arrow().sort_by("id").to_pylist() == batch.to_pylist()
