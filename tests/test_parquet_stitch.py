"""A rolled file's Parquet encode in pieces (format/parquet_stitch.py):
whatever the cut, the file is the serial writer's, byte for byte."""

import io
import json
import os
import threading

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from paimon_tpu import obs
from paimon_tpu.format import parquet_stitch, thrift
from paimon_tpu.format.format import get_format
from paimon_tpu.fs.fileio import MemoryFileIO
from paimon_tpu.metrics import (
    IO_ENCODE_ROWS, IO_ENCODE_SPLIT_ROWS, global_registry,
)
from paimon_tpu.parallel.executors import new_thread_pool, spawn_thread
from paimon_tpu.types import data_type_to_arrow, parse_data_type

_CONFIGS = os.path.join(os.path.dirname(__file__), os.pardir,
                        "chipbench", "configs")
_SHAPES = {"agg": "fullcompact-agg", "dedup": "mor50m-dedup",
           "wide64": "partial-update-wide64",
           "lineitem": "tpch-lineitem-pk"}
_LIMIT_S = 60


@pytest.fixture
def fine_pieces(monkeypatch):
    """Pieces of 16 KiB: the small tables here split as the benchmark's
    files do, narrow columns in runs and wide ones alone."""
    monkeypatch.setattr(parquet_stitch, "_PIECE_BYTES", 16 << 10)


def _column(kind: pa.DataType, rows: int, rng, p_null: float):
    if pa.types.is_integer(kind):
        values = pa.array(rng.integers(0, 1000, rows), pa.int64()) \
            .cast(kind)
    elif pa.types.is_floating(kind):
        values = pa.array(rng.random(rows), kind)
    elif pa.types.is_decimal(kind):
        values = pa.array(rng.integers(0, 10 ** 7, rows)) \
            .cast(pa.decimal128(38, 0)).cast(kind, safe=False)
    elif pa.types.is_date(kind):
        values = pa.array(rng.integers(8000, 11000, rows, dtype=np.int32)
                          ).cast(kind)
    elif pa.types.is_string(kind):
        words = np.array(["DELIVER IN PERSON", "A", "N", "TRUCK", "final",
                          "carefully ironic requests", ""])
        values = pa.array(words[rng.integers(0, len(words), rows)], kind)
    else:
        raise AssertionError(f"no test values for {kind}")
    if not p_null:
        return values
    return pa.array(values.to_pylist(), kind,
                    mask=rng.random(rows) < p_null)


def shape_table(shape: str, rows: int, seed: int = 7) -> pa.Table:
    """`rows` KV-shaped rows of a benchmark configuration's table: the
    key columns, sequence and kind, then every column (a nullable one a
    quarter null, the wide table's three quarters)."""
    with open(os.path.join(_CONFIGS, _SHAPES[shape] + ".json")) as f:
        cfg = json.load(f)["table"]
    rng = np.random.default_rng(seed)
    p_null = 0.75 if shape == "wide64" else 0.25
    cols = {}
    for name, sql in cfg["columns"]:
        kind = data_type_to_arrow(parse_data_type(sql))
        cols[name] = _column(kind, rows, rng,
                             0.0 if "NOT NULL" in sql else p_null)
    first = cfg["primary_key"][0]
    cols[first] = pa.array(np.sort(rng.integers(0, 1 << 40, rows)),
                           cols[first].type)
    kv = {"_KEY_" + k: cols[k] for k in cfg["primary_key"]}
    kv["_SEQUENCE_NUMBER"] = pa.array(np.arange(rows), pa.int64())
    kv["_VALUE_KIND"] = pa.array(rng.integers(0, 4, rows), pa.int8())
    kv.update(cols)
    return pa.table(kv)


def serial_bytes(writer, table: pa.Table, row_group_rows: int) -> bytes:
    """What `pq.write_table` leaves in one sink, given the writer's
    arguments."""
    sink = io.BytesIO()
    pq.write_table(table, sink, compression=writer.compression,
                   compression_level=writer.level,
                   row_group_size=row_group_rows,
                   use_dictionary=writer.use_dictionary,
                   write_statistics=True)
    return sink.getvalue()


def _counts():
    group = global_registry().group("io")
    return (group.counter(IO_ENCODE_ROWS).count,
            group.counter(IO_ENCODE_SPLIT_ROWS).count)


def written(writer, table: pa.Table, path: str = "f.parquet"):
    """(the file `writer.write` uploads, rows it counted, rows it
    counted as split)."""
    fio = MemoryFileIO()
    before = _counts()
    size = writer.write(fio, path, table)
    after = _counts()
    data = fio.read_bytes(path)
    assert size == len(data)
    return data, after[0] - before[0], after[1] - before[1]


def _writer(compression="zstd", dictionary=True, rows=None, block=None):
    options = {"parquet.enable.dictionary": str(dictionary).lower()}
    if rows is not None:
        options["parquet.row-group.rows"] = str(rows)
    if block is not None:
        options["file.block-size"] = str(block)
    return get_format("parquet").create_writer(compression,
                                               format_options=options)


@pytest.mark.parametrize("dictionary", [True, False],
                         ids=["dict", "plain"])
@pytest.mark.parametrize("compression", ["zstd", "zstd:3", "none", "lz4"])
@pytest.mark.parametrize("shape", sorted(_SHAPES))
def test_stitched_file_is_the_serial_file(fine_pieces, shape, compression,
                                          dictionary):
    table = shape_table(shape, 20_000)
    writer = _writer(compression, dictionary, rows=8192)
    data, rows, split = written(writer, table)
    assert (rows, split) == (20_000, 20_000)
    assert data == serial_bytes(writer, table, 8192)
    assert pq.read_table(io.BytesIO(data)).equals(table)


def _overflowing_dictionary(rows):
    # 40,000 distinct 40-byte strings: past the 1-MiB dictionary page,
    # so the chunk ends in plain pages
    return pa.table({
        "k": pa.array(np.arange(rows), pa.int64()),
        "s": pa.array([f"{i % 40_000:040d}" for i in range(rows)]),
        "t": pa.array([f"{i % 7}" for i in range(rows)])})


def _null_extremes(rows):
    return pa.table({
        "k": pa.array(np.arange(rows), pa.int64()),
        "all_null": pa.nulls(rows, pa.float64()),
        "no_null": pa.array(np.arange(rows) * 0.5),
        "null_strings": pa.nulls(rows, pa.string()),
        "flag": pa.array(np.arange(rows) % 3 == 0)})


def _chunked(rows):
    # what the compaction path hands the writer: chunks of uneven length
    # whose boundaries fall inside the row groups
    whole = shape_table("dedup", rows)
    cuts = [0, 1000, 9000, 9001, 16_500, rows]
    return pa.concat_tables([whole.slice(a, b - a)
                             for a, b in zip(cuts, cuts[1:])])


def _sliced(rows):
    return shape_table("lineitem", rows + 777).slice(777)


_EDGES = {
    # name: (table, writer arguments, row groups expected)
    "block_size": (lambda: shape_table("agg", 20_000),
                   dict(block=256 << 10), None),
    "ragged_tail": (lambda: shape_table("agg", 20_001), dict(rows=5000), 5),
    "one_row_group": (lambda: shape_table("agg", 8192), dict(rows=8192), 1),
    "one_row": (lambda: shape_table("dedup", 1), dict(rows=8192), 1),
    "no_rows": (lambda: shape_table("dedup", 0), dict(rows=8192), 1),
    "dictionary_overflow": (lambda: _overflowing_dictionary(100_000),
                            dict(rows=1 << 20), 1),
    "null_extremes": (lambda: _null_extremes(20_000), dict(rows=8192), 3),
    "chunked_inside_row_groups": (lambda: _chunked(20_000),
                                  dict(rows=8192), 3),
    "sliced_offset": (lambda: _sliced(20_000), dict(rows=8192), 3),
}


@pytest.mark.parametrize("dictionary", [True, False],
                         ids=["dict", "plain"])
@pytest.mark.parametrize("edge", sorted(_EDGES))
def test_stitched_edges(monkeypatch, edge, dictionary):
    # one column a piece, whatever it holds
    monkeypatch.setattr(parquet_stitch, "_PIECE_BYTES", 0)
    build, args, groups = _EDGES[edge]
    table = build()
    writer = _writer("zstd", dictionary, **args)
    rg = writer.row_group_rows
    if writer.block_bytes:
        rg = max(1024, writer.block_bytes
                 // (table.nbytes // table.num_rows))
    data, rows, split = written(writer, table)
    assert data == serial_bytes(writer, table, rg)
    assert rows == table.num_rows
    assert split == table.num_rows
    meta = pq.ParquetFile(io.BytesIO(data)).metadata
    if groups is not None:
        assert meta.num_row_groups == groups
    assert meta.num_rows == table.num_rows


def test_default_floor_keeps_small_files_on_the_serial_call():
    table = shape_table("agg", 20_000)
    writer = _writer(rows=8192)
    assert parquet_stitch.plan_pieces(table, 8192) \
        == [(0, 20_000, 0, table.num_columns)]
    data, rows, split = written(writer, table)
    assert (rows, split) == (20_000, 0)
    assert data == serial_bytes(writer, table, 8192)


def test_plan_follows_rows_row_groups_and_columns(monkeypatch):
    table = shape_table("agg", 20_000)
    # a row group holds 8 B x 8192 of an int64 column: pieces of that
    monkeypatch.setattr(parquet_stitch, "_PIECE_BYTES", 8 * 20_000 // 3)
    pieces = parquet_stitch.plan_pieces(table, 8192)
    runs = sorted({(a, b) for _, _, a, b in pieces})
    # the one-byte kind joins the column after it, the 4-byte tail the
    # run before it; every column is in exactly one run
    assert [a for a, _ in runs] + [table.num_columns] \
        == [0] + [b for _, b in runs]
    assert any(b - a > 1 for a, b in runs) and len(runs) > 1
    assert [(r, n) for r, n, a, _ in pieces if a == 0] \
        == [(0, 8192), (8192, 8192), (16384, 20_000 - 16384)]


@pytest.mark.parametrize("kind", ["list", "struct", "map"])
def test_nested_column_takes_the_serial_call(monkeypatch, kind):
    monkeypatch.setattr(parquet_stitch, "_PIECE_BYTES", 0)
    n = 5000
    nested = {
        "list": pa.array([[i, i + 1] for i in range(n)]),
        "struct": pa.array([{"a": i, "b": str(i)} for i in range(n)]),
        "map": pa.array([[(str(i), i)] for i in range(n)],
                        pa.map_(pa.string(), pa.int64())),
    }[kind]
    table = pa.table({"k": pa.array(np.arange(n)), "n": nested})
    writer = _writer(rows=1024)
    data, rows, split = written(writer, table)
    assert (rows, split) == (n, 0)
    assert data == serial_bytes(writer, table, 1024)


@pytest.mark.parametrize("fmt", ["orc", "avro", "csv", "json"])
def test_other_formats_are_not_counted(monkeypatch, fmt):
    monkeypatch.setattr(parquet_stitch, "_PIECE_BYTES", 0)
    table = pa.table({"k": pa.array(np.arange(3000)),
                      "v": pa.array(np.arange(3000) * 1.5)})
    writer = get_format(fmt).create_writer(
        "none" if fmt in ("csv", "json") else "zstd")
    _, rows, split = written(writer, table, "f." + fmt)
    assert (rows, split) == (0, 0)


# -- the pool -----------------------------------------------------------------

def _within_limit(fn):
    """Run `fn` on a thread of its own; fail if it has not returned
    within the limit."""
    box = {}

    def body():
        try:
            box["value"] = fn()
        except BaseException as e:      # handed to the test's thread
            box["error"] = e
    t = spawn_thread(body, name="test-stitch")
    t.join(_LIMIT_S)
    assert not t.is_alive(), "the writer hung"
    if "error" in box:
        raise box["error"]
    return box["value"]


@pytest.fixture
def small_pool(monkeypatch):
    """An encode pool of one worker in place of the process's."""
    pool = new_thread_pool(1, "test-encode")
    monkeypatch.setattr(parquet_stitch, "_pool", (pool, 1))
    yield pool
    pool.shutdown(wait=True)


def test_eight_writers_at_once_write_what_they_write_in_turn(fine_pieces):
    writer = _writer("zstd", False, rows=8192)
    tables = [shape_table("dedup", 20_000 + 500 * i, seed=i)
              for i in range(8)]
    in_turn = [written(writer, t)[0] for t in tables]
    fio = MemoryFileIO()
    start = threading.Barrier(8)

    def one(i):
        start.wait(_LIMIT_S)
        writer.write(fio, f"f{i}.parquet", tables[i])

    threads = [spawn_thread(one, name=f"test-writer-{i}", args=(i,))
               for i in range(8)]
    for t in threads:
        t.join(_LIMIT_S)
    assert not any(t.is_alive() for t in threads)
    assert [fio.read_bytes(f"f{i}.parquet") for i in range(8)] == in_turn
    assert in_turn[0] == serial_bytes(writer, tables[0], 8192)


def test_pool_of_one_worker_does_not_hang(fine_pieces, small_pool):
    table = shape_table("agg", 20_000)
    writer = _writer(rows=8192)
    data, _, split = _within_limit(lambda: written(writer, table))
    assert split == 20_000
    assert data == serial_bytes(writer, table, 8192)


def test_write_from_a_pool_thread_does_not_hang(fine_pieces, small_pool):
    # the pool's only worker is the writer: it must encode every piece
    # itself and wait for no helper
    table = shape_table("agg", 20_000)
    writer = _writer(rows=8192)
    fut = small_pool.submit(written, writer, table)
    data, _, split = _within_limit(lambda: fut.result(timeout=_LIMIT_S))
    assert split == 20_000
    assert data == serial_bytes(writer, table, 8192)


@pytest.mark.parametrize("failing", [0, 3, -1],
                         ids=["first", "middle", "last"])
def test_failing_piece_surfaces_and_uploads_nothing(fine_pieces,
                                                    monkeypatch, failing):
    table = shape_table("agg", 20_000)
    pieces = parquet_stitch.plan_pieces(table, 8192)
    bad = pieces[failing]
    encode_piece = parquet_stitch._encode_piece

    def flaky(tbl, piece, args):
        if piece == bad:
            raise OSError("the codec ran out of memory")
        return encode_piece(tbl, piece, args)

    monkeypatch.setattr(parquet_stitch, "_encode_piece", flaky)
    fio = MemoryFileIO()
    with pytest.raises(OSError, match="out of memory"):
        _within_limit(lambda: _writer(rows=8192).write(
            fio, "f.parquet", table))
    assert not fio.exists("f.parquet")


def test_encode_span_names_the_pieces(fine_pieces):
    was = obs.tracing_enabled()
    obs.enable_tracing()
    try:
        obs.collector().clear()
        table = shape_table("agg", 20_000)
        pieces = parquet_stitch.plan_pieces(table, 8192)
        written(_writer(rows=8192), table)
        written(_writer(rows=8192), table.slice(0, 1))
        spans = obs.take_spans(clear=True)
    finally:
        if not was:
            obs.disable_tracing()
    encodes = [s for s in spans if s.name == "encode"]
    assert [s.attrs["pieces"] for s in encodes] == [len(pieces), 1]
    assert [s.attrs["rows"] for s in encodes] == [20_000, 1]
    helped = [s for s in spans if s.name == "encode.piece"]
    # a piece has a span only on a pool thread, never on the writer's:
    # `encode` stays a leaf there
    assert len(helped) < len(pieces)
    assert all(s.thread.startswith("paimon-encode") for s in helped)
    assert all(s.parent_id == encodes[0].span_id for s in helped)


# -- the Thrift reader / writer -----------------------------------------------

@pytest.mark.parametrize("shape", sorted(_SHAPES))
def test_thrift_round_trip_of_real_footers(shape):
    table = shape_table(shape, 5000)
    for dictionary in (True, False):
        data = serial_bytes(_writer("zstd", dictionary), table, 2048)
        size = int.from_bytes(data[-8:-4], "little")
        footer = data[-8 - size:-8]
        fields, end = thrift.read_struct(footer, 0)
        assert end == len(footer)
        assert thrift.field(fields, 3)[2] == 5000       # num_rows
        out = bytearray()
        thrift.write_struct(out, fields)
        assert bytes(out) == footer


def test_thrift_round_trip_of_every_wire_type():
    fields = [
        [1, 1, None], [2, 2, None], [3, 3, 0x7F], [4, 4, -300],
        [5, 5, 1 << 30], [6, 6, -(1 << 62)], [7, 7, b"\x00" * 7 + b"\x40"],
        [8, 8, b"bytes"], [9, 9, (5, list(range(20)))],
        [10, 10, (8, [b"a", b"b"])], [11, 11, (8, 6, [(b"k", 9)])],
        [12, 11, (0, 0, [])], [40, 12, [[1, 6, 5], [17, 1, None]]],
        [41, 9, (1, [1, 2])], [3000, 5, -1]]
    out = bytearray()
    thrift.write_struct(out, fields)
    back, end = thrift.read_struct(bytes(out), 0)
    assert (back, end) == (fields, len(out))
    again = bytearray()
    thrift.write_struct(again, back)
    assert again == out
