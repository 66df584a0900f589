"""A Parquet file's column chunks encoded side by side, stitched into
the file the serial writer would have written, byte for byte.

A column chunk's pages depend only on that column's rows in that row
group, and Arrow lays the chunks out row group by row group, column by
column.  So a file is cut into pieces — one row group by a run of whole
top-level columns — each piece is one `pq.write_table` of a zero-copy
slice into a native sink (which releases the interpreter lock), and the
file is "PAR1", the pieces' bodies in order, and a footer made of the
pieces' own column-chunk metadata with the offsets shifted to where the
bodies landed.  The schema, the key-value metadata and the writer's
name come from a zero-row write of the whole table with the same
arguments.  `tests/test_parquet_stitch.py` holds the result to `==`
against `pq.write_table`'s bytes.

Pieces run on one process-wide pool (`paimon-encode`), shared by every
writer in the process; the calling thread takes pieces too, so a full
pool cannot hold a writer up and a writer called from a pool thread
cannot deadlock.
"""

from __future__ import annotations

import os
import threading
from collections import deque
from concurrent.futures import TimeoutError as FutureTimeout
from contextlib import nullcontext
from typing import Dict, List, Optional, Tuple

import pyarrow as pa
import pyarrow.parquet as pq

from paimon_tpu.format import thrift

__all__ = ["encode_table"]

_MAGIC = b"PAR1"
# Arrow never writes a longer row group, whatever `row_group_size` asks
# (pyarrow's _MAX_ROW_GROUP_SIZE, the writer's max_row_group_length)
_MAX_ROW_GROUP_ROWS = 64 << 20
# the in-memory bytes a piece should hold at least: a one-column piece
# of 1Mi eight-byte rows takes 17-80 ms to encode, and below a few MiB a
# piece's fixed cost (a writer, a footer, a task) shows.  A file whose
# row groups hold less than this is written in one call.
_PIECE_BYTES = 4 << 20

# parquet.thrift field ids the stitch edits
_FMD_NUM_ROWS, _FMD_ROW_GROUPS = 3, 4
_RG_COLUMNS, _RG_BYTES, _RG_OFFSET, _RG_COMPRESSED, _RG_ORDINAL = \
    1, 2, 5, 6, 7
_CC_META = 3
_CC_OFFSETS = (2, 4, 6)             # the chunk's own, its offset / column
#                                     index
_CMD_OFFSETS = (9, 10, 11, 14)      # data / index / dictionary page,
#                                     bloom filter

_pool = None
_pool_lock = threading.Lock()


def _encode_pool():
    """(the process's encode pool, its workers): one worker a core,
    less the caller's own."""
    global _pool
    with _pool_lock:
        if _pool is None:
            from paimon_tpu.parallel.executors import new_thread_pool
            workers = max(1, (os.cpu_count() or 1) - 1)
            _pool = (new_thread_pool(workers, "paimon-encode"), workers)
        return _pool


def _supported(schema: pa.Schema) -> bool:
    """Flat columns only: a nested column's chunks (and a dictionary or
    extension column's) are not proven to stitch."""
    t = pa.types
    return not any(
        t.is_nested(f.type) or t.is_dictionary(f.type)
        or isinstance(f.type, pa.BaseExtensionType) for f in schema)


def plan_pieces(table: pa.Table, row_group_rows: int
                ) -> List[Tuple[int, int, int, int]]:
    """The file's pieces in file order, as (first row, rows, first
    column, end column); one piece = write it in one call."""
    rows, ncols = table.num_rows, table.num_columns
    rg = min(max(1, row_group_rows), _MAX_ROW_GROUP_ROWS)
    groups = -(-rows // rg)
    whole = [(0, rows, 0, ncols)]
    if not groups or not ncols or not _supported(table.schema):
        return whole
    # column runs of at least _PIECE_BYTES a row group; a short tail
    # joins the run before it
    runs, first, held = [], 0, 0.0
    for i, col in enumerate(table.columns):
        held += col.nbytes / groups
        if held >= _PIECE_BYTES:
            runs.append((first, i + 1))
            first, held = i + 1, 0.0
    if not runs:
        return whole
    if first < ncols:
        runs[-1] = (runs[-1][0], ncols)
    return [(r * rg, min(rg, rows - r * rg), a, b)
            for r in range(groups) for a, b in runs]


def _written(table: pa.Table, row_group_rows: Optional[int],
             write_args: Dict) -> pa.Buffer:
    """One `pq.write_table` into a native sink (into `io.BytesIO` every
    write call takes the interpreter lock, and pieces do not scale)."""
    sink = pa.BufferOutputStream()
    pq.write_table(table, sink, row_group_size=row_group_rows,
                   **write_args)
    return sink.getvalue()


def _encode_piece(table: pa.Table, piece, write_args: Dict) -> pa.Buffer:
    row, rows, a, b = piece
    part = table.slice(row, rows)
    if (a, b) != (0, table.num_columns):
        part = part.select(range(a, b))
    return _written(part, rows, write_args)


def _split(buf: pa.Buffer) -> Tuple[memoryview, List[list]]:
    """A Parquet file as (its bytes between the leading magic and the
    footer, its footer's FileMetaData)."""
    view = memoryview(buf).cast("B")    # a pa.Buffer's items are signed
    end = len(view) - 8 - int.from_bytes(view[-8:-4], "little")
    return view[4:end], thrift.read_struct(view, end)[0]


def _move(fields: List[list], ids, by: int) -> None:
    """Add `by` to the absolute file offsets `ids` of a struct; 0 is how
    the format says "not there"."""
    for fid in ids:
        f = thrift.field(fields, fid)
        if f is not None and f[2] > 0:
            f[2] += by


def _stitch(shell: pa.Buffer, pieces, parts: List[pa.Buffer]) -> bytes:
    """The file of `parts`, the encoded `pieces` in file order; `shell`
    is the zero-row write of the whole table."""
    footer = _split(shell)[1]
    out = [_MAGIC]
    at = len(_MAGIC)
    groups: List[list] = []
    rows = 0
    for (_, n, first_col, _), part in zip(pieces, parts):
        body, meta = _split(part)
        (group,) = thrift.field(meta, _FMD_ROW_GROUPS)[2][1]
        chunks = thrift.field(group, _RG_COLUMNS)[2][1]
        by = at - len(_MAGIC)
        for chunk in chunks:
            _move(chunk, _CC_OFFSETS, by)
            _move(thrift.field(chunk, _CC_META)[2], _CMD_OFFSETS, by)
        if first_col == 0:
            # the piece opens a row group: its own, moved and renumbered
            _move(group, (_RG_OFFSET,), by)
            ordinal = thrift.field(group, _RG_ORDINAL)
            if ordinal is not None:
                ordinal[2] = len(groups)
            groups.append(group)
            rows += n
        else:
            # a further run of the open row group's columns
            head = groups[-1]
            thrift.field(head, _RG_COLUMNS)[2][1].extend(chunks)
            for fid in (_RG_BYTES, _RG_COMPRESSED):
                thrift.field(head, fid)[2] += thrift.field(group, fid)[2]
        out.append(body)
        at += len(body)
    thrift.field(footer, _FMD_NUM_ROWS)[2] = rows
    thrift.field(footer, _FMD_ROW_GROUPS)[2] = (12, groups)
    tail = bytearray()
    thrift.write_struct(tail, footer)
    out += [tail, len(tail).to_bytes(4, "little"), _MAGIC]
    return b"".join(out)


def encode_table(table: pa.Table, row_group_rows: int,
                 write_args: Dict) -> Tuple[bytes, int]:
    """`table` as the bytes `pq.write_table(table, sink,
    row_group_size=row_group_rows, **write_args)` leaves in its sink,
    and the number of pieces they were encoded in (1: that very call).

    `write_args` may hold what `_ParquetWriter` passes — compression,
    compression_level, use_dictionary, write_statistics — and nothing
    that puts bytes between the row groups (a page index, a bloom
    filter)."""
    pieces = plan_pieces(table, row_group_rows)
    if len(pieces) == 1:
        return _written(table, row_group_rows, write_args).to_pybytes(), 1
    parts = _run_pieces(table, pieces, write_args)
    shell = _written(table.schema.empty_table(), None, write_args)
    return _stitch(shell, pieces, parts), len(pieces)


def _run_pieces(table: pa.Table, pieces, write_args: Dict
                ) -> List[pa.Buffer]:
    """Every piece encoded, by the pool's workers and by this thread:
    all of them draw from one queue, so the caller alone finishes the
    file if no worker is free, and never waits for a worker that has
    not begun."""
    from paimon_tpu.obs.trace import carry, span
    todo = deque(range(len(pieces)))
    parts: List[Optional[pa.Buffer]] = [None] * len(pieces)

    def drain(helper: bool) -> None:
        while True:
            try:
                i = todo.popleft()
            except IndexError:
                return
            with span("encode.piece", cat="io", piece=i,
                      rows=pieces[i][1]) if helper else nullcontext():
                parts[i] = _encode_piece(table, pieces[i], write_args)

    pool, workers = _encode_pool()
    helpers = [pool.submit(carry(drain), True)
               for _ in range(min(workers, len(pieces) - 1))]
    try:
        drain(False)
        for f in helpers:
            # a helper that has not begun has nothing left to take
            if not f.cancel():
                _finished(f)
    except BaseException:
        todo.clear()
        for f in helpers:
            f.cancel()
        raise
    return parts


def _finished(fut) -> None:
    """Wait for a helper that is inside its last piece, inside the
    request's deadline.  No `wait` span (utils/deadline.wait_future
    opens one): `encode` stays a leaf on the writer's thread."""
    from paimon_tpu.utils.deadline import check_deadline
    while True:
        check_deadline("encode")
        try:
            return fut.result(timeout=0.5)
        except FutureTimeout:
            continue
