"""The device route's operands written once (ops/merge.py
`MergeOperands.device_planes`, ops/normkey.py `encode_planes`): the
planes are byte for byte what the lane-matrix encoder and its pad gave,
whatever the key's type and the table's chunking; tables the planar
writer does not serve reach the same planes through the adapter; and
the prep allocates the planes and nothing else of their size."""

import datetime
import decimal
import tracemalloc

import numpy as np
import pyarrow as pa
import pytest

from paimon_tpu.metrics import MERGE_PREP_PLANAR_ROWS, global_registry
from paimon_tpu.obs import trace
from paimon_tpu.ops import merge as M
from paimon_tpu.ops.merge import KIND_COL, SEQ_COL, merge_runs
from paimon_tpu.ops.normkey import NormalizedKeyEncoder
from paimon_tpu.types import RowKind

I64_MIN, I64_MAX = -(1 << 63), (1 << 63) - 1


def _reference_planes(lanes, order_lanes, seq):
    """The operands as `_padded_operands` made them before the planes:
    a padded `[m, L]` matrix whose columns are the lane operands, the
    sequence split by shift and mask, the validity word."""
    lanes = np.asarray(lanes)
    if order_lanes is not None and order_lanes.shape[1] > 0:
        lanes = np.concatenate([lanes, order_lanes], axis=1)
    n, m = len(seq), M._pad_size(len(seq))
    lanes_p = np.zeros((m, lanes.shape[1]), dtype=np.uint32)
    lanes_p[:n] = lanes
    useq = seq.astype(np.int64, copy=False).view(np.uint64)
    seq_hi = np.zeros(m, dtype=np.uint32)
    seq_lo = np.zeros(m, dtype=np.uint32)
    seq_hi[:n] = (useq >> np.uint64(32)).astype(np.uint32)
    seq_lo[:n] = (useq & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    invalid = np.ones(m, dtype=np.uint32)
    invalid[:n] = 0
    return np.stack([*(np.ascontiguousarray(lanes_p[:, i])
                       for i in range(lanes_p.shape[1])),
                     seq_hi, seq_lo, invalid])


def _runs(key_columns, cuts, seed=0, extra=None):
    """`key_columns` ({name: Arrow array}) with a sequence and a kind
    column, cut into runs at `cuts`; the sequence uses both words."""
    n = len(next(iter(key_columns.values())))
    rng = np.random.default_rng(seed)
    seq = rng.integers(0, 1 << 40, n).astype(np.int64)
    table = pa.table({**key_columns, **(extra or {}),
                      SEQ_COL: pa.array(seq),
                      KIND_COL: pa.array(np.zeros(n, np.int8))})
    bounds = [0, *cuts, n]
    return [table.slice(a, b - a) for a, b in zip(bounds, bounds[1:])]


def _planar_rows():
    return global_registry().group("merge") \
        .counter(MERGE_PREP_PLANAR_ROWS).count


def _check(runs, key_names, nullable=None, seq_fields=None,
           planar=True):
    """The planes of `runs` against the matrix encoder's, byte for
    byte; `planar`: whether the planar writer is to serve them."""
    schema = runs[0].schema
    enc = NormalizedKeyEncoder(
        [schema.field(k).type for k in key_names],
        nullable=nullable or [False] * len(key_names))
    trace.enable_tracing()
    try:
        before = _planar_rows()
        op = M.merge_operands(runs, key_names, key_encoder=enc,
                              seq_fields=seq_fields)
        planes = op.device_planes()
        forms = [s.attrs["form"] for s in trace.collector().snapshot()
                 if s.name == "merge.prep" and "form" in s.attrs]
    finally:
        trace.disable_tracing()
        trace.collector().clear()
    table = pa.concat_tables(runs)
    n = table.num_rows
    lanes, truncated, _ = enc.encode_table_ex(table, key_names)
    order = M.user_seq_order_lanes(table, seq_fields) \
        if seq_fields else None
    seq = np.asarray(table.column(SEQ_COL).combine_chunks())
    want = _reference_planes(lanes, order, seq)
    assert len(planes) == len(want)
    for plane, want_plane in zip(planes, want):
        assert plane.dtype == np.uint32 and plane.flags.c_contiguous
        assert plane.base is None       # an array each, not one block
        assert plane.tobytes() == want_plane.tobytes()
    assert forms == ["planes" if planar else "matrix"]
    assert _planar_rows() - before == (n if planar else 0)
    assert op.any_truncated == bool(truncated.any())
    return np.stack(planes)


def _ints(n, seed, lo, hi, dtype):
    return np.random.default_rng(seed).integers(lo, hi, n).astype(dtype)


@pytest.mark.parametrize("name", [
    "int64", "int64_extremes", "int32", "int8", "uint32", "date32",
    "date64", "timestamp", "time64", "bool", "float64", "float32"])
def test_one_fixed_width_key_in_several_chunks(name):
    n = 5000
    if name == "int64":
        col = pa.array(_ints(n, 1, -1 << 50, 1 << 50, np.int64))
    elif name == "int64_extremes":
        vals = _ints(n, 2, -5, 5, np.int64)
        vals[:6] = [I64_MIN, I64_MAX, -1, 0, 1, I64_MIN + 1]
        col = pa.array(vals)
    elif name == "int32":
        col = pa.array(_ints(n, 3, -1 << 31, 1 << 31, np.int32))
    elif name == "int8":
        col = pa.array(_ints(n, 4, -128, 128, np.int8))
    elif name == "uint32":
        col = pa.array(_ints(n, 5, 0, 1 << 32, np.uint32))
    elif name == "date32":
        col = pa.array(_ints(n, 6, -40000, 40000, np.int32)) \
            .cast(pa.date32())
    elif name == "date64":
        col = pa.array(_ints(n, 7, -40000, 40000, np.int64) * 86400000) \
            .cast(pa.date64())
    elif name == "timestamp":
        col = pa.array(_ints(n, 8, -1 << 55, 1 << 55, np.int64)) \
            .cast(pa.timestamp("us"))
    elif name == "time64":
        col = pa.array(_ints(n, 9, 0, 86400 * 10 ** 6, np.int64)) \
            .cast(pa.time64("us"))
    elif name == "bool":
        col = pa.array(_ints(n, 10, 0, 2, np.int8).astype(bool))
    else:
        vals = np.random.default_rng(11).normal(0, 1e6, n)
        vals[:8] = [0.0, -0.0, np.inf, -np.inf, np.nan, 1e-310, -1e-310,
                    -1.5]
        col = pa.array(vals.astype(np.float64 if name == "float64"
                                   else np.float32))
    _check(_runs({"k": col}, [700, 701, 2900, 4999]), ["k"])


def test_composite_bigint_int_key():
    """TPC-H LINEITEM's (l_orderkey BIGINT, l_linenumber INT)."""
    n = 4000
    runs = _runs({"o": pa.array(_ints(n, 20, 0, 1 << 40, np.int64)),
                  "l": pa.array(_ints(n, 21, 1, 8, np.int32))},
                 [1000, 2500])
    planes = _check(runs, ["o", "l"])
    assert planes.shape[0] == 4 + 3


@pytest.mark.parametrize("key_type", [pa.int64(), pa.int32(),
                                      pa.float64(), pa.timestamp("ms")])
def test_nullable_key_with_nulls(key_type):
    """The presence plane from each chunk's validity, the value words
    of a null row zero whatever lies under it — a chunk of nulls only
    and a chunk without any among them."""
    n = 3000
    vals = _ints(n, 30, -1000, 1000, np.int64)
    mask = np.random.default_rng(31).random(n) < 0.2
    mask[:500] = False              # first run: no null
    mask[500:900] = True            # second run: nulls only
    col = pa.array(vals, pa.int64(), mask=mask).cast(key_type)
    planes = _check(_runs({"k": col}, [500, 900, 2000]), ["k"],
                    nullable=[True])
    assert planes[0, :n].tolist() == mask.astype(int).tolist()
    assert not planes[1:3, :n][:, mask].any()


def test_null_in_a_not_null_key_raises():
    col = pa.array([1, None, 3], pa.int64())
    op = M.merge_operands(_runs({"k": col}, []), ["k"],
                          key_encoder=NormalizedKeyEncoder(
                              [pa.int64()], nullable=[False]))
    with pytest.raises(ValueError, match="NOT NULL"):
        op.device_planes()


def test_sliced_and_empty_chunks():
    """Chunks with an offset (a key buffer shared by two slices, a bit
    offset into the validity) and chunks of no rows."""
    n = 2000
    key = pa.array(_ints(n, 40, -1 << 60, 1 << 60, np.int64))
    opt = pa.array(_ints(n, 41, -9, 9, np.int32), pa.int32(),
                   mask=np.random.default_rng(42).random(n) < 0.3)
    runs = _runs({"k": key, "o": opt}, [])
    whole = runs[0]
    runs = [whole.slice(3, 500), whole.slice(0, 0), whole.slice(503, 1),
            whole.slice(504, 0), whole.slice(504, 1496)]
    assert runs[0].column("k").chunk(0).offset == 3
    _check(runs, ["k", "o"], nullable=[False, True])


@pytest.mark.parametrize("n", [1, 1024, 1025, 2048, 2049])
def test_padding(n):
    """n at, under and one above a program size: 1024 and 2048 rows
    leave no padding row at all."""
    col = pa.array(_ints(n, 50 + n, -1 << 62, 1 << 62, np.int64))
    planes = _check(_runs({"k": col}, [n // 2] if n > 1 else []), ["k"])
    m = 1024 if n <= 1024 else 2048 if n <= 2048 else 4096
    assert planes.shape == (5, m)
    assert planes[4].tolist() == [0] * n + [1] * (m - n)


def test_bytes_key_through_the_adapter():
    n = 1500
    words = ["k%05d" % v for v in _ints(n, 60, 0, 900, np.int64)]
    words[7] = "a key that is longer than the sixteen bytes of its lanes"
    runs = _runs({"k": pa.array(words, pa.string())}, [400, 1100])
    planes = _check(runs, ["k"], planar=False)
    assert planes.shape[0] == 4 + 3


def test_decimal_key_through_the_adapter():
    n = 600
    vals = [decimal.Decimal(int(v)).scaleb(-2)
            for v in _ints(n, 61, -10 ** 9, 10 ** 9, np.int64)]
    runs = _runs({"k": pa.array(vals, pa.decimal128(15, 2))}, [100])
    _check(runs, ["k"], planar=False)


@pytest.mark.parametrize("seq_type", [pa.int64(), pa.date32()])
def test_sequence_field_order_lanes(seq_type):
    """User order lanes keep their encoder and are transposed in behind
    the key's planes; the key and the sequence still go straight."""
    n = 2500
    ts = pa.array(_ints(n, 70, 0, 20000, np.int32), pa.int32(),
                  mask=np.random.default_rng(71).random(n) < 0.1) \
        .cast(seq_type)
    runs = _runs({"k": pa.array(_ints(n, 72, 0, 300, np.int64))},
                 [800, 1700], extra={"ts": ts})
    planes = _check(runs, ["k"], seq_fields=["ts"])
    assert planes.shape[0] == 2 + 3 + 3


def test_date32_key_orders_as_its_days():
    """A DATE key's lanes are its days since the epoch, sign flipped
    (Arrow casts date32 to int32 only; the encoder goes through it)."""
    days = [datetime.date(1969, 12, 31), datetime.date(1970, 1, 1),
            datetime.date(2024, 2, 29)]
    enc = NormalizedKeyEncoder([pa.date32()], nullable=[False])
    _, _, packed = enc.encode_columns_ex(
        [pa.chunked_array([pa.array(days, pa.date32())])])
    assert (packed ^ np.uint64(1 << 63)).view(np.int64).tolist() == \
        [-1, 0, 19782]


# -- the routes give the same winners ---------------------------------------

def _overlapping_runs(seed, runs=5, rows=700, keys=400, composite=False):
    rng = np.random.default_rng(seed)
    out, seq0 = [], 0
    for _ in range(runs):
        k = np.sort(rng.integers(-keys, keys, rows)).astype(np.int64)
        cols = {"k": pa.array(k)}
        if composite:
            cols["l"] = pa.array(rng.integers(0, 3, rows)
                                 .astype(np.int32))
            order = np.lexsort((cols["l"].to_numpy(), k))
            cols = {c: v.take(pa.array(order)) for c, v in cols.items()}
        kinds = rng.choice([RowKind.INSERT, RowKind.UPDATE_AFTER,
                            RowKind.DELETE], rows, p=[0.6, 0.25, 0.15])
        out.append(pa.table({
            **cols,
            SEQ_COL: pa.array(np.arange(seq0, seq0 + rows, dtype=np.int64)),
            KIND_COL: pa.array(kinds.astype(np.int8)),
            "v": pa.array(rng.integers(0, 1 << 30, rows))}))
        seq0 += rows - 50           # sequences overlap between runs too
    return out


@pytest.mark.parametrize("composite", [False, True])
@pytest.mark.parametrize("drop_deletes", [True, False])
@pytest.mark.parametrize("engine", ["deduplicate", "first-row"])
@pytest.mark.parametrize("seed", [3, 19])
def test_merge_runs_planar_device_route_matches_host(
        seed, engine, drop_deletes, composite, monkeypatch):
    runs = _overlapping_runs(seed, composite=composite)
    key_names = ["k", "l"] if composite else ["k"]
    monkeypatch.setenv("PAIMON_FORCE_HOST_SORT", "1")
    host = merge_runs(runs, key_names, merge_engine=engine,
                      drop_deletes=drop_deletes)
    monkeypatch.delenv("PAIMON_FORCE_HOST_SORT")
    monkeypatch.setenv("PAIMON_FORCE_DEVICE_SORT", "1")
    before = _planar_rows()
    dev = merge_runs(runs, key_names, merge_engine=engine,
                     drop_deletes=drop_deletes)
    assert _planar_rows() - before == sum(r.num_rows for r in runs)
    assert dev.indices.dtype == np.int64
    assert np.array_equal(dev.indices, host.indices)
    assert dev.take().equals(host.take())


def test_changelog_merge_keeps_its_matrix(monkeypatch):
    """`with_prev` on sorted runs takes the full return with the
    offset-value codes, which read the lane matrix: the operands go
    through the adapter and the planar counter stands."""
    runs = _overlapping_runs(8)
    host = merge_runs(runs, ["k"], with_prev=True)
    monkeypatch.setenv("PAIMON_FORCE_DEVICE_SORT", "1")
    before = _planar_rows()
    dev = merge_runs(runs, ["k"], with_prev=True)
    assert _planar_rows() == before
    assert np.array_equal(dev.indices, host.indices)
    assert np.array_equal(dev.prev_indices, host.prev_indices)


def test_counter_counts_fixed_width_device_merges_only(monkeypatch):
    """`merge` / `prep_planar_rows`: every row of a device-route merge
    of a fixed-width-key table, none of a `bytes`-key table's, none of
    a host-route merge."""
    fixed = _overlapping_runs(1)
    rows = sum(r.num_rows for r in fixed)
    text = [r.set_column(0, "k", r.column("k").cast(pa.string()))
            for r in fixed]
    text = [r.sort_by([("k", "ascending"), (SEQ_COL, "ascending")])
            for r in text]
    before = _planar_rows()
    merge_runs(fixed, ["k"])                        # cpu backend: host
    assert _planar_rows() == before
    monkeypatch.setenv("PAIMON_FORCE_DEVICE_SORT", "1")
    merge_runs(text, ["k"])
    assert _planar_rows() == before
    merge_runs(fixed, ["k"])
    merge_runs(fixed, ["k"], merge_engine="first-row")
    assert _planar_rows() - before == 2 * rows


# -- written once -------------------------------------------------------------

def test_prep_allocates_the_planes_and_nothing_else_their_size():
    """One int64 key, ~1M rows in 8 chunks: the prep's peak of Python
    and numpy memory stays under 1.3 x the planes' own bytes, and
    Arrow's pool never holds 8 bytes a row more than before (neither
    the key nor the sequence is combined)."""
    n = 1_000_000
    rng = np.random.default_rng(5)
    bounds = np.linspace(0, n, 9).astype(int)
    runs = [pa.table({
        "k": pa.array(np.sort(rng.integers(0, 1 << 40, b - a))),
        SEQ_COL: pa.array(np.arange(a, b, dtype=np.int64)),
        KIND_COL: pa.array(np.zeros(b - a, np.int8))})
        for a, b in zip(bounds, bounds[1:])]
    enc = NormalizedKeyEncoder([pa.int64()], nullable=[False])
    default_pool = pa.default_memory_pool()
    pool = pa.proxy_memory_pool(default_pool)   # counts from zero
    pa.set_memory_pool(pool)
    tracemalloc.start()
    try:
        tracemalloc.reset_peak()
        base = tracemalloc.get_traced_memory()[0]
        op = M.merge_operands(runs, ["k"], key_encoder=enc)
        planes = op.device_planes()
        peak = tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()
        pa.set_memory_pool(default_pool)
    assert [p.shape for p in planes] == [(1 << 20,)] * 5
    nbytes = sum(p.nbytes for p in planes)
    assert nbytes <= peak < 1.3 * nbytes
    assert pool.max_memory() < 8 * n
    assert op.lanes is None and op.seq is None      # no host form made
