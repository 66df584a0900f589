"""Device decode plane: raw Parquet pages -> vectorized device ops.

Three layers, per ISSUE 12:

1. Fuzz/oracle suite — random column chunks across encodings (RLE
   dictionary, PLAIN), codecs, null densities and row-group/page
   shapes, asserted BYTE-IDENTICAL to the pyarrow decode of the same
   file (format/rawpage.py + ops/decode.py).
2. End-to-end: `read.device-decode` tables scan/compact identically to
   the pyarrow path per merge engine, and unsupported files fall back
   (counted) instead of erroring.
3. Lowering proof — the fused decode+merge program compiles to a
   jaxpr/HLO with NO host callback or host transfer inside, the
   acceptance ROADMAP item 1 names while real TPUs are unavailable.
"""

import os
import tempfile

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from paimon_tpu.format.rawpage import (
    DeviceDecodeUnsupported, read_parquet_device,
)
from paimon_tpu.fs.fileio import LocalFileIO


@pytest.fixture
def fio():
    return LocalFileIO()


def _roundtrip(tmp_path, fio, table, name, **write_kw):
    path = str(tmp_path / f"{name}.parquet")
    pq.write_table(table, path, **write_kw)
    oracle = pq.ParquetFile(path).read()
    got = read_parquet_device(fio, path)
    assert got.equals(oracle), f"{name}: device decode != pyarrow"
    return got


# ---------------------------------------------------------------------------
# 1. fuzz/oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("codec", ["none", "zstd", "snappy"])
def test_plain_fixed_width_oracle(tmp_path, fio, seed, codec):
    """PLAIN INT32/INT64/FLOAT/DOUBLE pages decode byte-identical."""
    rng = np.random.default_rng(seed)
    n = 20_000
    t = pa.table({
        "i64": pa.array(rng.integers(-1 << 60, 1 << 60, n), pa.int64()),
        "f64": pa.array(rng.standard_normal(n), pa.float64()),
        "i32": pa.array(rng.integers(-1 << 30, 1 << 30, n).astype(
            np.int32), pa.int32()),
        "f32": pa.array(rng.random(n).astype(np.float32), pa.float32()),
    })
    _roundtrip(tmp_path, fio, t, f"plain_{codec}_{seed}",
               compression=codec, use_dictionary=False)


@pytest.mark.parametrize("seed,cards", [(0, 7), (1, 100), (2, 1000)])
def test_dictionary_oracle(tmp_path, fio, seed, cards):
    """RLE_DICTIONARY index streams + PLAIN dictionary pages."""
    rng = np.random.default_rng(seed)
    n = 30_000
    t = pa.table({
        "a": pa.array(rng.integers(0, cards, n), pa.int64()),
        "b": pa.array((rng.integers(0, cards, n) * 0.5), pa.float64()),
        "c": pa.array(rng.integers(0, cards, n).astype(np.int32),
                      pa.int32()),
    })
    _roundtrip(tmp_path, fio, t, f"dict_{cards}_{seed}",
               compression="zstd")


@pytest.mark.parametrize("density", [0.0, 0.01, 0.5, 0.97, 1.0])
def test_null_density_oracle(tmp_path, fio, density):
    """Definition-level RLE streams across null densities (incl. the
    all-null and no-null edges)."""
    rng = np.random.default_rng(17)
    n = 12_000
    mask = rng.random(n) < density       # True = null
    vals = rng.integers(0, 1 << 40, n)
    t = pa.table({
        "x": pa.array(vals, pa.int64(), mask=mask),
        "y": pa.array(rng.random(n), pa.float64(),
                      mask=rng.random(n) < density),
    })
    _roundtrip(tmp_path, fio, t, f"nulls_{density}",
               compression="zstd", use_dictionary=False)


@pytest.mark.parametrize("rg,page", [(977, 512), (5_000, 2048),
                                     (50_000, 1 << 20)])
def test_row_group_and_page_shapes(tmp_path, fio, rg, page):
    """Many row groups / tiny pages exercise the page walk + per-page
    RLE run parsing."""
    rng = np.random.default_rng(23)
    n = 25_000
    mask = rng.random(n) < 0.2
    t = pa.table({
        "k": pa.array(rng.integers(0, 1 << 50, n), pa.int64()),
        "d": pa.array(rng.integers(0, 30, n), pa.int64()),
        "nul": pa.array(rng.integers(0, 99, n), pa.int64(), mask=mask),
    })
    _roundtrip(tmp_path, fio, t, f"shapes_{rg}_{page}",
               compression="zstd", row_group_size=rg,
               data_page_size=page)


def test_temporal_and_narrow_ints(tmp_path, fio):
    """Logical types over the fixed-width physicals: timestamps, dates,
    int8/int16 (sign-extended INT32 storage)."""
    rng = np.random.default_rng(5)
    n = 8_000
    t = pa.table({
        "ts": pa.array(rng.integers(0, 1 << 44, n), pa.timestamp("us")),
        "d32": pa.array(rng.integers(0, 20_000, n).astype(np.int32),
                        pa.date32()),
        "i8": pa.array(rng.integers(-128, 128, n).astype(np.int8),
                       pa.int8()),
        "i16": pa.array(rng.integers(-1 << 15, 1 << 15, n).astype(
            np.int16), pa.int16()),
    })
    _roundtrip(tmp_path, fio, t, "temporal", compression="zstd",
               use_dictionary=False)


def test_projection_and_column_order(tmp_path, fio):
    rng = np.random.default_rng(7)
    n = 5_000
    t = pa.table({
        "a": pa.array(rng.integers(0, 10, n), pa.int64()),
        "b": pa.array(rng.random(n), pa.float64()),
        "c": pa.array(rng.integers(0, 9, n).astype(np.int32),
                      pa.int32()),
    })
    path = str(tmp_path / "proj.parquet")
    pq.write_table(t, path, compression="zstd")
    got = read_parquet_device(fio, path, projection=["c", "a"])
    assert got.equals(pq.ParquetFile(path).read(columns=["c", "a"]))


def test_unsupported_shapes_raise(tmp_path, fio):
    """Strings, v2 data pages and unknown codecs raise the typed
    fallback signal — never a wrong answer."""
    n = 1_000
    rng = np.random.default_rng(1)
    strings = pa.table({"s": pa.array(
        [f"v{i}" for i in range(n)], pa.string())})
    p = str(tmp_path / "str.parquet")
    pq.write_table(strings, p)
    with pytest.raises(DeviceDecodeUnsupported):
        read_parquet_device(fio, p)

    ints = pa.table({"x": pa.array(rng.integers(0, 1 << 40, n),
                                   pa.int64())})
    p2 = str(tmp_path / "v2.parquet")
    pq.write_table(ints, p2, data_page_version="2.0",
                   use_dictionary=False)
    with pytest.raises(DeviceDecodeUnsupported):
        read_parquet_device(fio, p2)

    p3 = str(tmp_path / "lz4.parquet")
    pq.write_table(ints, p3, compression="lz4")
    with pytest.raises(DeviceDecodeUnsupported):
        read_parquet_device(fio, p3)


def test_maybe_read_device_counts_fallback(tmp_path, fio):
    from paimon_tpu.format.rawpage import maybe_read_device
    from paimon_tpu.metrics import (
        SCAN_DEVICE_DECODE_FALLBACKS, global_registry,
    )
    t = pa.table({"s": pa.array(["a", "b"], pa.string())})
    p = str(tmp_path / "fb.parquet")
    pq.write_table(t, p)
    before = global_registry().group("scan").counter(
        SCAN_DEVICE_DECODE_FALLBACKS).count
    assert maybe_read_device(fio, p) is None
    after = global_registry().group("scan").counter(
        SCAN_DEVICE_DECODE_FALLBACKS).count
    assert after == before + 1


@pytest.mark.parametrize("exc", [TypeError, ValueError,
                                 NotImplementedError])
@pytest.mark.parametrize("transform", ["plain_to_u64",
                                       "expand_rle_hybrid"])
def test_device_transform_error_propagates(tmp_path, fio, monkeypatch,
                                           transform, exc):
    """What JAX raises for a lowering it cannot do (TypeError,
    ValueError, NotImplementedError) comes from the DEVICE step: it
    fails the read.  Only the parse step's errors mean "uncovered
    file" — a truncated page of the same file still falls back."""
    import paimon_tpu.ops.decode as decode
    from paimon_tpu.format.rawpage import maybe_read_device
    from paimon_tpu.metrics import (
        SCAN_DEVICE_DECODE_FALLBACKS, global_registry,
    )
    rng = np.random.default_rng(3)
    n = 5_000
    t = pa.table({"x": pa.array(rng.integers(0, 1 << 40, n), pa.int64(),
                                mask=rng.random(n) < 0.2)})
    p = str(tmp_path / "dev.parquet")
    pq.write_table(t, p, use_dictionary=False)
    assert maybe_read_device(fio, p).equals(pq.read_table(p))

    def refuse(*a, **k):
        raise exc("no lowering for this on the backend")

    counter = global_registry().group("scan").counter(
        SCAN_DEVICE_DECODE_FALLBACKS)
    before = counter.count
    with monkeypatch.context() as m:
        m.setattr(decode, transform, refuse)
        with pytest.raises(exc, match="no lowering"):
            maybe_read_device(fio, p)
    assert counter.count == before          # not counted as a fallback

    # the parse step keeps its fallback: cut the file's pages short
    raw = open(p, "rb").read()
    md = pq.read_metadata(p)
    start = md.row_group(0).column(0).data_page_offset
    torn = bytearray(raw)
    torn[start:start + 6] = b"\xff" * 6      # garbage page header
    p2 = str(tmp_path / "torn.parquet")
    open(p2, "wb").write(bytes(torn))
    assert maybe_read_device(fio, p2) is None
    assert counter.count == before + 1


# ---------------------------------------------------------------------------
# 2. end-to-end table reads
# ---------------------------------------------------------------------------


def _numeric_engine_table(path, engine, seed=3, commits=3, rows=4_000):
    from paimon_tpu.schema import Schema
    from paimon_tpu.table import FileStoreTable
    from paimon_tpu.types import BigIntType, DoubleType, IntType
    rng = np.random.default_rng(seed)
    opts = {"bucket": "2", "write-only": "true", "merge-engine": engine,
            "parquet.enable.dictionary": "false"}
    if engine == "aggregation":
        opts.update({"fields.v1.aggregate-function": "sum",
                     "fields.v2.aggregate-function": "max"})
    schema = (Schema.builder()
              .column("id", BigIntType(False))
              .column("v1", BigIntType())
              .column("v2", DoubleType())
              .column("v3", IntType())
              .primary_key("id")
              .options(opts)
              .build())
    table = FileStoreTable.create(path, schema)
    wb = table.new_batch_write_builder()
    for _ in range(commits):
        with wb.new_write() as w:
            ids = rng.integers(0, rows, rows)
            w.write_arrow(pa.table({
                "id": pa.array(ids, pa.int64()),
                "v1": pa.array(rng.integers(0, 1 << 30, rows),
                               pa.int64()),
                "v2": pa.array(rng.random(rows), pa.float64()),
                "v3": pa.array(rng.integers(0, 50, rows).astype(
                    np.int32), pa.int32()),
            }))
            wb.new_commit().commit(w.prepare_commit())
    return table


@pytest.mark.parametrize("engine", ["deduplicate", "first-row",
                                    "aggregation", "partial-update"])
def test_scan_oracle_per_engine(tmp_path, engine):
    """Merge-on-read scans through the device decode plane are
    row-identical to the pyarrow path for every merge engine."""
    from paimon_tpu.metrics import (
        SCAN_DEVICE_DECODE_FILES, global_registry,
    )
    t = _numeric_engine_table(str(tmp_path / "t"), engine)
    oracle = t.to_arrow().sort_by("id")
    before = global_registry().group("scan").counter(
        SCAN_DEVICE_DECODE_FILES).count
    dev = t.copy({"read.device-decode": "true"}).to_arrow().sort_by("id")
    after = global_registry().group("scan").counter(
        SCAN_DEVICE_DECODE_FILES).count
    assert dev.equals(oracle)
    assert after > before, "device decode path never engaged"


def test_compact_oracle_device_decode(tmp_path):
    """Full compaction reading through the device decode plane produces
    a table identical to the host-decoded twin."""
    a = _numeric_engine_table(str(tmp_path / "a"), "deduplicate")
    b = _numeric_engine_table(str(tmp_path / "b"), "deduplicate")
    a.copy({"read.device-decode": "true"}).compact(full=True)
    b.compact(full=True)
    assert a.to_arrow().sort_by("id").equals(b.to_arrow().sort_by("id"))


def test_string_schema_falls_back_identically(tmp_path):
    """A schema with a string column (BYTE_ARRAY) silently takes the
    pyarrow path under read.device-decode — results identical."""
    from paimon_tpu.schema import Schema
    from paimon_tpu.table import FileStoreTable
    from paimon_tpu.types import BigIntType, VarCharType
    schema = (Schema.builder()
              .column("id", BigIntType(False))
              .column("s", VarCharType())
              .primary_key("id")
              .options({"bucket": "1"})
              .build())
    t = FileStoreTable.create(str(tmp_path / "t"), schema)
    wb = t.new_batch_write_builder()
    with wb.new_write() as w:
        w.write_arrow(pa.table({
            "id": pa.array(np.arange(500), pa.int64()),
            "s": pa.array([f"row-{i}" for i in range(500)]),
        }))
        wb.new_commit().commit(w.prepare_commit())
    oracle = t.to_arrow().sort_by("id")
    dev = t.copy({"read.device-decode": "true"}).to_arrow().sort_by("id")
    assert dev.equals(oracle)


# ---------------------------------------------------------------------------
# 3. lowering proof (ROADMAP item 1 acceptance)
# ---------------------------------------------------------------------------


_HOST_MARKERS = ("pure_callback", "io_callback", "python_callback",
                 "outside_compilation", "infeed", "outfeed",
                 "SendToHost", "RecvFromHost", "host_callback")


def test_fused_decode_merge_lowering_has_no_host_transfers():
    """The fused raw-bytes -> decode -> normalized-key -> merge program
    must stay on-device end to end: its jaxpr holds no callback
    primitive and its compiled HLO no host-transfer custom call."""
    import jax
    import jax.numpy as jnp

    from paimon_tpu.ops.decode import fused_decode_merge

    n = 2048
    key_bytes = jnp.zeros(8 * n, jnp.uint8)
    seq_bytes = jnp.zeros(8 * n, jnp.uint8)
    invalid = jnp.zeros(n, jnp.uint32)

    jaxpr = jax.make_jaxpr(
        lambda k, s, i: fused_decode_merge(k, s, i))(
        key_bytes, seq_bytes, invalid)
    text = str(jaxpr)
    for marker in _HOST_MARKERS:
        assert marker not in text, f"jaxpr contains {marker}"

    lowered = jax.jit(
        lambda k, s, i: fused_decode_merge(k, s, i)).lower(
        key_bytes, seq_bytes, invalid)
    hlo = lowered.as_text()
    for marker in _HOST_MARKERS:
        assert marker not in hlo, f"HLO contains {marker}"


def test_fused_decode_merge_matches_numpy_reference():
    """The fused program's winners equal the host-side reference merge
    over the same raw bytes."""
    import jax.numpy as jnp

    from paimon_tpu.ops.decode import fused_decode_merge

    rng = np.random.default_rng(9)
    n = 2048
    keys = rng.integers(-1 << 40, 1 << 40, n).astype(np.int64)
    seq = np.arange(n, dtype=np.int64)
    perm, winner, packed = fused_decode_merge(
        jnp.asarray(keys.view(np.uint8)),
        jnp.asarray(seq.view(np.uint8)),
        jnp.zeros(n, jnp.uint32))
    perm = np.asarray(perm)
    winner = np.asarray(winner)
    # reference: stable sort by (key, seq); winner = last of key group
    order = np.lexsort((seq, keys))
    assert np.array_equal(perm, order)
    ks = keys[order]
    eq_next = np.concatenate([ks[1:] == ks[:-1], [False]])
    assert np.array_equal(winner, ~eq_next)
    # packed keys are the order-preserving normkey transform
    assert np.array_equal(
        np.asarray(packed),
        keys.view(np.uint64) ^ np.uint64(1 << 63))


def test_decode_primitives_unit():
    """unpack_bits / expand_rle_hybrid against tiny hand-computed
    streams (the parquet hybrid layout)."""
    import jax.numpy as jnp

    from paimon_tpu.format.rawpage import parse_rle_runs
    from paimon_tpu.ops.decode import expand_rle_hybrid, unpack_bits

    # bit-packed: header 0b11 = 1 group of 8 values, width 3
    # values 0..7 packed little-endian: 3 bytes
    vals = np.arange(8, dtype=np.uint8)
    packed = np.packbits(
        np.unpackbits(vals[:, None], axis=1, count=3,
                      bitorder="little"), bitorder="little").tobytes()
    buf = bytes([0b11]) + packed
    runs = parse_rle_runs(buf, 3, 8)
    is_p, val, cum, bits = runs
    assert is_p.tolist() == [1] and cum.tolist() == [8]
    words = np.frombuffer(buf + b"\0" * (32 - len(buf)), np.uint32)
    out = expand_rle_hybrid(
        jnp.asarray(words), jnp.asarray(is_p), jnp.asarray(val),
        jnp.asarray(cum), jnp.asarray(bits), 3, 8)
    assert np.asarray(out).tolist() == list(range(8))

    # RLE run: header 0b1010 = 5 repeats of value 4 (1 byte, width 3)
    buf2 = bytes([0b1010, 4])
    is_p, val, cum, bits = parse_rle_runs(buf2, 3, 5)
    assert is_p.tolist() == [0] and val.tolist() == [4] \
        and cum.tolist() == [5]

    # offsets: arbitrary bit positions
    words = jnp.asarray(np.frombuffer(
        np.uint64(0b110_101_100_011_010_001).tobytes() + b"\0" * 8,
        np.uint32))
    offs = jnp.asarray(np.arange(6, dtype=np.int32) * 3)
    got = np.asarray(unpack_bits(words, 3, offs))
    assert got.tolist() == [1, 2, 3, 4, 5, 6]


def test_iter_batches_device_streams_and_falls_back_midfile(tmp_path,
                                                            fio):
    """The streamed-compaction iterator decodes one row group at a
    time (bounded memory) and, when a page shape the footer cannot
    reveal appears (v2 data pages), silently reroutes the remaining
    row groups through pyarrow — rows identical either way."""
    from paimon_tpu.format.rawpage import iter_batches_device

    rng = np.random.default_rng(31)
    n = 24_000
    t = pa.table({
        "a": pa.array(rng.integers(0, 1 << 40, n), pa.int64()),
        "b": pa.array(rng.random(n), pa.float64()),
    })
    p1 = str(tmp_path / "v1.parquet")
    pq.write_table(t, p1, compression="zstd", use_dictionary=False,
                   row_group_size=5_000)
    got = pa.concat_tables(
        list(iter_batches_device(fio, p1, 2_000)))
    assert got.equals(pq.ParquetFile(p1).read())
    assert got.num_rows == n

    # v2 data pages: the footer pre-check passes, the first page does
    # not — the iterator must still deliver every row via pyarrow
    p2 = str(tmp_path / "v2.parquet")
    pq.write_table(t, p2, compression="zstd", use_dictionary=False,
                   row_group_size=5_000, data_page_version="2.0")
    from paimon_tpu.metrics import (
        SCAN_DEVICE_DECODE_FALLBACKS, global_registry,
    )
    before = global_registry().group("scan").counter(
        SCAN_DEVICE_DECODE_FALLBACKS).count
    got2 = pa.concat_tables(
        list(iter_batches_device(fio, p2, 2_000)))
    after = global_registry().group("scan").counter(
        SCAN_DEVICE_DECODE_FALLBACKS).count
    assert got2.equals(pq.ParquetFile(p2).read())
    assert after == before + 1
