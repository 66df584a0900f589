"""The vectorized bucket hash against its scalar reference.

`KeyHasher.hashes` (32-bit words read from the key columns' own values,
or, for string and binary keys, gathered from the Arrow offsets and
data; mixed a block of rows at a time) must equal `KeyHasher._hash_rows`
(the BinaryRow codec + `murmur_hash_bytes`, a row at a time) and
`bucket_of` in every row; `_bucket_from_hash` must equal Java's
`Math.abs(h % n)` computed in Python ints.
"""

import zlib

import numpy as np
import pyarrow as pa
import pytest

from paimon_tpu.core import bucket as bucket_mod
from paimon_tpu.core.bucket import (
    FixedBucketAssigner, KeyHasher, _bucket_from_hash, bucket_of,
)
from paimon_tpu.types import (
    BigIntType, BooleanType, CharType, DateType, DoubleType, FloatType,
    IntType, SmallIntType, TimeType, TinyIntType, VarBinaryType,
    VarCharType,
)

BLOCK = 64                     # a small block: the sizes below cross it
REAL_BLOCK = bucket_mod._BLOCK_ROWS


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    monkeypatch.setattr(bucket_mod, "_BLOCK_ROWS", BLOCK)


def _column(kind: str, n: int, rng) -> pa.Array:
    """Random values of one fixed-width key type, extremes included."""
    ints = {"tinyint": (np.int8, pa.int8()),
            "smallint": (np.int16, pa.int16()),
            "int": (np.int32, pa.int32()),
            "bigint": (np.int64, pa.int64())}
    if kind in ints:
        dtype, arrow = ints[kind]
        info = np.iinfo(dtype)
        vals = rng.integers(info.min, info.max, n, dtype=dtype,
                            endpoint=True)
        vals[:3] = (info.min, info.max, -1)[:n]
        return pa.array(vals, arrow)
    if kind == "boolean":
        return pa.array(rng.integers(0, 2, n).astype(bool))
    if kind == "float":
        vals = rng.standard_normal(n).astype(np.float32)
        vals[:2] = (-0.0, np.float32(3.4e38))[:n]
        return pa.array(vals, pa.float32())
    if kind == "double":
        vals = rng.standard_normal(n) * 1e200
        vals[:2] = (-0.0, 1.7e308)[:n]
        return pa.array(vals, pa.float64())
    if kind == "date":
        return pa.array(rng.integers(-30000, 60000, n).astype(np.int32),
                        pa.int32()).cast(pa.date32())
    assert kind == "time"
    return pa.array(rng.integers(0, 86_400_000, n).astype(np.int32),
                    pa.int32()).cast(pa.time32("ms"))


TYPES = {"boolean": BooleanType, "tinyint": TinyIntType,
         "smallint": SmallIntType, "int": IntType, "bigint": BigIntType,
         "float": FloatType, "double": DoubleType, "date": DateType,
         "time": TimeType}
# string and binary key types, as the hasher is given them
VAR_TYPES = {"string": lambda: VarCharType(VarCharType.MAX_LENGTH),
             "char": lambda: CharType(40),
             "binary": lambda: VarBinaryType(VarBinaryType.MAX_LENGTH)}


def _var_column(kind: str, lengths, rng) -> pa.Array:
    """Strings (ASCII digits, "é" and "€" among them: 1-, 2- and 3-byte
    UTF-8) or random bytes, zero bytes included, of the given lengths in
    characters."""
    if kind == "binary":
        return pa.array([rng.integers(0, 256, k, dtype=np.uint8).tobytes()
                         for k in lengths], pa.binary())
    alphabet = np.array(list("0123456789user\x00é€"))
    return pa.array(["".join(rng.choice(alphabet, k)) for k in lengths],
                    pa.string())


def _with_nulls(arr: pa.Array, nulls: str, rng) -> pa.Array:
    if nulls == "none":
        return arr
    mask = np.ones(len(arr), dtype=bool) if nulls == "all" \
        else rng.random(len(arr)) < 0.3
    return pa.array([None if m else v
                     for v, m in zip(arr.to_pylist(), mask)], type=arr.type)


def _assert_equal_to_reference(table: pa.Table, kinds):
    hasher = KeyHasher(table.column_names,
                       [(TYPES.get(k) or VAR_TYPES[k])() for k in kinds])
    fast = hasher.hashes(table)
    assert fast.dtype == np.uint32 and len(fast) == table.num_rows
    assert np.array_equal(fast, hasher._hash_rows(table))
    cols = [c.to_pylist() for c in table.columns]
    for num_buckets in (7, 8):
        got = _bucket_from_hash(fast, num_buckets)
        for i in range(0, table.num_rows, max(1, table.num_rows // 25)):
            values = [c[i] for c in cols]
            assert got[i] == bucket_of(values, hasher.types, num_buckets)


@pytest.mark.parametrize("nulls", ["none", "some", "all"])
@pytest.mark.parametrize("kind", sorted(TYPES))
def test_one_key_column_of_every_fixed_width_type(kind, nulls):
    rng = np.random.default_rng(len(kind) * 31 + len(nulls))
    col = _with_nulls(_column(kind, 3 * BLOCK + 5, rng), nulls, rng)
    _assert_equal_to_reference(pa.table({"k": col}), [kind])


@pytest.mark.parametrize("nulls", [
    ("none", "none", "none"), ("some", "none", "none"),
    ("none", "some", "all"), ("all", "all", "all"),
    ("none", "none", "some")])
@pytest.mark.parametrize("kinds", [
    ("bigint", "int"), ("int", "bigint"), ("tinyint", "double"),
    ("smallint", "boolean", "bigint"), ("date", "float", "time"),
    ("double", "tinyint", "smallint")])
def test_several_key_columns_of_mixed_widths(kinds, nulls):
    rng = np.random.default_rng(zlib.crc32(repr((kinds, nulls)).encode()))
    n = 2 * BLOCK + 9
    table = pa.table({
        f"k{i}": _with_nulls(_column(kind, n, rng), nl, rng)
        for i, (kind, nl) in enumerate(zip(kinds, nulls))})
    _assert_equal_to_reference(table, list(kinds))


@pytest.mark.parametrize("n", [0, 1, 8, 9, BLOCK - 1, BLOCK, BLOCK + 1,
                               4 * BLOCK + 17])
@pytest.mark.parametrize("nulls", ["none", "some"])
def test_row_counts_around_the_scalar_threshold_and_the_block(n, nulls):
    rng = np.random.default_rng(n + len(nulls))
    table = pa.table({
        "a": _with_nulls(_column("bigint", n, rng), nulls, rng),
        "b": _column("int", n, rng)})
    _assert_equal_to_reference(table, ["bigint", "int"])


@pytest.mark.parametrize("nulls", ["none", "some"])
def test_chunked_columns_with_unaligned_chunks(nulls):
    rng = np.random.default_rng(5)
    n = 3 * BLOCK
    a = _with_nulls(_column("bigint", n, rng), nulls, rng)
    b = _with_nulls(_column("smallint", n, rng), nulls, rng)
    table = pa.table({
        "a": pa.chunked_array([a.slice(0, 10), a.slice(10, BLOCK),
                               a.slice(10 + BLOCK)]),
        "b": pa.chunked_array([b.slice(0, 2 * BLOCK + 1),
                               b.slice(2 * BLOCK + 1)])})
    _assert_equal_to_reference(table, ["bigint", "smallint"])
    whole = KeyHasher(["a", "b"], [BigIntType(), SmallIntType()])
    assert np.array_equal(whole.hashes(table),
                          whole.hashes(pa.table({"a": a, "b": b})))


@pytest.mark.parametrize("nulls", ["none", "some"])
@pytest.mark.parametrize("kind", ["boolean", "tinyint", "int", "bigint",
                                  "double"])
def test_a_sliced_table_with_a_non_zero_offset(kind, nulls):
    rng = np.random.default_rng(len(kind) + len(nulls))
    n = 3 * BLOCK
    whole = pa.table({"k": _with_nulls(_column(kind, n, rng), nulls, rng)})
    part = whole.slice(BLOCK // 2 + 3, BLOCK + 11)
    assert part.column("k").chunk(0).offset > 0
    _assert_equal_to_reference(part, [kind])
    hasher = KeyHasher(["k"], [TYPES[kind]()])
    assert np.array_equal(
        hasher.hashes(part),
        hasher.hashes(whole)[BLOCK // 2 + 3:][:BLOCK + 11])


@pytest.mark.parametrize("length", list(range(0, 41)))
@pytest.mark.parametrize("kind", ["string", "binary"])
def test_one_string_key_of_every_length_to_40(kind, length):
    """Inline slots (0-7 bytes), then a variable part of whole 8-byte
    words: every length lands on its own side of each edge."""
    rng = np.random.default_rng(length * 3 + len(kind))
    col = _var_column(kind, [length] * (BLOCK + 9), rng)
    _assert_equal_to_reference(pa.table({"k": col}), [kind])


@pytest.mark.parametrize("nulls", ["none", "some", "all"])
@pytest.mark.parametrize("kind", sorted(VAR_TYPES))
def test_one_string_key_of_mixed_lengths(kind, nulls):
    """Rows of several layouts in one batch (lengths 0-40, multi-byte
    UTF-8 characters), nulls among them: each layout hashed apart."""
    rng = np.random.default_rng(len(kind) * 7 + len(nulls))
    col = _var_column("binary" if kind == "binary" else "string",
                      rng.integers(0, 41, 3 * BLOCK + 5), rng)
    _assert_equal_to_reference(pa.table({"k": _with_nulls(col, nulls, rng)}),
                               [kind])


@pytest.mark.parametrize("nulls", [("none", "none"), ("some", "none"),
                                   ("none", "some"), ("some", "some")])
@pytest.mark.parametrize("kinds", [
    ("string", "bigint"), ("bigint", "string"), ("int", "binary"),
    ("string", "string"), ("string", "binary", "smallint")])
def test_string_keys_beside_other_columns(kinds, nulls):
    """A (STRING, BIGINT) bucket key and its kin: a string's variable
    part lies after every slot, the next string's after it."""
    rng = np.random.default_rng(zlib.crc32(repr((kinds, nulls)).encode()))
    n = 2 * BLOCK + 9
    nulls = nulls + ("none",) * (len(kinds) - len(nulls))
    cols = {}
    for i, (kind, nl) in enumerate(zip(kinds, nulls)):
        col = _var_column(kind, rng.integers(0, 30, n), rng) \
            if kind in VAR_TYPES else _column(kind, n, rng)
        cols[f"k{i}"] = _with_nulls(col, nl, rng)
    _assert_equal_to_reference(pa.table(cols), list(kinds))


def test_ycsb_keys_in_chunks_and_slices():
    """YCSB's 18-23-byte "user" keys, a chunked column and a sliced one:
    the offsets honoured, the last value read to the end of its data."""
    rng = np.random.default_rng(23)
    keys = pa.array([f"user{v}" for v in rng.integers(0, 1 << 63,
                                                      3 * BLOCK)])
    chunked = pa.table({"k": pa.chunked_array(
        [keys.slice(0, 7), keys.slice(7, BLOCK), keys.slice(7 + BLOCK)])})
    _assert_equal_to_reference(chunked, ["string"])
    part = pa.table({"k": keys}).slice(BLOCK // 2 + 3, BLOCK + 11)
    _assert_equal_to_reference(part, ["string"])
    hasher = KeyHasher(["k"], [VAR_TYPES["string"]()])
    assert hasher.vectorized(part.num_rows)
    assert np.array_equal(hasher.hashes(part), hasher.hashes(
        pa.table({"k": keys}))[BLOCK // 2 + 3:][:BLOCK + 11])


@pytest.mark.parametrize("num_buckets", [1, 2, 7, 16])
def test_the_assigner_buckets_string_keys_as_bucket_of(num_buckets):
    rng = np.random.default_rng(num_buckets + 100)
    n = BLOCK + 30
    table = pa.table({"s": _var_column("string", rng.integers(0, 30, n),
                                       rng),
                      "b": _column("bigint", n, rng)})
    types = [VarCharType(VarCharType.MAX_LENGTH, False), BigIntType(False)]
    assigner = FixedBucketAssigner(["s", "b"], types, num_buckets)
    got = assigner.assign(table)
    s, b = table.column("s").to_pylist(), table.column("b").to_pylist()
    assert got.tolist() == [bucket_of([s[i], b[i]], types, num_buckets)
                            for i in range(n)]
    assert assigner.hashed_rows(n) == ((0, 0) if num_buckets == 1
                                       else (n, n))


def test_the_real_block_size_gives_the_same_hashes(monkeypatch):
    rng = np.random.default_rng(9)
    n = 2 * REAL_BLOCK + 77
    table = pa.table({"a": _with_nulls(_column("bigint", n, rng), "some",
                                       rng),
                      "b": _column("int", n, rng)})
    hasher = KeyHasher(["a", "b"], [BigIntType(), IntType()])
    small = hasher.hashes(table)
    monkeypatch.setattr(bucket_mod, "_BLOCK_ROWS", REAL_BLOCK)
    real = hasher.hashes(table)
    assert np.array_equal(real, small)
    for start in (0, REAL_BLOCK - 20, 2 * REAL_BLOCK - 20, n - 40):
        part = table.slice(start, 40)
        assert np.array_equal(real[start:start + 40],
                              hasher._hash_rows(part))


def test_a_column_of_another_arrow_width_is_cast_to_the_key_type():
    """A caller's int64 column under an INT key hashes as the INT."""
    vals = np.arange(-20, 20, dtype=np.int64)
    hasher = KeyHasher(["k"], [IntType()])
    assert np.array_equal(
        hasher.hashes(pa.table({"k": vals})),
        hasher.hashes(pa.table({"k": vals.astype(np.int32)})))


def _java_bucket(h: int, n: int) -> int:
    """Math.abs(h % n) with h an int (two's complement), % truncating."""
    signed = h - (1 << 32) if h >= 1 << 31 else h
    rem = abs(signed) % n
    java_rem = -rem if signed < 0 else rem     # the dividend's sign
    return abs(java_rem)


HASHES = [0, 1, 41, 0x7FFFFFFF, 0x80000000, 0x80000001, 0xFFFFFFFF,
          0xDEADBEEF, 0x12345678, 0xCAFEBABE, 0x7FFFFFFE, 0xFFFFFFF9]


@pytest.mark.parametrize("dtype", [np.uint32, np.uint64])
@pytest.mark.parametrize("n", [1, 2, 7, 8, (1 << 31) - 1])
def test_bucket_from_hash_is_javas_abs_of_the_remainder(n, dtype):
    rng = np.random.default_rng(n % 1000)
    hashes = HASHES + rng.integers(0, 1 << 32, 500).tolist()
    got = _bucket_from_hash(np.array(hashes, dtype=dtype), n)
    assert got.dtype == np.int32
    assert got.tolist() == [_java_bucket(h, n) for h in hashes]
    assert any(h >= 1 << 31 for h in hashes) and \
        any(h < 1 << 31 for h in hashes)


@pytest.mark.parametrize("num_buckets", [1, 2, 7, 8])
def test_the_assigner_gives_every_row_bucket_ofs_bucket(num_buckets):
    rng = np.random.default_rng(num_buckets)
    n = BLOCK + 30
    table = pa.table({"a": _column("bigint", n, rng),
                      "b": _column("int", n, rng),
                      "payload": rng.random(n)})
    types = [BigIntType(False), IntType(False)]
    got = FixedBucketAssigner(["a", "b"], types, num_buckets).assign(table)
    assert got.dtype == np.int32
    a, b = table.column("a").to_pylist(), table.column("b").to_pylist()
    assert got.tolist() == [bucket_of([a[i], b[i]], types, num_buckets)
                            for i in range(n)]
