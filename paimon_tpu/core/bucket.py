"""Bucket assignment.

reference: paimon-common/.../utils/MurmurHashUtils + table/sink/
KeyAndBucketExtractor: bucket = abs(javaRem(murmur32_words(binaryRow bytes
without arity prefix, seed=42), numBuckets)). Matching the reference hash
bit-for-bit keeps our data files bucket-compatible with JVM/pypaimon
readers and writers.

The hash is vectorized over rows with numpy: 32-bit words mixed in uint32
(which wraps as Java's int does), a cache-sized block of rows at a time.
A key that serializes to fixed-width BinaryRows (int/float/date keys)
gives its words straight from the key columns' own values; a key with
string or binary columns has its rows built as bytes from the Arrow
offsets and data, a group of rows of one BinaryRow layout at a time.
Other key types (decimal, timestamp) and batches of a few rows take a
per-row loop, which is also the reference the tests hold the fast paths
to.
"""

from __future__ import annotations

import struct
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

from paimon_tpu.data.binary_row import BinaryRowCodec
from paimon_tpu.types import (
    BigIntType, BinaryType, BooleanType, CharType, DataType, DateType,
    DoubleType, FloatType, IntType, SmallIntType, TimeType, TinyIntType,
    VarBinaryType, VarCharType,
)

__all__ = ["murmur_hash_bytes", "KeyHasher", "FixedBucketAssigner",
           "bucket_of"]

_C1 = 0xCC9E2D51
_C2 = 0x1B873593
_SEED = 42
_M32 = 0xFFFFFFFF


def _mix_word(h1: int, k1: int) -> int:
    """One murmur round: the 32-bit word `k1` into the state `h1`."""
    k1 = (k1 * _C1) & _M32
    k1 = ((k1 << 15) | (k1 >> 17)) & _M32
    k1 = (k1 * _C2) & _M32
    h1 = (h1 ^ k1) & _M32
    h1 = ((h1 << 13) | (h1 >> 19)) & _M32
    return (h1 * 5 + 0xE6546B64) & _M32


def murmur_hash_bytes(data: bytes, seed: int = _SEED) -> int:
    """Murmur3-style word hash over complete 4-byte words (tail bytes
    ignored, matching the reference's hashBytesByWords)."""
    n = len(data)
    h1 = seed
    for i in range(0, n - (n % 4), 4):
        h1 = _mix_word(h1, struct.unpack_from("<I", data, i)[0])
    return _fmix(h1, n)


def _fmix(h1: int, length: int) -> int:
    h1 = (h1 ^ length) & _M32
    h1 ^= h1 >> 16
    h1 = (h1 * 0x85EBCA6B) & _M32
    h1 ^= h1 >> 13
    h1 = (h1 * 0xC2B2AE35) & _M32
    h1 ^= h1 >> 16
    return h1


def _bucket_from_hash(h: np.ndarray, num_buckets: int) -> np.ndarray:
    """Java `Math.abs(h % n)` on the hash as an int: int32[N].  `h` is an
    unsigned array whose low 32 bits are the hash; `np.fmod` truncates
    toward zero as Java's `%` does, and |remainder| < n fits an int32."""
    signed = h.astype(np.uint32, copy=False).view(np.int32)
    return np.abs(np.fmod(signed, np.int32(num_buckets)))


def bucket_of(values: Sequence[Any], types: Sequence[DataType],
              num_buckets: int) -> int:
    codec = BinaryRowCodec(types)
    data = codec.to_bytes(values, with_arity_prefix=False)
    h = murmur_hash_bytes(data)
    return int(_bucket_from_hash(np.array([h], dtype=np.uint64),
                                 num_buckets)[0])


# fixed-width key types: the Arrow type whose values buffer is the
# BinaryRow slot's bytes, and those bytes as one little-endian number
_FIXED_SLOTS = (
    ((BooleanType,), pa.uint8(), "u1"),
    ((TinyIntType,), pa.int8(), "u1"),
    ((SmallIntType,), pa.int16(), "<u2"),
    ((IntType, DateType, TimeType), pa.int32(), "<u4"),
    ((BigIntType,), pa.int64(), "<u8"),
    ((FloatType,), pa.float32(), "<u4"),
    ((DoubleType,), pa.float64(), "<u8"),
)
_FIXED_SLOT_TYPES = tuple(t for types, _, _ in _FIXED_SLOTS for t in types)
# variable-width key types: a string's UTF-8 bytes or a binary's, inline
# in the slot up to 7 bytes, else in the row's variable part
_VAR_SLOT_TYPES = (CharType, VarCharType, BinaryType, VarBinaryType)
_MAX_INLINE = 7

# rows hashed at a time: the block's state, word and scratch arrays
# (256 KB each) stay in cache through the ~30 passes a row takes;
# 64Ki measured fastest of 8Ki..whole-batch at 3.2M rows
_BLOCK_ROWS = 1 << 16


def _slot_words(col: pa.ChunkedArray, t: DataType
                ) -> Tuple[np.ndarray, Optional[np.ndarray],
                           Optional[np.ndarray]]:
    """One key column as the two 32-bit words of its 8-byte BinaryRow
    slot: (low, high, nulls).  The words are views of the column's own
    values (a 1/2/4-byte type is its value zero-extended and a high word
    that is zero in every row: None); `nulls` is bool[N] where the
    column holds a null, under which the values are arbitrary."""
    arr = col.chunk(0) if col.num_chunks == 1 else col.combine_chunks()
    arrow_type, word = next((a, w) for types, a, w in _FIXED_SLOTS
                            if isinstance(t, types))
    if arr.type != arrow_type:
        arr = arr.cast(arrow_type)
    vals = np.frombuffer(arr.buffers()[1], dtype=word, count=len(arr),
                         offset=arr.offset * np.dtype(word).itemsize)
    nulls = arr.is_null().to_numpy(zero_copy_only=False) \
        if arr.null_count else None
    if vals.itemsize == 8:
        halves = vals.view("<u4")
        return halves[0::2], halves[1::2], nulls
    return vals, None, nulls


def _var_slot(col: pa.ChunkedArray):
    """One string or binary key column: (starts, lengths, nulls, part,
    data) — each row's offset into `data` and its length, 0 under a
    null; where a null lies (None if nowhere); the bytes its value takes
    in the row's variable part, 0 when it is inline (<= 7 bytes) or
    null; and the column's bytes, a view of its data buffer."""
    arr = col.chunk(0) if col.num_chunks == 1 else col.combine_chunks()
    if not (pa.types.is_binary(arr.type) or pa.types.is_string(arr.type)
            or pa.types.is_large_binary(arr.type)
            or pa.types.is_large_string(arr.type)):
        arr = arr.cast(pa.large_binary())
    wide = pa.types.is_large_binary(arr.type) or \
        pa.types.is_large_string(arr.type)
    offsets = np.frombuffer(arr.buffers()[1],
                            dtype=np.int64 if wide else np.int32,
                            count=len(arr) + 1,
                            offset=arr.offset * (8 if wide else 4))
    starts = offsets[:-1]
    lengths = np.diff(offsets)
    nulls = arr.is_null().to_numpy(zero_copy_only=False) \
        if arr.null_count else None
    if nulls is not None:
        lengths[nulls] = 0
        starts = np.where(nulls, 0, starts)
    part = (lengths + 7) & ~7
    part[lengths <= _MAX_INLINE] = 0
    buf = arr.buffers()[2]
    data = np.zeros(0, dtype=np.uint8) if buf is None \
        else np.frombuffer(buf, dtype=np.uint8)
    return starts, lengths, nulls, part, data


def _gather_words(data: np.ndarray, starts: np.ndarray, width: int
                  ) -> np.ndarray:
    """uint32[rows, width / 4]: the `width` bytes of `data` from each
    row's start (zeros past its end) — one gather of `width`-byte items
    from a view that has one starting at every byte; the rows that start
    within `width` of the end read from a zero-padded copy of the end."""
    limit = len(data) - width + 1
    past = starts >= limit
    any_past = bool(past.any())
    if limit > 0:
        items = np.ndarray((limit,), dtype=f"V{width}", buffer=data,
                           strides=(1,))
        # an index, not np.take: take copies a strided source whole
        out = items[np.where(past, 0, starts) if any_past else starts]
    else:
        out = np.empty(len(starts), dtype=f"V{width}")
    if any_past:
        first = int(starts[past].min())
        end = np.zeros(len(data) - first + width, dtype=np.uint8)
        end[:len(data) - first] = data[first:]
        items = np.ndarray((len(end) - width + 1,), dtype=f"V{width}",
                           buffer=end, strides=(1,))
        out[past] = items[starts[past] - first]
    return out.view("<u4").reshape(len(starts), width // 4)


# the low k bytes of a little-endian word, k = 0..4
_BYTE_MASKS = np.array([0, 0xFF, 0xFFFF, 0xFFFFFF, 0xFFFFFFFF],
                       dtype=np.uint32)


def _bytes_word(words: np.ndarray, lengths: np.ndarray, at: int,
                top: Optional[np.ndarray] = None):
    """A word of the rows' bytes (`words`, that at byte `at` of each
    value), the bytes past a value's length zeroed; `top`: a byte to put
    in bits 24..31 (an inline slot's 0x80 | length)."""
    full = len(lengths) == 0 or int(lengths.min()) >= at + 4

    def load(s: int, e: int, out: np.ndarray):
        if full:
            np.multiply(words[s:e], np.uint32(_C1), out=out)
            return
        np.bitwise_and(words[s:e],
                       _BYTE_MASKS[np.clip(lengths[s:e] - at, 0, 4)],
                       out=out)
        if top is not None:
            out |= top[s:e]
        np.multiply(out, np.uint32(_C1), out=out)
    return load


def _const_word(value: int):
    def load(s: int, e: int, out: np.ndarray):
        out.fill((value * _C1) & _M32)
    return load


# A word of the rows is a loader — load(start, end, out) writes the
# block's `word * C1` (the first step of its round) into `out` — or None
# where the word is zero in every row of the batch.

def _value_word(values: np.ndarray, nulls: Optional[np.ndarray]):
    def load(s: int, e: int, out: np.ndarray):
        np.multiply(values[s:e], np.uint32(_C1), out=out)
        if nulls is not None:
            out[nulls[s:e]] = 0          # a null's slot is zero
    return load


def _null_word(bits: List[Tuple[np.ndarray, np.uint32]]):
    def load(s: int, e: int, out: np.ndarray):
        out.fill(0)
        for nulls, bit in bits:
            np.bitwise_or(out, bit, out=out, where=nulls[s:e])
        np.multiply(out, np.uint32(_C1), out=out)
    return load


def _rotl(x: np.ndarray, r: int, tmp: np.ndarray):
    np.left_shift(x, np.uint32(r), out=tmp)
    np.right_shift(x, np.uint32(32 - r), out=x)
    np.bitwise_or(x, tmp, out=x)


def _xorshift(x: np.ndarray, r: int, tmp: np.ndarray):
    np.right_shift(x, np.uint32(r), out=tmp)
    np.bitwise_xor(x, tmp, out=x)


def _murmur_words(words: List, n: int, row_len: int) -> np.ndarray:
    """`murmur_hash_bytes` of N rows of `row_len` bytes given as their
    words: uint32 arithmetic in place (it wraps as Java's int does),
    `_BLOCK_ROWS` rows at a time; leading all-zero words are mixed into
    the starting state once."""
    h0 = _SEED
    while words and words[0] is None:
        h0 = _mix_word(h0, 0)
        words = words[1:]
    out = np.empty(n, dtype=np.uint32)
    k_buf = np.empty(min(n, _BLOCK_ROWS), dtype=np.uint32)
    t_buf = np.empty_like(k_buf)
    u32 = np.uint32
    for s in range(0, n, _BLOCK_ROWS):
        e = min(s + _BLOCK_ROWS, n)
        h1, k1, tmp = out[s:e], k_buf[:e - s], t_buf[:e - s]
        h1.fill(h0)
        for load in words:
            if load is not None:         # a zero word leaves h1 ^ 0
                load(s, e, k1)
                _rotl(k1, 15, tmp)
                np.multiply(k1, u32(_C2), out=k1)
                np.bitwise_xor(h1, k1, out=h1)
            _rotl(h1, 13, tmp)
            np.multiply(h1, u32(5), out=h1)
            np.add(h1, u32(0xE6546B64), out=h1)
        np.bitwise_xor(h1, u32(row_len), out=h1)     # _fmix
        _xorshift(h1, 16, tmp)
        np.multiply(h1, u32(0x85EBCA6B), out=h1)
        _xorshift(h1, 13, tmp)
        np.multiply(h1, u32(0xC2B2AE35), out=h1)
        _xorshift(h1, 16, tmp)
    return out


class KeyHasher:
    """Vectorized reference-compatible murmur hash of bucket-key rows
    (the shared base of fixed and dynamic bucket assignment)."""

    def __init__(self, bucket_key_names: Sequence[str],
                 bucket_key_types: Sequence[DataType]):
        self.names = list(bucket_key_names)
        self.types = list(bucket_key_types)
        self._codec = BinaryRowCodec(self.types)
        self._fixed_width = all(isinstance(t, _FIXED_SLOT_TYPES)
                                for t in self.types)
        self._var_width = all(isinstance(t, _FIXED_SLOT_TYPES
                                         + _VAR_SLOT_TYPES)
                              for t in self.types)

    def hashes(self, table: pa.Table) -> np.ndarray:
        """uint32[N] murmur hashes."""
        if self.vectorized(table.num_rows):
            if self._fixed_width:
                return self._hash_vectorized(table)
            return self._hash_var_width(table)
        return self._hash_rows(table).astype(np.uint32)

    def vectorized(self, num_rows: int) -> bool:
        """Whether `hashes` of that many rows takes a vectorized path.
        The numpy paths' fixed setup (views, scratch, ~30 calls a block)
        costs more than row-at-a-time hashing below ~10 rows — point
        lookup batches take the scalar codec path, ingest batches the
        vectorized one; both give identical reference hashes."""
        return num_rows > 8 and (self._fixed_width or self._var_width)

    def _hash_rows(self, table: pa.Table) -> np.ndarray:
        cols = [table.column(n).to_pylist() for n in self.names]
        out = np.empty(table.num_rows, dtype=np.uint64)
        for i in range(table.num_rows):
            values = tuple(c[i] for c in cols)
            data = self._codec.to_bytes(values, with_arity_prefix=False)
            out[i] = murmur_hash_bytes(data)
        return out

    def _hash_vectorized(self, table: pa.Table) -> np.ndarray:
        """The BinaryRow's words — the null-bit words, then two a key
        slot — without the rows' bytes: words that are zero in every
        row of this batch (the null bits of a batch without nulls, the
        high word of a narrow type) cost no pass over the rows."""
        arity = len(self.types)
        null_words = ((arity + 63 + 8) // 64) * 2
        null_bits: List[list] = [[] for _ in range(null_words)]
        slots: List = []
        for i, (name, t) in enumerate(zip(self.names, self.types)):
            low, high, nulls = _slot_words(table.column(name), t)
            if nulls is not None:
                bit = i + 8              # after the 8 header bits
                null_bits[bit // 32].append(
                    (nulls, np.uint32(1 << (bit % 32))))
            slots.append(_value_word(low, nulls))
            slots.append(None if high is None
                         else _value_word(high, nulls))
        words = [_null_word(bits) if bits else None
                 for bits in null_bits] + slots
        return _murmur_words(words, table.num_rows, len(words) * 4)

    def _hash_var_width(self, table: pa.Table) -> np.ndarray:
        """The BinaryRow words of a key with string or binary columns,
        read from the Arrow offsets and data: the null bits, a slot's
        two words (a fixed-width value's; a short string's bytes and
        0x80 | length; a long one's length and offset), then each long
        string's bytes in whole 8-byte words.  Rows whose long strings
        pad to the same lengths share one layout and one
        `_murmur_words`; a key of one string column has a layout per
        padded length (18- to 23-byte keys all give a 40-byte row)."""
        n = table.num_rows
        arity = len(self.types)
        null_bytes = ((arity + 63 + 8) // 64) * 8
        cols = [_var_slot(table.column(name))
                if isinstance(t, _VAR_SLOT_TYPES)
                else _slot_words(table.column(name), t)
                for name, t in zip(self.names, self.types)]
        var = [i for i, t in enumerate(self.types)
               if isinstance(t, _VAR_SLOT_TYPES)]
        if all(int(cols[i][3].min()) == int(cols[i][3].max())
               for i in var):
            return self._hash_layout(cols, None, null_bytes)
        # one code a row for its layout: the padded parts, mixed radix
        code = np.zeros(n, dtype=np.int64)
        for i in var:
            words8 = cols[i][3] // 8
            code = code * (int(words8.max()) + 1) + words8
        out = np.empty(n, dtype=np.uint32)
        for c in np.unique(code):
            rows = np.flatnonzero(code == c)
            out[rows] = self._hash_layout(cols, rows, null_bytes)
        return out

    def _hash_layout(self, cols, rows: Optional[np.ndarray],
                     null_bytes: int) -> np.ndarray:
        """`_murmur_words` of `rows` (None: all), whose long strings pad
        to the same lengths."""
        def pick(a):
            return a if rows is None or a is None else a[rows]

        null_bits: List[list] = [[] for _ in range(null_bytes // 4)]
        slots: List = []
        tail: List = []
        var_off = null_bytes + 8 * len(cols)
        for i, col in enumerate(cols):
            nulls = pick(col[2])
            if nulls is not None:
                bit = i + 8              # after the 8 header bits
                null_bits[bit // 32].append(
                    (nulls, np.uint32(1 << (bit % 32))))
            if len(col) == 3:            # fixed width
                slots.append(_value_word(pick(col[0]), nulls))
                slots.append(None if col[1] is None
                             else _value_word(pick(col[1]), nulls))
                continue
            starts, lengths = pick(col[0]), pick(col[1])
            part = int(col[3][0] if rows is None else col[3][rows[0]])
            if part == 0:                # inline: bytes, then 0x80 | len
                words = _gather_words(col[4], starts, 8)
                top = ((np.uint32(0x80) | lengths.astype(np.uint32))
                       << np.uint32(24))
                if nulls is not None:
                    top[nulls] = 0
                slots.append(_bytes_word(words[:, 0], lengths, 0))
                slots.append(_bytes_word(words[:, 1], lengths, 4, top))
                continue
            slots.append(_value_word(lengths.astype(np.uint32), None))
            slots.append(_const_word(var_off))   # offset << 32 | length
            words = _gather_words(col[4], starts, part)
            tail.extend(_bytes_word(words[:, k], lengths, 4 * k)
                        for k in range(part // 4))
            var_off += part
        words = [_null_word(bits) if bits else None
                 for bits in null_bits] + slots + tail
        count = len(cols[0][0]) if rows is None else len(rows)
        return _murmur_words(words, count, var_off)


class FixedBucketAssigner:
    """Vectorized fixed-bucket assignment for Arrow batches."""

    def __init__(self, bucket_key_names: Sequence[str],
                 bucket_key_types: Sequence[DataType], num_buckets: int):
        if num_buckets <= 0:
            raise ValueError(f"bucket must be > 0, got {num_buckets}")
        self.names = list(bucket_key_names)
        self.types = list(bucket_key_types)
        self.num_buckets = num_buckets
        self._hasher = KeyHasher(bucket_key_names, bucket_key_types)

    def assign(self, table: pa.Table) -> np.ndarray:
        if self.num_buckets == 1:        # every hash lands in bucket 0
            return np.zeros(table.num_rows, dtype=np.int32)
        return _bucket_from_hash(self._hasher.hashes(table),
                                 self.num_buckets)

    def hashed_rows(self, num_rows: int) -> Tuple[int, int]:
        """(rows `assign` hashes of a batch of `num_rows`, those of them
        by a vectorized path): none under one bucket."""
        if self.num_buckets == 1:
            return 0, 0
        return num_rows, num_rows if self._hasher.vectorized(num_rows) \
            else 0
