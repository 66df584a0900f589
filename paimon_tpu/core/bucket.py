"""Bucket assignment.

reference: paimon-common/.../utils/MurmurHashUtils + table/sink/
KeyAndBucketExtractor: bucket = abs(javaRem(murmur32_words(binaryRow bytes
without arity prefix, seed=42), numBuckets)). Matching the reference hash
bit-for-bit keeps our data files bucket-compatible with JVM/pypaimon
readers and writers.

The hash is vectorized over rows with numpy when the bucket key serializes
to fixed-width BinaryRows (int/float/date keys): 32-bit words read from the
key columns' own values, mixed in uint32 (which wraps as Java's int does),
a cache-sized block of rows at a time.  Variable-width keys fall back to a
per-row loop, which is also the reference the tests hold the fast path to.
"""

from __future__ import annotations

import struct
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

from paimon_tpu.data.binary_row import BinaryRowCodec
from paimon_tpu.types import (
    BigIntType, BooleanType, DataType, DateType, DoubleType, FloatType,
    IntType, SmallIntType, TimeType, TinyIntType,
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

    def hashes(self, table: pa.Table) -> np.ndarray:
        """uint32[N] murmur hashes."""
        # the numpy path's fixed setup (views, scratch, ~30 calls a
        # block) costs more than row-at-a-time hashing below ~10 rows —
        # point-lookup batches take the scalar codec path, ingest batches
        # the vectorized one; both produce identical reference hashes
        if self._fixed_width and table.num_rows > 8:
            return self._hash_vectorized(table)
        return self._hash_rows(table).astype(np.uint32)

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
