"""Normalized keys: order-preserving fixed-width encoding of key columns.

The reference compares keys via codegen'd comparators over BinaryRow's
memcmp-comparable layout (paimon-common/.../codegen NormalizedKeyComputer,
sort/BinaryIndexedSortable). On TPU we need keys as fixed-width vector
lanes instead: each row's key becomes L uint32 lanes such that
lexicographic lane comparison == key comparison.

Encodings (all big-endian style, most-significant lane first):
- signed ints: value XOR sign bit -> unsigned of same width
- floats: IEEE total order trick (negative -> flip all bits, else flip
  sign bit)
- strings/bytes: first `prefix_bytes` bytes as big-endian lanes, zero
  padded; a `truncated` flag marks rows whose lanes do not determine the
  value — longer than the prefix, or ending in a zero byte the padding
  cannot tell from its own — so callers can resolve the rare
  prefix-equal ties (ops/merge.py `tiebreak_cut_keys`)
- date/time/timestamp: underlying ints

Null ordering: nulls-last via a dedicated leading presence LANE per
nullable column (0 = present, 1 = null), so a null is never byte-identical
to any real value (INT64_MAX, all-0xFF string prefixes). Columns declared
non-nullable (primary keys) skip the lane.
"""

from __future__ import annotations

import sys
from typing import List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

__all__ = ["NormalizedKeyEncoder"]


def _ints_to_u64(arr: np.ndarray) -> np.ndarray:
    """Signed int array -> order-preserving uint64."""
    a = arr.astype(np.int64, copy=False)
    return (a.view(np.uint64) ^ np.uint64(1 << 63))


def _floats_to_u64(arr: np.ndarray) -> np.ndarray:
    a = arr.astype(np.float64, copy=False)
    bits = a.view(np.uint64)
    neg = bits >> np.uint64(63) != 0
    out = np.where(neg, ~bits, bits ^ np.uint64(1 << 63))
    return out


def _split_u64(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    return ((x >> np.uint64(32)).astype(np.uint32),
            (x & np.uint64(0xFFFFFFFF)).astype(np.uint32))


def _int64_values(arr: pa.Array) -> pa.Array:
    """An "int" kind column as int64.  Arrow casts date32 and time32
    to their 32-bit storage only, so those go through it."""
    if pa.types.is_date32(arr.type) or pa.types.is_time32(arr.type):
        arr = arr.cast(pa.int32())
    return arr.cast(pa.int64())


def _fixed_u64(arr: pa.Array, kind: str) -> np.ndarray:
    """An "int" or "float" kind array's order-preserving uint64; a null
    reads as the value 0's."""
    cast = _int64_values(arr) if kind == "int" else arr.cast(pa.float64())
    # fill_null is a full copy at millions of rows: skip it for
    # null-free columns (the common pk case)
    if cast.null_count:
        cast = cast.fill_null(0)
    vals = np.asarray(cast)
    return _ints_to_u64(vals) if kind == "int" else _floats_to_u64(vals)


# where a 64-bit value's low and high 32-bit words lie in memory
_LO, _HI = (0, 1) if sys.byteorder == "little" else (1, 0)
_SIGN32 = np.uint32(1 << 31)


def _chunks(col) -> List[pa.Array]:
    return col.chunks if isinstance(col, pa.ChunkedArray) else [col]


def words64(values: np.ndarray) -> np.ndarray:
    """uint32[k, 2] view of k contiguous 64-bit values: no copy."""
    return values.view(np.uint32).reshape(-1, 2)


def _value_words(chunk: pa.Array) -> np.ndarray:
    """uint32[k, 2] view of a 64-bit-wide chunk's own values buffer,
    its offset honoured.  What lies under a null slot is arbitrary."""
    return np.frombuffer(chunk.buffers()[1], dtype=np.uint32,
                         count=2 * len(chunk),
                         offset=8 * chunk.offset).reshape(-1, 2)


def write_words(words: np.ndarray, hi: np.ndarray, lo: np.ndarray,
                flip_sign: bool = False) -> None:
    """The high and low words of `words` (uint32[k, 2], as `words64`
    gives them) into the planes `hi` and `lo` (uint32[k] each): one read
    and one write a word, no 64-bit temporary.  `flip_sign`: a signed
    value's order-preserving form (`_ints_to_u64`) is its high word with
    the sign bit flipped."""
    if flip_sign:
        np.bitwise_xor(words[:, _HI], _SIGN32, out=hi)
    else:
        hi[:] = words[:, _HI]
    lo[:] = words[:, _LO]


def write_int64_column(col, hi: np.ndarray, lo: np.ndarray,
                       flip_sign: bool = False) -> None:
    """A null-free int64 column's words into two planes, chunk by chunk
    from the chunks' own buffers (no `combine_chunks`)."""
    off = 0
    for chunk in _chunks(col):
        k = len(chunk)
        if k:
            write_words(_value_words(chunk), hi[off:off + k],
                        lo[off:off + k], flip_sign)
        off += k


class LazyPackedLanes:
    """[n, 2] u32 lane-matrix VIEW over a packed u64 key vector.

    The hot single-fixed-key paths (OVC merge, packed radix, the
    searchsorted window cut) sort the packed u64 and never read the
    lane matrix, so the encoder hands back this deferred view instead
    of paying a [n, 2] allocation + two strided column writes per
    chunk; np.asarray(...) materializes with a one-shot cache for the
    paths that do want lanes (device kernels, lexsort fallbacks)."""

    def __init__(self, packed: np.ndarray):
        self.packed = packed
        self.shape = (len(packed), 2)
        self._mat: Optional[np.ndarray] = None

    def _materialize(self) -> np.ndarray:
        if self._mat is None:
            hi, lo = _split_u64(self.packed)
            self._mat = np.stack([hi, lo], axis=1)
        return self._mat

    def __array__(self, dtype=None, copy=None):
        out = self._materialize()
        if dtype is not None:
            out = out.astype(dtype)
        if copy and out is self._mat:
            out = out.copy()
        return out

    def __len__(self) -> int:
        return self.shape[0]

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return LazyPackedLanes(self.packed[idx])
        return self._materialize()[idx]


class NormalizedKeyEncoder:
    """Encodes the key columns of Arrow batches into uint32 lane matrices."""

    def __init__(self, key_types: Sequence[pa.DataType],
                 string_prefix_bytes: int = 16,
                 nullable: Optional[Sequence[bool]] = None):
        self.key_types = list(key_types)
        self.string_prefix_bytes = ((string_prefix_bytes + 7) // 8) * 8
        self.nullable = (list(nullable) if nullable is not None
                         else [True] * len(self.key_types))
        assert len(self.nullable) == len(self.key_types)
        self.lanes_per_col: List[int] = []
        self._kinds: List[str] = []
        for t in self.key_types:
            if pa.types.is_integer(t) or pa.types.is_date(t) \
                    or pa.types.is_time(t) or pa.types.is_timestamp(t) \
                    or pa.types.is_boolean(t):
                self._kinds.append("int")
                self.lanes_per_col.append(2)
            elif pa.types.is_floating(t):
                self._kinds.append("float")
                self.lanes_per_col.append(2)
            elif pa.types.is_decimal(t):
                self._kinds.append("decimal")
                self.lanes_per_col.append(2)
            elif (pa.types.is_string(t) or pa.types.is_large_string(t)
                  or pa.types.is_binary(t) or pa.types.is_large_binary(t)):
                self._kinds.append("bytes")
                self.lanes_per_col.append(self.string_prefix_bytes // 4)
            else:
                raise ValueError(f"Unsupported key type {t}")
        # one leading presence lane per nullable column (0=value, 1=null)
        self.lanes_per_col = [
            nl + (1 if nul else 0)
            for nl, nul in zip(self.lanes_per_col, self.nullable)]

    @property
    def num_lanes(self) -> int:
        return sum(self.lanes_per_col)

    @property
    def fixed_width(self) -> bool:
        """Every key column is an integer, temporal, boolean or float:
        no key is ever cut to a prefix (`truncated` is all false by
        construction) and `encode_planes` can write the lanes."""
        return all(k in ("int", "float") for k in self._kinds)

    @property
    def bytes_columns(self) -> List[int]:
        """Positions of the string / binary key columns: the only ones
        whose lanes can leave a key undetermined (`truncated`)."""
        return [i for i, k in enumerate(self._kinds) if k == "bytes"]

    @property
    def cut_head_lanes(self) -> int:
        """Lanes up to and including the last string / binary column's.
        Ordered by these alone, rows are in the order of their full keys
        with ties: a key whose lanes are cut sorts among its equals
        here, and only the lanes after them can disagree with its full
        bytes."""
        cols = self.bytes_columns
        return sum(self.lanes_per_col[:cols[-1] + 1]) if cols else 0

    @property
    def packs_single_key(self) -> bool:
        """True when this encoder's keys pack into ONE u64 (single
        non-null fixed-width column — the hot pk shape): encode_*_ex
        then returns a LazyPackedLanes view and consumers may compare
        by the packed integer alone."""
        return (self.num_lanes == 2 and len(self.key_types) == 1
                and not self.nullable[0]
                and self._kinds[0] in ("int", "float"))

    def encode_columns(self, columns: Sequence[pa.ChunkedArray],
                       ) -> Tuple[np.ndarray, np.ndarray]:
        """-> (lanes uint32[N, num_lanes], truncated bool[N])."""
        lanes, truncated, _ = self.encode_columns_ex(columns)
        return np.asarray(lanes), truncated

    def encode_columns_ex(self, columns: Sequence[pa.ChunkedArray],
                          ) -> Tuple[np.ndarray, np.ndarray,
                                     Optional[np.ndarray]]:
        """-> (lanes, truncated, packed): like encode_columns, plus the
        u64 packed normalized key when the key is a single two-lane
        fixed-width non-null column (the hot pk shape) — the host merge
        fast path then sorts the u64 we already computed instead of
        re-packing the lanes (3 temporaries saved at bucket scale)."""
        assert len(columns) == len(self.key_types)
        n = len(columns[0]) if columns else 0
        if self.packs_single_key and n > 0:
            # hot pk shape: ONLY the packed u64 is computed; the [n, 2]
            # lane matrix is a deferred view most consumers never touch
            arr = columns[0]
            arr = arr.combine_chunks() \
                if isinstance(arr, pa.ChunkedArray) else arr
            if arr.null_count:
                raise ValueError(
                    "null value in a key column declared NOT NULL")
            u = _fixed_u64(arr, self._kinds[0])
            return LazyPackedLanes(u), np.zeros(n, dtype=bool), u
        lanes = np.zeros((n, self.num_lanes), dtype=np.uint32)
        truncated = np.zeros(n, dtype=bool)
        packed: Optional[np.ndarray] = None
        want_packed = (self.num_lanes == 2 and len(columns) == 1
                       and not self.nullable[0]
                       and self._kinds[0] in ("int", "float", "decimal"))
        lane_pos = 0
        for col, kind, total_nl, t, nul in zip(
                columns, self._kinds, self.lanes_per_col, self.key_types,
                self.nullable):
            arr = col.combine_chunks() if isinstance(col, pa.ChunkedArray) \
                else col
            # null_count is O(1) metadata: null-free columns (the
            # common pk case) skip materializing a per-row mask
            has_nulls = bool(arr.null_count)
            null_mask = np.asarray(arr.is_null()) if has_nulls \
                else np.zeros(n, dtype=bool)
            if nul:
                if has_nulls:
                    lanes[:, lane_pos] = null_mask.astype(np.uint32)
                lane_pos += 1
                nl = total_nl - 1
            else:
                if has_nulls:
                    raise ValueError(
                        "null value in a key column declared NOT NULL")
                nl = total_nl
            if kind in ("int", "float"):
                u = _fixed_u64(arr, kind)
                if want_packed:
                    packed = u
                hi, lo = _split_u64(u)
                lanes[:, lane_pos] = hi
                lanes[:, lane_pos + 1] = lo
            elif kind == "decimal":
                # scale-preserving: compare by unscaled value (same scale
                # within a column)
                vals = np.array(
                    [0 if v is None else int(v.scaleb(t.scale))
                     for v in arr.to_pylist()], dtype=np.int64)
                u = _ints_to_u64(vals)
                if want_packed:
                    packed = u
                hi, lo = _split_u64(u)
                lanes[:, lane_pos] = hi
                lanes[:, lane_pos + 1] = lo
            else:  # bytes
                trunc_col = self._encode_bytes(arr, lanes, lane_pos, nl)
                truncated |= trunc_col & ~null_mask
            if null_mask.any():
                # value lanes of null rows are zeroed (presence lane alone
                # decides the order; any residue from fill_null is wiped)
                lanes[null_mask, lane_pos:lane_pos + nl] = np.uint32(0)
            lane_pos += nl
        return lanes, truncated, packed

    def encode_planes(self, columns: Sequence[pa.ChunkedArray],
                      planes: Sequence[np.ndarray]) -> None:
        """The lanes of `encode_columns`, transposed and written once:
        lane i of row r into `planes[i][r]` (num_lanes arrays of
        uint32[>= N], zero on entry), chunk by chunk from the chunks'
        own buffers.
        `fixed_width` encoders only.  A 64-bit integer or temporal
        chunk goes word by word from its values buffer; any other gets
        a chunk-sized temporary of its order-preserving uint64."""
        assert self.fixed_width and len(columns) == len(self.key_types)
        lane = 0
        for col, kind, nul in zip(columns, self._kinds, self.nullable):
            presence = None
            if nul:
                presence = planes[lane]
                lane += 1
            hi, lo = planes[lane], planes[lane + 1]
            lane += 2
            off = 0
            for chunk in _chunks(col):
                k = len(chunk)
                if k == 0:
                    continue
                end = off + k
                has_nulls = bool(chunk.null_count)
                if has_nulls and not nul:
                    raise ValueError(
                        "null value in a key column declared NOT NULL")
                ct = chunk.type
                if kind == "int" and (
                        pa.types.is_int64(ct) or pa.types.is_timestamp(ct)
                        or pa.types.is_date64(ct) or pa.types.is_time64(ct)):
                    write_words(_value_words(chunk), hi[off:end],
                                lo[off:end], flip_sign=True)
                else:
                    write_words(words64(_fixed_u64(chunk, kind)),
                                hi[off:end], lo[off:end])
                if has_nulls:
                    # as encode_columns_ex: the presence lane alone
                    # orders a null, its value words are zero
                    null_mask = np.asarray(chunk.is_null())
                    presence[off:end] = null_mask
                    hi[off:end][null_mask] = 0
                    lo[off:end][null_mask] = 0
                off = end

    def _encode_bytes(self, arr: pa.Array, lanes: np.ndarray, lane_pos: int,
                      nl: int) -> np.ndarray:
        pb = self.string_prefix_bytes
        if pa.types.is_string(arr.type) or pa.types.is_large_string(arr.type):
            arr = arr.cast(pa.binary())
        arr = arr.cast(pa.large_binary())
        # vectorized: buffer + offsets
        arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
        offsets = np.asarray(arr.buffers()[1]).view(np.int64)
        data = np.frombuffer(arr.buffers()[2], dtype=np.uint8) \
            if arr.buffers()[2] is not None else np.zeros(0, np.uint8)
        n = len(arr)
        starts = offsets[:-1]
        ends = offsets[1:]
        lengths = ends - starts
        truncated = lengths > pb
        # b"ab" and b"ab\0" pad to the same lanes: the longer of two such
        # keys ends in a zero byte, so marking those tells them apart
        ends_in_zero = lengths > 0
        if len(data):
            ends_in_zero &= data[np.maximum(ends - 1, 0)] == 0
        else:
            ends_in_zero[:] = False
        truncated |= ends_in_zero
        # gather first pb bytes of each value, zero-padded
        take = np.minimum(lengths, pb)
        padded = np.zeros((n, pb), dtype=np.uint8)
        # index matrix trick: for each row, positions starts[i]..starts[i]+take[i]
        col_idx = np.arange(pb)[None, :]
        src_idx = starts[:, None] + col_idx
        valid = col_idx < take[:, None]
        src_idx = np.where(valid, src_idx, 0)
        if len(data):
            padded = np.where(valid, data[src_idx], 0).astype(np.uint8)
        # big-endian u32 lanes
        as_u32 = padded.reshape(n, pb // 4, 4)
        lanes_col = (as_u32[:, :, 0].astype(np.uint32) << 24) | \
                    (as_u32[:, :, 1].astype(np.uint32) << 16) | \
                    (as_u32[:, :, 2].astype(np.uint32) << 8) | \
                    as_u32[:, :, 3].astype(np.uint32)
        lanes[:, lane_pos:lane_pos + nl] = lanes_col
        return truncated

    def encode_table(self, table: pa.Table,
                     key_names: Sequence[str]) -> Tuple[np.ndarray,
                                                        np.ndarray]:
        cols = [table.column(n) for n in key_names]
        return self.encode_columns(cols)

    def encode_table_ex(self, table: pa.Table,
                        key_names: Sequence[str]
                        ) -> Tuple[np.ndarray, np.ndarray,
                                   Optional[np.ndarray]]:
        cols = [table.column(n) for n in key_names]
        return self.encode_columns_ex(cols)
