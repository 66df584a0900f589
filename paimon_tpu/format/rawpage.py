"""Raw Parquet page reader: undecoded column chunks -> device decode.

The pyarrow read path decodes pages on the host and hands Arrow arrays
to the merge plane, which re-encodes keys into normalized lanes before
any kernel runs.  This reader moves the per-value work onto the device
(ops/decode.py): the parquet FOOTER (already cached process-wide by
read.cache.footer) locates each column chunk, the chunk's raw bytes
are sliced through ``FileIO.read_ranges`` — riding the block-range
cache, SSD tier, hedging and retry ladders for free — and the only
host work left is page-header/run-header parsing (a few dozen thrift
varints per page) and codec decompression.  Every per-value transform
(RLE/bit-packed level expansion, dictionary index gather, PLAIN
fixed-width reinterpret, null scatter) is a traced JAX op.

Coverage is deliberately the hot-path subset: flat columns
(max_repetition_level == 0), physical INT32/INT64/FLOAT/DOUBLE, v1
data pages, PLAIN and RLE/PLAIN-dictionary value encodings, RLE
definition levels, UNCOMPRESSED/SNAPPY/GZIP/ZSTD codecs.  Anything
else raises ``DeviceDecodeUnsupported`` and the caller falls back to
the pyarrow path (core/read.py gates on ``read.device-decode``);
results are byte-identical to pyarrow by the oracle test suite.

Two kinds of step alternate below and are kept apart: HOST PARSE of
file bytes (page/run headers, padding) runs inside ``_parsing()``, which
turns whatever the hand-rolled parsers raise on byte shapes they never
anticipated into ``DeviceDecodeUnsupported``; DEVICE TRANSFORMS
(ops/decode.py) run outside it, so an error JAX raises for a lowering
or a compile it cannot do fails the read instead of passing for an
uncovered file.
"""

from __future__ import annotations

import contextlib
import io
import struct
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from paimon_tpu.format.thrift import varint as _varint, zigzag as _zigzag
from paimon_tpu.fs import FileIO

__all__ = ["DeviceDecodeUnsupported", "read_parquet_device",
           "device_decode_supported", "parse_page_header",
           "parse_rle_runs"]

# parquet-format enums (format/src/main/thrift/parquet.thrift)
_ENC_PLAIN = 0
_ENC_PLAIN_DICT = 2
_ENC_RLE = 3
_ENC_RLE_DICT = 8
_PAGE_DATA = 0
_PAGE_DICT = 2
_PAGE_DATA_V2 = 3

_PHYS_WIDTH = {"INT32": 4, "INT64": 8, "FLOAT": 4, "DOUBLE": 8}
_CODECS = {"UNCOMPRESSED", "SNAPPY", "GZIP", "ZSTD"}
# footer-declared chunk encodings inside coverage; anything else
# (DELTA_*, BYTE_STREAM_SPLIT, legacy BIT_PACKED levels) pre-falls-back
# from the footer alone, before any data byte is fetched
_ENCODINGS = {"PLAIN", "RLE", "PLAIN_DICTIONARY", "RLE_DICTIONARY"}


class DeviceDecodeUnsupported(Exception):
    """This file/column needs an encoding, codec or shape outside the
    device decode plane's coverage; the caller takes the pyarrow host
    path (never an error surfaced to users)."""


# what the hand-rolled thrift/page/run-header parsers raise on byte
# shapes they never anticipated (truncated varints, absent header
# fields) — the host reader is the arbiter of whether such a file is
# readable or genuinely corrupt
_PARSE_ERRORS = (IndexError, KeyError, TypeError, ValueError,
                 struct.error)


@contextlib.contextmanager
def _parsing():
    """Scope of one host-side parse step over file bytes.  No device
    transform may run inside it: JAX raises the same TypeError /
    ValueError classes for a lowering it cannot do."""
    try:
        yield
    except _PARSE_ERRORS as e:
        raise DeviceDecodeUnsupported(
            f"unparseable page bytes: {e!r}") from e


# ---------------------------------------------------------------------------
# thrift compact protocol (page headers only — footers come from the
# cached pyarrow FileMetaData)
# ---------------------------------------------------------------------------


def _skip(buf: bytes, pos: int, ftype: int) -> int:
    if ftype in (1, 2):                       # bool encoded in header
        return pos
    if ftype == 3:                            # i8
        return pos + 1
    if ftype in (4, 5, 6):                    # i16/i32/i64 zigzag
        return _zigzag(buf, pos)[1]
    if ftype == 7:                            # double
        return pos + 8
    if ftype == 8:                            # binary
        ln, pos = _varint(buf, pos)
        return pos + ln
    if ftype in (9, 10):                      # list/set
        head = buf[pos]
        pos += 1
        size, etype = head >> 4, head & 0x0F
        if size == 0x0F:
            size, pos = _varint(buf, pos)
        for _ in range(size):
            pos = _skip(buf, pos, etype)
        return pos
    if ftype == 11:                           # map
        size, pos = _varint(buf, pos)
        if size == 0:
            return pos
        kv = buf[pos]
        pos += 1
        for _ in range(size):
            pos = _skip(buf, pos, kv >> 4)
            pos = _skip(buf, pos, kv & 0x0F)
        return pos
    if ftype == 12:                           # struct
        _, pos = _compact_struct(buf, pos, keep=())
        return pos
    raise DeviceDecodeUnsupported(f"thrift compact type {ftype}")


def _compact_struct(buf: bytes, pos: int,
                    keep: Sequence[int],
                    structs: Dict[int, Sequence[int]] = {},
                    ) -> Tuple[Dict[int, object], int]:
    """Walk one compact-protocol struct, returning {field id: value}
    for scalar fields in `keep` and nested structs in `structs`
    (mapping field id -> that struct's keep list); everything else is
    skipped."""
    out: Dict[int, object] = {}
    fid = 0
    while True:
        head = buf[pos]
        pos += 1
        if head == 0:
            return out, pos
        delta = head >> 4
        ftype = head & 0x0F
        if delta:
            fid += delta
        else:
            fid, pos = _zigzag(buf, pos)
        if ftype in (1, 2):
            if fid in keep:
                out[fid] = ftype == 1
            continue
        if fid in structs and ftype == 12:
            out[fid], pos = _compact_struct(buf, pos,
                                            keep=structs[fid])
            continue
        if fid in keep and ftype in (4, 5, 6):
            v, pos = _zigzag(buf, pos)
            out[fid] = v
            continue
        pos = _skip(buf, pos, ftype)


def parse_page_header(buf: bytes, pos: int) -> Tuple[Dict, int]:
    """Parse one thrift-compact PageHeader at `pos`; returns (header
    dict, payload start).  Keys: type, uncompressed/compressed sizes,
    plus the nested data/dictionary page headers that matter here."""
    fields, pos = _compact_struct(
        buf, pos, keep=(1, 2, 3),
        structs={5: (1, 2, 3, 4),       # DataPageHeader
                 7: (1, 2, 3),          # DictionaryPageHeader
                 8: (1, 2, 3, 4, 5, 6, 7)})   # DataPageHeaderV2
    hdr = {
        "type": fields.get(1),
        "uncompressed_size": fields.get(2),
        "compressed_size": fields.get(3),
        "data": fields.get(5),
        "dict": fields.get(7),
        "data_v2": fields.get(8),
    }
    return hdr, pos


# ---------------------------------------------------------------------------
# RLE/bit-packed hybrid run headers (host side: a handful of varints)
# ---------------------------------------------------------------------------


def parse_rle_runs(buf: bytes, bit_width: int, count: int,
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                              np.ndarray]:
    """Parse the run HEADERS of an RLE/bit-packed hybrid stream over
    `buf` (values start at offset 0) into per-run descriptor arrays for
    ops/decode.expand_rle_hybrid: (is_packed u32[R], value u32[R],
    cum-counts i32[R] inclusive, bit-start i32[R])."""
    is_packed: List[int] = []
    value: List[int] = []
    cum: List[int] = []
    bit_start: List[int] = []
    pos = 0
    total = 0
    vbytes = (bit_width + 7) // 8
    while total < count:
        if pos >= len(buf):
            raise DeviceDecodeUnsupported("truncated RLE stream")
        header, pos = _varint(buf, pos)
        if header & 1:
            groups = header >> 1
            n = groups * 8
            is_packed.append(1)
            value.append(0)
            bit_start.append(pos * 8)
            pos += groups * bit_width
        else:
            n = header >> 1
            v = int.from_bytes(buf[pos:pos + vbytes], "little") \
                if vbytes else 0
            pos += vbytes
            is_packed.append(0)
            value.append(v)
            bit_start.append(0)
        total += n
        cum.append(min(total, count))
    if not cum:
        raise DeviceDecodeUnsupported("empty RLE stream")
    return (np.asarray(is_packed, np.uint32),
            np.asarray(value, np.uint32),
            np.asarray(cum, np.int32),
            np.asarray(bit_start, np.int32))


# ---------------------------------------------------------------------------
# jitted per-page decode entries (padded shapes -> stable compile cache)
# ---------------------------------------------------------------------------


def _pad_bytes_u32(data: bytes) -> np.ndarray:
    """Page bytes -> little-endian u32 word array with one word of
    slack (unpack_bits reads a two-word window) padded to a pow2."""
    from paimon_tpu.ops.decode import pad_pow2
    n_words = len(data) // 4 + 2
    padded = pad_pow2(n_words, floor=256)
    buf = np.zeros(padded * 4, dtype=np.uint8)
    buf[:len(data)] = np.frombuffer(data, np.uint8)
    return buf.view(np.uint32)


def _pad_u8(data: bytes, floor: int = 1024) -> np.ndarray:
    from paimon_tpu.ops.decode import pad_pow2
    buf = np.zeros(pad_pow2(len(data), floor=floor), dtype=np.uint8)
    buf[:len(data)] = np.frombuffer(data, np.uint8)
    return buf


def _pad_runs(runs: Tuple[np.ndarray, ...]) -> Tuple[np.ndarray, ...]:
    """Pad run-descriptor arrays to a pow2 length; padding runs repeat
    the last cumulative count, so searchsorted never selects them."""
    from paimon_tpu.ops.decode import pad_pow2
    is_packed, value, cum, bit_start = runs
    r = len(cum)
    rp = pad_pow2(r, floor=8)
    pad = rp - r

    def ext(a, fill):
        return np.concatenate([a, np.full(pad, fill, a.dtype)]) \
            if pad else a
    return (ext(is_packed, 0), ext(value, 0), ext(cum, cum[-1]),
            ext(bit_start, 0))


def _decode_rle_values(buf: bytes, bit_width: int,
                       count: int) -> np.ndarray:
    """Full RLE/bit-packed hybrid decode: host run headers + device
    expansion.  Returns uint32[count]."""
    import jax.numpy as jnp

    from paimon_tpu.ops.decode import expand_rle_hybrid, pad_pow2
    with _parsing():
        runs = _pad_runs(parse_rle_runs(buf, bit_width, count))
        words = _pad_bytes_u32(buf)
    padded_count = pad_pow2(count)
    out = expand_rle_hybrid(jnp.asarray(words),
                            jnp.asarray(runs[0]), jnp.asarray(runs[1]),
                            jnp.asarray(runs[2]), jnp.asarray(runs[3]),
                            bit_width, padded_count)
    return np.asarray(out)[:count]


def _decode_plain_values(data: bytes, phys: str,
                         count: int) -> np.ndarray:
    """PLAIN fixed-width page payload -> device reinterpret ->
    numpy raw-bits array (u32 or u64)."""
    import jax.numpy as jnp

    from paimon_tpu.ops.decode import (pad_pow2, plain_to_u32,
                                       plain_to_u64)
    width = _PHYS_WIDTH[phys]
    padded_count = pad_pow2(count)
    with _parsing():
        if len(data) < width * count:
            raise DeviceDecodeUnsupported(
                "PLAIN page shorter than values")
        buf = _pad_u8(data, floor=padded_count * width)
        if len(buf) < padded_count * width:
            buf = np.concatenate(
                [buf,
                 np.zeros(padded_count * width - len(buf), np.uint8)])
    fn = plain_to_u64 if width == 8 else plain_to_u32
    out = fn(jnp.asarray(buf), padded_count)
    return np.asarray(out)[:count]


# ---------------------------------------------------------------------------
# footer access (rides the process footer cache)
# ---------------------------------------------------------------------------


class _TailFile(io.RawIOBase):
    """Seekable file view for pq.read_metadata backed by the already-
    fetched tail bytes, falling back to ranged reads for anything
    outside the tail (wide schemas whose footer exceeds the probe)."""

    def __init__(self, file_io: FileIO, path: str, size: int,
                 tail: bytes):
        self._io = file_io
        self._path = path
        self._size = size
        self._tail = tail
        self._pos = 0

    def seekable(self) -> bool:
        return True

    def readable(self) -> bool:
        return True

    def seek(self, offset: int, whence: int = 0) -> int:
        if whence == 0:
            self._pos = offset
        elif whence == 1:
            self._pos += offset
        else:
            self._pos = self._size + offset
        return self._pos

    def tell(self) -> int:
        return self._pos

    def read(self, n: int = -1) -> bytes:
        if n < 0:
            n = self._size - self._pos
        start = self._pos
        tail_start = self._size - len(self._tail)
        if start >= tail_start:
            off = start - tail_start
            out = self._tail[off:off + n]
        else:
            out = self._io.read_range(self._path, start, n)
        self._pos = start + len(out)
        return out


def _footer_metadata(file_io: FileIO, path: str, options=None):
    """Parsed parquet FileMetaData for `path`, via the process footer
    cache (fs/caching.py) when the table allows it; a miss reads only
    the footer bytes through ranged reads, never the whole file."""
    from paimon_tpu.fs.caching import footer_cache_scope, \
        global_footer_cache
    with footer_cache_scope(options):
        cache = global_footer_cache()
        md = cache.get(path)
        if md is not None:
            return md
        size = file_io.get_file_size(path)
        probe = min(size, 1 << 16)
        tail = file_io.read_range(path, size - probe, probe)
        if len(tail) < 8 or tail[-4:] != b"PAR1":
            raise DeviceDecodeUnsupported(f"not a parquet file: {path}")
        footer_len = struct.unpack("<I", tail[-8:-4])[0]
        if footer_len + 8 > probe:
            tail = file_io.read_range(path, size - footer_len - 8,
                                      footer_len + 8)
        md = pq.read_metadata(_TailFile(file_io, path, size, tail))
        cache.put(path, md)
        return md


# ---------------------------------------------------------------------------
# column-chunk decode
# ---------------------------------------------------------------------------


def _decompress(data: bytes, codec: str, uncompressed: int) -> bytes:
    if codec == "UNCOMPRESSED":
        return data
    return pa.Codec(codec.lower()).decompress(
        data, decompressed_size=uncompressed).to_pybytes()


def _next_page(data: bytes, pos: int, codec: str
               ) -> Tuple[Dict, bytes, int]:
    """HOST: parse the page header at `pos` and decompress its payload.
    Returns (header, page bytes, position past the page)."""
    with _parsing():
        if pos >= len(data):
            raise DeviceDecodeUnsupported("column chunk truncated")
        hdr, body = parse_page_header(data, pos)
        end = body + hdr["compressed_size"]
        payload = data[body:end]
        if hdr["type"] == _PAGE_DATA_V2:
            raise DeviceDecodeUnsupported("v2 data page")
        if hdr["type"] == _PAGE_DATA and hdr["data"] is None:
            raise DeviceDecodeUnsupported("data page without header")
        if hdr["type"] not in (_PAGE_DICT, _PAGE_DATA):
            return hdr, b"", end              # index pages etc.: skipped
        return hdr, _decompress(payload, codec,
                                hdr["uncompressed_size"]), end


def _decode_chunk(data: bytes, col_meta, max_def: int,
                  ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """One column chunk's pages -> (raw-bits values with zeros at null
    slots, present mask or None).  Dict pages decode PLAIN on device;
    data pages expand levels + indices on device."""
    import jax.numpy as jnp

    from paimon_tpu.ops.decode import dict_gather, expand_nulls, \
        pad_pow2
    phys = col_meta.physical_type
    codec = col_meta.compression
    total = col_meta.num_values
    pos = 0
    dict_vals = None
    out_parts: List[np.ndarray] = []
    mask_parts: List[np.ndarray] = []
    seen = 0
    while seen < total:
        hdr, page, pos = _next_page(data, pos, codec)
        ptype = hdr["type"]
        if ptype == _PAGE_DICT:
            with _parsing():
                dhdr = hdr["dict"] or {}
                if dhdr.get(2, _ENC_PLAIN) not in (_ENC_PLAIN,
                                                   _ENC_PLAIN_DICT):
                    raise DeviceDecodeUnsupported("non-PLAIN dictionary")
                dict_count = dhdr.get(1, 0)
            dict_vals = _decode_plain_values(page, phys, dict_count)
            continue
        if ptype != _PAGE_DATA:
            continue
        with _parsing():
            dh = hdr["data"]
            nvals = dh.get(1, 0)
            enc = dh.get(2, _ENC_PLAIN)
            if enc not in (_ENC_PLAIN, _ENC_PLAIN_DICT, _ENC_RLE_DICT):
                raise DeviceDecodeUnsupported(f"value encoding {enc}")
            off = 0
            level_bytes = None
            if max_def > 0:
                if dh.get(3, _ENC_RLE) != _ENC_RLE:
                    raise DeviceDecodeUnsupported("non-RLE def levels")
                dlen = struct.unpack("<I", page[off:off + 4])[0]
                off += 4
                level_bytes = page[off:off + dlen]
                off += dlen
        present = None
        n_present = nvals
        if level_bytes is not None:
            levels = _decode_rle_values(level_bytes,
                                        max_def.bit_length(), nvals)
            present = levels == max_def
            n_present = int(present.sum())
        if enc == _ENC_PLAIN:
            vals = _decode_plain_values(page[off:], phys, n_present)
        else:
            if dict_vals is None:
                raise DeviceDecodeUnsupported("dict page missing")
            if n_present:
                with _parsing():
                    bw = page[off]
                idx = _decode_rle_values(page[off + 1:], bw, n_present)
                vals = np.asarray(dict_gather(
                    jnp.asarray(dict_vals), jnp.asarray(idx)))
            else:
                vals = dict_vals[:0]
        if present is not None and n_present != nvals:
            padded = pad_pow2(nvals)
            vp = np.zeros(padded, vals.dtype)
            vp[:n_present] = vals
            pp = np.zeros(padded, bool)
            pp[:nvals] = present
            full, _ = expand_nulls(jnp.asarray(vp), jnp.asarray(pp))
            vals = np.asarray(full)[:nvals]
        out_parts.append(vals)
        mask_parts.append(present if present is not None
                          else np.ones(nvals, bool))
        seen += nvals
    if not out_parts:
        width = _PHYS_WIDTH[phys]
        empty = np.zeros(0, np.uint64 if width == 8 else np.uint32)
        return empty, np.zeros(0, bool)
    values = np.concatenate(out_parts) if len(out_parts) > 1 \
        else out_parts[0]
    mask = np.concatenate(mask_parts) if len(mask_parts) > 1 \
        else mask_parts[0]
    return values, (None if mask.all() else mask)


def _arrow_array(values: np.ndarray, mask: Optional[np.ndarray],
                 field_type: pa.DataType) -> pa.Array:
    """Raw-bits values + presence mask -> Arrow array of the footer
    schema's type, zero-copy via from_buffers."""
    n = len(values)
    phys_bits = values.dtype.itemsize * 8
    if field_type.bit_width != phys_bits:
        if pa.types.is_integer(field_type) \
                and field_type.bit_width < phys_bits:
            # INT(8/16) logical types store sign-extended in INT32:
            # truncating cast recovers the narrow value exactly
            signed = values.view(np.int32 if phys_bits == 32
                                 else np.int64)
            values = signed.astype(field_type.to_pandas_dtype())
        else:
            raise DeviceDecodeUnsupported(
                f"arrow {field_type} vs physical width {phys_bits}")
    validity = None
    null_count = 0
    if mask is not None:
        null_count = int(n - mask.sum())
        validity = pa.py_buffer(
            np.packbits(mask, bitorder="little").tobytes())
    return pa.Array.from_buffers(
        field_type, n,
        [validity, pa.py_buffer(np.ascontiguousarray(values))],
        null_count=null_count)


def device_decode_supported(md, columns: Sequence[str]) -> bool:
    """Cheap pre-check (footer only) that every requested column is
    inside the decode plane's coverage."""
    try:
        _check_supported(md, columns)
        return True
    except DeviceDecodeUnsupported:
        return False


def _check_supported(md, columns: Sequence[str]) -> Dict[str, int]:
    schema = md.schema
    by_name = {schema.column(i).name: i
               for i in range(len(schema.names))}
    out = {}
    for name in columns:
        ci = by_name.get(name)
        if ci is None:
            raise DeviceDecodeUnsupported(f"no flat column {name!r}")
        col_schema = schema.column(ci)
        if col_schema.max_repetition_level != 0:
            raise DeviceDecodeUnsupported(f"nested column {name!r}")
        if col_schema.physical_type not in _PHYS_WIDTH:
            raise DeviceDecodeUnsupported(
                f"physical type {col_schema.physical_type}")
        for rg in range(md.num_row_groups):
            cm = md.row_group(rg).column(ci)
            if cm.compression not in _CODECS:
                raise DeviceDecodeUnsupported(
                    f"codec {cm.compression}")
            unknown = set(cm.encodings) - _ENCODINGS
            if unknown:
                raise DeviceDecodeUnsupported(
                    f"encodings {sorted(unknown)} in {name!r}")
        out[name] = ci
    return out


def maybe_read_device(file_io: FileIO, path: str,
                      projection: Optional[List[str]] = None,
                      options=None) -> Optional[pa.Table]:
    """read_parquet_device, or None when the file needs the pyarrow
    host path (fallback counted in the scan metric group).  Only the
    typed coverage signal falls back; a device transform's own error
    propagates."""
    try:
        return read_parquet_device(file_io, path, projection, options)
    except DeviceDecodeUnsupported:
        from paimon_tpu.metrics import SCAN_DEVICE_DECODE_FALLBACKS, \
            global_registry
        global_registry().group("scan").counter(
            SCAN_DEVICE_DECODE_FALLBACKS).inc()
        return None


def read_parquet_device(file_io: FileIO, path: str,
                        projection: Optional[List[str]] = None,
                        options=None,
                        row_groups: Optional[Sequence[int]] = None
                        ) -> pa.Table:
    """Read a parquet file through the device decode plane; byte-
    identical to the pyarrow reader for covered files, raises
    DeviceDecodeUnsupported otherwise (caller falls back).
    `row_groups` restricts the read (the streamed-compaction batch
    iterator reads one group at a time to keep its memory bound)."""
    with _parsing():
        md = _footer_metadata(file_io, path, options)
        arrow_schema = md.schema.to_arrow_schema()
        names = list(projection) if projection \
            else list(arrow_schema.names)
        col_idx = _check_supported(md, names)
        groups = list(row_groups) if row_groups is not None \
            else list(range(md.num_row_groups))

        # one ranged read per (row group, column) chunk, all batched
        # into a single read_ranges call (block-range cache / SSD tier
        # / hedging)
        ranges: List[Tuple[int, int]] = []
        keys: List[Tuple[int, str]] = []
        for rg in groups:
            for name in names:
                cm = md.row_group(rg).column(col_idx[name])
                start = cm.data_page_offset
                if cm.dictionary_page_offset is not None:
                    start = min(start, cm.dictionary_page_offset)
                ranges.append((start, cm.total_compressed_size))
                keys.append((rg, name))
    blobs = file_io.read_ranges(path, ranges) if ranges else []
    chunks = dict(zip(keys, blobs))

    from paimon_tpu.metrics import SCAN_DEVICE_DECODE_FILES, \
        global_registry
    arrays: Dict[str, List[pa.Array]] = {n: [] for n in names}
    for rg in groups:
        for name in names:
            cm = md.row_group(rg).column(col_idx[name])
            schema_col = md.schema.column(col_idx[name])
            values, mask = _decode_chunk(
                chunks[(rg, name)], cm,
                schema_col.max_definition_level)
            with _parsing():
                arrays[name].append(_arrow_array(
                    values, mask, arrow_schema.field(name).type))
    cols = {n: pa.chunked_array(arrays[n],
                                type=arrow_schema.field(n).type)
            for n in names}
    out = pa.table(
        [cols[n] for n in names],
        schema=pa.schema([arrow_schema.field(n) for n in names]))
    if row_groups is None:                  # partial reads count once,
        global_registry().group("scan").counter(   # in the iterator
            SCAN_DEVICE_DECODE_FILES).inc()
    return out


def iter_batches_device(file_io: FileIO, path: str,
                        batch_rows: int,
                        options=None):
    """Streamed device-decode: yields the file as bounded Arrow tables,
    decoding and FETCHING one row group at a time — the streamed
    compaction rewriters' memory bound (~runs x chunk rows) holds with
    device decode exactly as it does on the pyarrow iter_batches path.
    Raises DeviceDecodeUnsupported before yielding anything when the
    file is outside coverage (checked from the footer alone)."""
    with _parsing():
        md = _footer_metadata(file_io, path, options)
        names = list(md.schema.to_arrow_schema().names)
        _check_supported(md, names)        # EAGER: before any yield
    return _iter_batches_device(file_io, path, batch_rows, options, md)


def _iter_batches_device(file_io, path, batch_rows, options, md):
    from paimon_tpu.metrics import SCAN_DEVICE_DECODE_FALLBACKS, \
        SCAN_DEVICE_DECODE_FILES, global_registry
    global_registry().group("scan").counter(
        SCAN_DEVICE_DECODE_FILES).inc()
    for rg in range(md.num_row_groups):
        try:
            t = read_parquet_device(file_io, path, options=options,
                                    row_groups=[rg])
        except DeviceDecodeUnsupported:
            # a page shape the footer cannot reveal (v2 data pages,
            # odd in-page encodings): the REMAINING row groups decode
            # through pyarrow — earlier groups already yielded the
            # identical rows, so the stream stays seamless
            global_registry().group("scan").counter(
                SCAN_DEVICE_DECODE_FALLBACKS).inc()
            data = file_io.read_bytes(path)
            pf = pq.ParquetFile(io.BytesIO(data), metadata=md)
            for rb in pf.iter_batches(
                    batch_size=batch_rows,
                    row_groups=list(range(rg, md.num_row_groups))):
                yield pa.Table.from_batches([rb])
            return
        for start in range(0, t.num_rows, batch_rows):
            yield t.slice(start, batch_rows)
