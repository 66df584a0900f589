"""Thrift compact protocol, schema-free: the varint primitives the
page-header parser (format/rawpage.py) reads with, and a whole-struct
reader / writer for Parquet footers (format/parquet_stitch.py).

A struct is a list of `[field id, wire type, value]` entries in wire
order, so that it can be edited in place and written back: booleans
live in the wire type (1 true, 2 false; value None), integers are
Python ints, a double its 8 raw bytes, a binary `bytes`, a list or set
`(element type, [values])`, a map `(key type, value type, [(k, v)])`,
a nested struct another such list.  Writing what was read gives the
bytes that were read: field headers take the short form wherever the
id delta fits (1..15) and lists wherever the size does (< 15), as
every Thrift writer does.
"""

from __future__ import annotations

from typing import List, Tuple

__all__ = ["varint", "zigzag", "read_struct", "write_struct", "field"]

_TRUE, _FALSE, _BYTE, _I16, _I32, _I64 = 1, 2, 3, 4, 5, 6
_DOUBLE, _BINARY, _LIST, _SET, _MAP, _STRUCT = 7, 8, 9, 10, 11, 12


def varint(buf, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, pos
        shift += 7


def zigzag(buf, pos: int) -> Tuple[int, int]:
    v, pos = varint(buf, pos)
    return (v >> 1) ^ -(v & 1), pos


def _read_value(buf, pos: int, ftype: int):
    if ftype in (_I16, _I32, _I64):
        return zigzag(buf, pos)
    if ftype == _BINARY:
        ln, pos = varint(buf, pos)
        return bytes(buf[pos:pos + ln]), pos + ln
    if ftype == _STRUCT:
        return read_struct(buf, pos)
    if ftype in (_LIST, _SET):
        head = buf[pos]
        pos += 1
        size, etype = head >> 4, head & 0x0F
        if size == 0x0F:
            size, pos = varint(buf, pos)
        items = []
        for _ in range(size):
            v, pos = _read_value(buf, pos, etype)
            items.append(v)
        return (etype, items), pos
    if ftype in (_TRUE, _FALSE, _BYTE):       # one byte inside a container
        return buf[pos], pos + 1
    if ftype == _DOUBLE:
        return bytes(buf[pos:pos + 8]), pos + 8
    if ftype == _MAP:
        size, pos = varint(buf, pos)
        if size == 0:
            return (0, 0, []), pos
        kv = buf[pos]
        pos += 1
        pairs = []
        for _ in range(size):
            k, pos = _read_value(buf, pos, kv >> 4)
            v, pos = _read_value(buf, pos, kv & 0x0F)
            pairs.append((k, v))
        return (kv >> 4, kv & 0x0F, pairs), pos
    raise ValueError(f"thrift compact type {ftype}")


def read_struct(buf, pos: int = 0) -> Tuple[List[list], int]:
    """One struct at `pos` of a bytes-like with unsigned items;
    returns (its fields, the position after its stop byte)."""
    fields: List[list] = []
    fid = 0
    while True:
        head = buf[pos]
        pos += 1
        if head == 0:
            return fields, pos
        ftype = head & 0x0F
        if head >> 4:
            fid += head >> 4
        else:
            fid, pos = zigzag(buf, pos)
        if ftype in (_TRUE, _FALSE):
            fields.append([fid, ftype, None])
            continue
        value, pos = _read_value(buf, pos, ftype)
        fields.append([fid, ftype, value])


def _write_varint(out: bytearray, v: int) -> None:
    while v > 0x7F:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)


def _write_value(out: bytearray, ftype: int, value) -> None:
    if ftype in (_I16, _I32, _I64):
        _write_varint(out, (value << 1) ^ (value >> 63))
    elif ftype == _BINARY:
        _write_varint(out, len(value))
        out += value
    elif ftype == _STRUCT:
        write_struct(out, value)
    elif ftype in (_LIST, _SET):
        etype, items = value
        if len(items) < 0x0F:
            out.append(len(items) << 4 | etype)
        else:
            out.append(0xF0 | etype)
            _write_varint(out, len(items))
        for v in items:
            _write_value(out, etype, v)
    elif ftype in (_TRUE, _FALSE, _BYTE):
        out.append(value)
    elif ftype == _DOUBLE:
        out += value
    elif ftype == _MAP:
        ktype, vtype, pairs = value
        _write_varint(out, len(pairs))
        if pairs:
            out.append(ktype << 4 | vtype)
            for k, v in pairs:
                _write_value(out, ktype, k)
                _write_value(out, vtype, v)
    else:
        raise ValueError(f"thrift compact type {ftype}")


def write_struct(out: bytearray, fields: List[list]) -> None:
    """Append `fields` (as `read_struct` returns them) and the stop
    byte to `out`."""
    last = 0
    for fid, ftype, value in fields:
        delta = fid - last
        if 0 < delta <= 15:
            out.append(delta << 4 | ftype)
        else:
            out.append(ftype)
            _write_varint(out, (fid << 1) ^ (fid >> 15))
        last = fid
        if ftype not in (_TRUE, _FALSE):
            _write_value(out, ftype, value)
    out.append(0)


def field(fields: List[list], fid: int):
    """The `[id, type, value]` entry of field `fid`, or None."""
    for f in fields:
        if f[0] == fid:
            return f
    return None
