"""The plain reference and the comparison that decides `correct`.

Numpy only, independent of `paimon_tpu` (copied from chip_smoke.py
`_reference`, `_bits`, `_check_table`).
"""

from __future__ import annotations

import numpy as np


class Mismatch(Exception):
    """A result differs from the reference."""


def merged(cols: dict, engine: str) -> dict:
    """Stable sort by key (arrival order breaks ties, so later commits
    come later), then last-by-arrival (deduplicate) or sum(v1) / max(v2)
    / max(v3) (aggregation).  Sorted by key."""
    order = np.argsort(cols["id"], kind="stable")
    sid = cols["id"][order]
    if engine == "deduplicate":
        last = np.concatenate([sid[1:] != sid[:-1], [True]])
        win = order[last]
        return {k: v[win] for k, v in cols.items()}
    if engine != "aggregation":
        raise ValueError(f"no reference for merge engine {engine!r}")
    starts = np.flatnonzero(np.concatenate([[True], sid[1:] != sid[:-1]]))
    return {"id": sid[starts],
            "v1": np.add.reduceat(cols["v1"][order], starts),
            "v2": np.maximum.reduceat(cols["v2"][order], starts),
            "v3": np.maximum.reduceat(cols["v3"][order], starts)}


def _bits(a):
    """Floats compare by their bits: equal means identical."""
    return a.view(np.uint64) if a.dtype == np.float64 else a


def _bit_sum(a) -> int:
    return int(_bits(a).astype(np.uint64, copy=False).sum(dtype=np.uint64))


def checksum(cols: dict) -> dict:
    """Row count and, per column, the sum of the values' bits modulo
    2**64: independent of row order, so it needs no sort."""
    out = {"rows": len(cols["id"])}
    for name, a in cols.items():
        out[name] = _bit_sum(a)
    return out


def table_checksum(arrow_table) -> dict:
    """`checksum` of a pyarrow table, chunk by chunk: nothing is copied."""
    out = {"rows": arrow_table.num_rows}
    for name in arrow_table.column_names:
        col = arrow_table.column(name)
        if col.null_count:
            raise Mismatch(f"{col.null_count} nulls in {name}")
        out[name] = sum(_bit_sum(c.to_numpy(zero_copy_only=True))
                        for c in col.chunks) % (1 << 64)
    return out


def columns_of(arrow_table) -> dict:
    """A pyarrow table as numpy columns; a null anywhere is a mismatch."""
    out = {}
    for name in arrow_table.column_names:
        col = arrow_table.column(name)
        if col.null_count:
            raise Mismatch(f"{col.null_count} nulls in {name}")
        out[name] = col.to_numpy()
    return out


def check_equal(got: dict, want: dict, what: str):
    """Every column of `got` equals the reference exactly after sorting
    by key."""
    if len(got["id"]) != len(want["id"]):
        raise Mismatch(f"{what}: {len(got['id'])} rows, reference has "
                       f"{len(want['id'])}")
    order = np.argsort(got["id"], kind="stable")
    for name, ref in want.items():
        have = got[name][order]
        if have.dtype != ref.dtype:
            raise Mismatch(f"{what}: {name} is {have.dtype}, reference "
                           f"{ref.dtype}")
        bad = np.flatnonzero(_bits(have) != _bits(ref))
        if len(bad):
            i = int(bad[0])
            raise Mismatch(f"{what}: {len(bad)} rows differ in {name}; "
                           f"first at key {int(got['id'][order][i])}: got "
                           f"{have[i]!r}, reference {ref[i]!r}")


def check_checksum(have: dict, want: dict, what: str):
    """`have` and `want` are checksums (`checksum`, `table_checksum`)."""
    if have != want:
        bad = sorted(k for k in want if have.get(k) != want[k])
        raise Mismatch(f"{what}: row count or column checksums differ "
                       f"in {bad}")
