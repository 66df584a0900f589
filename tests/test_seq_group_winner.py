"""`ops/agg.py` `_seq_group_winner_index` — the partial-update engine's
sequence-group resolution — against a row-at-a-time replay on random
sorted segments: row for row the same index, and no sort of the window
on the way."""

import datetime
import decimal

import numpy as np
import pyarrow as pa
import pytest

from paimon_tpu.ops import agg

ABOVE_2_53 = (1 << 53) + 1          # float64 cannot tell it from 2^53


def _draw(kind, rng, n):
    """`n` values of one sequence type from a small range, so that ties
    within a field are common."""
    small = rng.integers(0, 4, n)
    if kind == "bigint":            # three values a float64 rank merges
        return pa.array(ABOVE_2_53 + small, pa.int64())
    if kind == "int":
        return pa.array(small.astype(np.int32) - 2, pa.int32())
    if kind == "double":
        return pa.array(small * 0.25 - 0.5, pa.float64())
    if kind == "float_nan":         # a NaN is the largest, and ties
        return pa.array(np.where(small == 3, np.nan, small * 0.5),
                        pa.float32())
    if kind == "date":
        return pa.array([datetime.date(2024, 1, 1 + int(v))
                         for v in small], pa.date32())
    if kind == "timestamp":
        return pa.array(ABOVE_2_53 + small, pa.timestamp("us"))
    if kind == "decimal":
        return pa.array([decimal.Decimal(int(v) - 1) / 100 for v in small],
                        pa.decimal128(38, 2))
    raise AssertionError(kind)


def _segments(rng, n):
    """Ascending, dense segment ids over n rows: runs of 1 to 9 rows."""
    ends = np.cumsum(rng.integers(1, 10, n))
    seg_id = np.searchsorted(ends, np.arange(n), side="right")
    return seg_id.astype(np.int64), int(seg_id[-1]) + 1


def _winner_index(tbl, names, seg_id, num_seg, add_mask):
    """The resolution over the table's rows as they stand (they are in
    sorted order already): each field as its (values, validity)."""
    identity = np.arange(tbl.num_rows)
    return agg._seq_group_winner_index(
        [agg._sorted_sequence_field(tbl, name, identity) for name in names],
        seg_id, num_seg, add_mask)


def _replay(columns, seg_id, num_seg, add_mask):
    """The reference's loop: rows in order, a null in any field or a
    retract skips the row, `>=` keeps the later of equals."""
    def key(v):                     # NaN last and equal to itself
        return (1, 0.0) if isinstance(v, float) and v != v else (0, v)

    best = [None] * num_seg
    out = np.full(num_seg, -1, dtype=np.int64)
    rows = [c.to_pylist() for c in columns]
    for i, seg in enumerate(seg_id.tolist()):
        values = [r[i] for r in rows]
        if not add_mask[i] or any(v is None for v in values):
            continue
        current = tuple(key(v) for v in values)
        if best[seg] is None or current >= best[seg]:
            best[seg], out[seg] = current, i
    return out


def _case(kinds, seed, null_field=None, n=700):
    rng = np.random.default_rng(seed)
    seg_id, num_seg = _segments(rng, n)
    columns = []
    for f, kind in enumerate(kinds):
        arr = _draw(kind, rng, n)
        if null_field in (f, "all"):
            arr = pa.array(arr.to_pylist(), arr.type,
                           mask=rng.random(n) < 0.3)
        columns.append(arr)
    names = [f"s{f}" for f in range(len(kinds))]
    return pa.table(dict(zip(names, columns))), names, seg_id, num_seg, rng


KINDS = [("bigint",), ("int",), ("double",), ("float_nan",), ("date",),
         ("timestamp",), ("decimal",),
         ("bigint", "int"), ("date", "double"), ("timestamp", "bigint"),
         ("decimal", "int"), ("int", "float_nan"),
         ("int", "bigint", "double"), ("date", "timestamp", "int")]


@pytest.mark.parametrize("null_field", [None, 0, "last", "all"])
@pytest.mark.parametrize("kinds", KINDS, ids="-".join)
def test_winner_index_equals_the_replay(kinds, null_field):
    if null_field == "last":
        null_field = len(kinds) - 1
    tbl, names, seg_id, num_seg, rng = _case(kinds, 5, null_field)
    add_mask = rng.random(len(seg_id)) < 0.8         # retracts masked
    # segments with no row in the running: all masked, all null
    add_mask[np.isin(seg_id, [0, 3, num_seg - 1])] = False
    got = _winner_index(tbl, names, seg_id, num_seg, add_mask)
    want = _replay(tbl.columns, seg_id, num_seg, add_mask)
    assert got.dtype == np.int64 and got.tolist() == want.tolist()
    assert got[0] == got[3] == got[-1] == -1
    if null_field is None and len(kinds) > 1:
        # ties in the first field were decided by a later one somewhere
        first = [repr(v) for v in tbl.column(0).to_pylist()]
        tied = sum(1 for s in np.flatnonzero(got >= 0)
                   if sum(first[i] == first[got[s]] for i in
                          np.flatnonzero((seg_id == s) & add_mask)) > 1)
        assert tied > 10


@pytest.mark.parametrize("kinds", [("bigint",), ("int", "double", "date")],
                         ids="-".join)
def test_winner_index_sorts_nothing(kinds, monkeypatch):
    """No `np.unique`, `np.sort`, `argsort` or `lexsort` over the
    window: the resolution is segment maxima and comparisons."""
    tbl, names, seg_id, num_seg, rng = _case(kinds, 9, n=5_000)
    calls = []
    for name in ("unique", "sort", "argsort", "lexsort"):
        real = getattr(np, name)
        monkeypatch.setattr(
            np, name, lambda *a, _n=name, _r=real, **k:
            (calls.append(_n), _r(*a, **k))[1])
    got = _winner_index(tbl, names, seg_id, num_seg,
                        np.ones(len(seg_id), dtype=bool))
    assert calls == []
    monkeypatch.undo()
    assert got.tolist() == _replay(tbl.columns, seg_id, num_seg,
                                   np.ones(len(seg_id), bool)).tolist()


def test_values_above_2_53_stay_distinct():
    """Three rows of one key whose sequences differ only below float64's
    resolution: the largest wins, not the last."""
    tbl = pa.table({"s": pa.array([ABOVE_2_53 + 1, ABOVE_2_53 + 2,
                                   ABOVE_2_53], pa.int64())})
    got = _winner_index(tbl, ["s"], np.zeros(3, np.int64), 1,
                        np.ones(3, dtype=bool))
    assert got.tolist() == [1]
