"""The plain reference of `tpch-lineitem-pk`: the merge of the commits
and TPC-H Q1 and Q6 over it, in integers.

numpy only, importing nothing of `paimon_tpu`.  All commits concatenated
with their commit number, `lexsort` by (l_orderkey, l_linenumber,
commit), the last row of each key, the keys whose last row is a delete
dropped; then the two queries with int64 arithmetic on unscaled decimals
(Python integers where a sum's bound is not inside int64).  A result of
the program is held to it exactly: group keys, every sum, count, minimum
and maximum as integers, every average by the executor's stated rule —
the exact sum over the count, rounded half away from zero at the
column's scale — and no floating point anywhere.
"""

from __future__ import annotations

import datetime
import decimal

import numpy as np

from chipbench.data_tpch import KIND_DELETE, LINESTATUS, RETURNFLAGS
from chipbench.reference import Mismatch

_EPOCH = datetime.date(1970, 1, 1)
_WIDE = decimal.Context(prec=80)        # the default rounds at 28 digits
_NEEDED = ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
           "l_returnflag", "l_linestatus", "l_shipdate"]


def live_rows(commits) -> dict:
    """The columns Q1 and Q6 read, of the rows a merge-on-read keeps."""
    orderkey = np.concatenate([c["l_orderkey"] for c in commits])
    line = np.concatenate([c["l_linenumber"] for c in commits])
    number = np.concatenate([np.full(len(c["kind"]), i, np.int32)
                             for i, c in enumerate(commits)])
    order = np.lexsort((number, line, orderkey))
    sk, sl = orderkey[order], line[order]
    last = np.concatenate([(sk[1:] != sk[:-1]) | (sl[1:] != sl[:-1]),
                           [True]])
    win = order[last]
    kind = np.concatenate([c["kind"] for c in commits])
    win = win[kind[win] != KIND_DELETE]
    return {k: np.concatenate([c[k] for c in commits])[win]
            for k in _NEEDED}


def _sum(v: np.ndarray) -> int:
    if len(v) and int(np.abs(v).max()) * len(v) >= 1 << 63:
        return sum(v.tolist())
    return int(v.sum(dtype=np.int64))


def _avg(total: int, count: int) -> int:
    """Half away from zero, at the column's scale."""
    q = (2 * abs(total) + count) // (2 * count)
    return q if total >= 0 else -q


def _days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


def q1(rows: dict, params: dict) -> list:
    """Pricing summary: per (l_returnflag, l_linestatus), ordered, the
    tuple (flag, status, sum_qty [scale 2], sum_base_price [2],
    sum_disc_price [4], sum_charge [6], avg_qty [2], avg_price [2],
    avg_disc [2], count_order), decimals unscaled."""
    keep = rows["l_shipdate"] <= _days(params["shipdate_max"])
    qty, price = rows["l_quantity"][keep], rows["l_extendedprice"][keep]
    disc, tax = rows["l_discount"][keep], rows["l_tax"][keep]
    group = rows["l_returnflag"][keep].astype(np.int64) * len(LINESTATUS) \
        + rows["l_linestatus"][keep]
    disc_price = price * (100 - disc)
    charge = disc_price * (100 + tax)
    out = []
    for g in np.unique(group):
        m = group == g
        n = int(m.sum())
        sums = [_sum(v[m]) for v in (qty, price, disc_price, charge, disc)]
        out.append((RETURNFLAGS[g // len(LINESTATUS)],
                    LINESTATUS[g % len(LINESTATUS)],
                    sums[0], sums[1], sums[2], sums[3],
                    _avg(sums[0], n), _avg(sums[1], n), _avg(sums[4], n),
                    n))
    return out


def q6(rows: dict, params: dict) -> list:
    """Forecasting revenue change: one row, sum(l_extendedprice *
    l_discount) [scale 4] or None where no row qualifies."""
    ship = rows["l_shipdate"]
    keep = (ship >= _days(params["shipdate_min"])) \
        & (ship < _days(params["shipdate_end"])) \
        & (rows["l_discount"] >= params["discount_min_cents"]) \
        & (rows["l_discount"] <= params["discount_max_cents"]) \
        & (rows["l_quantity"] < params["quantity_below"] * 100)
    if not keep.any():
        return [(None,)]
    return [(_sum(rows["l_extendedprice"][keep] * rows["l_discount"][keep]),)]


# the result columns' decimal scales (None: not a decimal), by query
SCALES = {"q1": [None, None, 2, 2, 4, 6, 2, 2, 2, None], "q6": [4]}


def check(result, want: list, scales: list, what: str):
    """`result` (a pyarrow table, rows in the reference's order) equals
    `want` exactly, decimals by their unscaled integers."""
    if result.num_rows != len(want) or result.num_columns != len(scales):
        raise Mismatch(f"{what}: {result.num_rows} rows x "
                       f"{result.num_columns} columns, reference has "
                       f"{len(want)} x {len(scales)}")
    got = [result.column(i).to_pylist() for i in range(len(scales))]
    for r, ref in enumerate(want):
        for c, scale in enumerate(scales):
            have = got[c][r]
            if isinstance(have, float):
                raise Mismatch(f"{what}: column {c} is floating point")
            if scale is not None and have is not None:
                if not isinstance(have, decimal.Decimal) or \
                        -have.as_tuple().exponent != scale:
                    raise Mismatch(f"{what}: row {r} column {c} is "
                                   f"{have!r}, not a scale-{scale} decimal")
                have = int(have.scaleb(scale, context=_WIDE))
            if have != ref[c]:
                raise Mismatch(f"{what}: row {r} column {c}: got "
                               f"{have!r}, reference {ref[c]!r}")
