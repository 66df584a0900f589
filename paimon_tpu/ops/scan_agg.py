"""Aggregates below the merge-on-read merge.

A single-table `SELECT sum(...) ... GROUP BY ...` does not need the
merged rows, only each key's winner folded into a few numbers.  The
SQL executor describes such a statement as a `ScanAggregate`; the split
read (core/read.py) then returns, per split, one row a group of partial
sums, counts, minima and maxima in place of the split's rows.  Buckets
are key-disjoint, so the splits' partials add up.

Everything is integer arithmetic on lanes: a DECIMAL(p, s) column is its
unscaled 64-bit value, a DATE its days, an integer itself, a GROUP BY
column a small per-split dictionary code.  A product of two scale-2
decimals is a scale-4 integer; scales are aligned when the description
is normalised, so the evaluator has none.  Before a split is reduced its
columns' observed ranges are pushed through the expressions (interval
arithmetic): if any intermediate or any sum could leave int64 the split
is reduced with Python integers on the host instead (route `exact`) —
never a wrapped number.

Routes, chosen by the merge router's own terms (ops/merge.py
`route_to_host`, told the value lanes that go up and the few bytes that
come back): on the device the merge's own cached sort
(`_merge_fn_packed`), each key's winner carried back to input order by a
one-word sort (`scan_agg_winners`), then a small program per shape of
statement and split — winner & live kind & predicate -> expressions ->
one-hot group reductions — with nothing crossing the link between them,
return `[groups, numbers]`; on the host the same numbers come from numpy on
the winners.  Both give identical integers.  A raw-convertible split
takes the same epilogue without the sort.  A split with a string key
longer than the key lanes' prefix stays on the host, where the winners
are repaired by the full key as `merge_runs` does.
"""

from __future__ import annotations

import datetime
import decimal
from dataclasses import dataclass
from fractions import Fraction
from functools import lru_cache
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from paimon_tpu import predicate as P
from paimon_tpu.metrics import (
    SCAN_AGG_BELOW_ROWS, SCAN_AGG_MS, global_registry,
)
from paimon_tpu.obs.trace import metrics_enabled, span
from paimon_tpu.ops import merge as M
from paimon_tpu.types import RowKind

__all__ = ["ScanAggregate", "Measure", "split_partials", "ROWS_COL",
           "count_col", "value_col"]

# one-hot reductions: more groups than this in a split reduce on the host
MAX_DEVICE_GROUPS = 64
_I64_MAX = (1 << 63) - 1
_I64_MIN = -(1 << 63)

ROWS_COL = "__rows"                 # a group's qualifying rows


def count_col(k: int) -> str:
    """Partial column: measure k's non-null inputs."""
    return f"__n{k}"


def value_col(k: int) -> str:
    """Partial column: measure k's sum (decimal128(38, 0)), minimum or
    maximum (int64), in the expression's integer domain; null where no
    input qualified."""
    return f"__v{k}"


_SUM_TYPE = pa.decimal128(38, 0)


# -- the description ---------------------------------------------------------

@dataclass(frozen=True)
class Measure:
    """One reduction: `func` in sum / count / min / max over `expr`,
    a tree of ("col", name) | ("lit", number) | ("neg", e) |
    ("+" | "-" | "*", a, b); `expr` None is count(*)."""
    func: str
    expr: Optional[tuple]


@dataclass(frozen=True)
class ScanAggregate:
    """What a scan returns in place of rows: per split and group
    (`group_by` names columns) the measures' partials."""
    group_by: Tuple[str, ...]
    measures: Tuple[Measure, ...]

    def columns(self) -> List[str]:
        out: List[str] = list(self.group_by)
        for m in self.measures:
            if m.expr is not None:
                out.extend(_expr_columns(m.expr))
        return list(dict.fromkeys(out))

    def unsupported(self, fields: Dict[str, pa.DataType],
                    predicate: Optional[P.Predicate]) -> Optional[str]:
        """Why the description (with the scan's filter) cannot run below
        the merge over columns of these Arrow types, or None."""
        try:
            for g in self.group_by:
                t = fields[g]
                if _lane_scale(t) is None and not _is_text(t):
                    return f"GROUP BY {g}: {t}"
            scales = _Scales(fields)
            for m in self.measures:
                if m.expr is not None:
                    _normalize(m.expr, scales)
                    if m.func == "sum" and any(
                            scales.is_date(c)
                            for c in _expr_columns(m.expr)):
                        return "sum of a DATE"
            if predicate is not None:
                _normalize_predicate(predicate, scales)
        except _Unsupported as e:
            return str(e)
        return None

    def measure_scale(self, k: int, fields: Dict[str, pa.DataType]) -> int:
        """Decimal scale of measure k's integer domain."""
        m = self.measures[k]
        return 0 if m.expr is None else _normalize(m.expr, _Scales(fields))[1]


class _Unsupported(Exception):
    pass


def _expr_columns(e: tuple) -> List[str]:
    if e[0] == "col":
        return [e[1]]
    if e[0] == "lit":
        return []
    return [c for x in e[1:] for c in _expr_columns(x)]


def _is_text(t: pa.DataType) -> bool:
    return pa.types.is_string(t) or pa.types.is_large_string(t)


def _lane_scale(t: pa.DataType) -> Optional[int]:
    """Decimal scale of a column's integer lane; None = no lane."""
    if pa.types.is_integer(t) or pa.types.is_date32(t):
        return 0
    if pa.types.is_decimal128(t) and t.precision <= 18:
        return t.scale
    return None


class _Scales:
    def __init__(self, fields: Dict[str, pa.DataType]):
        self.fields = fields

    def of(self, name: str) -> int:
        t = self.fields.get(name)
        s = None if t is None else _lane_scale(t)
        if s is None:
            raise _Unsupported(f"column {name}: {t}")
        return s

    def is_date(self, name: str) -> bool:
        return pa.types.is_date32(self.fields[name])


def _literal_fraction(v) -> Fraction:
    """A SQL number as the user wrote it: a float literal is its
    shortest decimal form, never its binary expansion."""
    if isinstance(v, bool) or v is None:
        raise _Unsupported(f"literal {v!r}")
    if isinstance(v, int):
        return Fraction(v)
    if isinstance(v, float):
        return Fraction(decimal.Decimal(repr(v)))
    if isinstance(v, decimal.Decimal):
        return Fraction(v)
    raise _Unsupported(f"literal {v!r}")


def _normalize(e: tuple, scales: _Scales) -> Tuple[tuple, int]:
    """(tree over integer lanes with the scales aligned, its scale)."""
    kind = e[0]
    if kind == "col":
        return e, scales.of(e[1])
    if kind == "lit":
        f = _literal_fraction(e[1])
        scale = 0
        while f.denominator != 1:
            f *= 10
            scale += 1
            if scale > 18:
                raise _Unsupported(f"literal {e[1]!r}")
        return ("lit", int(f)), scale
    if kind not in ("neg", "+", "-", "*"):
        raise _Unsupported(f"operator {kind}")
    for x in e[1:]:
        if x[0] == "col" and scales.is_date(x[1]):
            raise _Unsupported("arithmetic on a DATE")
    if kind == "neg":
        a, s = _normalize(e[1], scales)
        return ("neg", a), s
    (a, sa), (b, sb) = _normalize(e[1], scales), _normalize(e[2], scales)
    if kind == "*":
        return ("*", a, b), sa + sb
    s = max(sa, sb)
    if sa < s:
        a = ("*", a, ("lit", 10 ** (s - sa)))
    if sb < s:
        b = ("*", b, ("lit", 10 ** (s - sb)))
    return (kind, a, b), s


def _threshold(column: str, v, scales: _Scales) -> Fraction:
    """A literal in the column's lane domain."""
    if scales.is_date(column):
        if type(v) is not datetime.date:
            raise _Unsupported(f"DATE {column} against {v!r}")
        return Fraction((v - datetime.date(1970, 1, 1)).days)
    if isinstance(v, (datetime.date, datetime.datetime)):
        raise _Unsupported(f"{column} against {v!r}")
    return _literal_fraction(v) * 10 ** scales.of(column)


_CMP = {"lt": lambda v, t: v < t, "le": lambda v, t: v <= t,
        "gt": lambda v, t: v > t, "ge": lambda v, t: v >= t,
        "eq": lambda v, t: v == t}


def _compare(column: str, op: str, t: Fraction) -> tuple:
    """One comparison against a threshold that is a lane value."""
    if t.denominator != 1 or not _I64_MIN <= t <= _I64_MAX:
        raise _Unsupported(f"{column} against a threshold off its lane")
    return ("cmp", op, column, int(t))


def _normalize_predicate(p: P.Predicate, scales: _Scales) -> tuple:
    """Predicate tree over integer lanes: comparisons and BETWEEN under
    AND / OR (anything else materialises).  Two-valued — a null makes
    its leaf false — which equals SQL's three-valued filter without
    NOT."""
    if isinstance(p, P.Compound):
        if p.op == "not":
            raise _Unsupported("NOT")
        return (p.op, tuple(_normalize_predicate(c, scales)
                            for c in p.children))
    if not isinstance(p, P.Leaf):
        raise _Unsupported(f"predicate {p!r}")
    op, column, lit = p.op, p.field, p.literal
    scales.of(column)                       # the column has a lane
    if op == "between":
        return ("and", (_compare(column, "ge",
                                 _threshold(column, lit[0], scales)),
                        _compare(column, "le",
                                 _threshold(column, lit[1], scales))))
    if op not in _CMP:
        raise _Unsupported(f"predicate {op}")
    return _compare(column, op, _threshold(column, lit, scales))


def _predicate_columns(p: tuple) -> List[str]:
    if p[0] in ("and", "or"):
        return [c for x in p[1] for c in _predicate_columns(x)]
    return [p[2]]


# -- a split's lanes ---------------------------------------------------------

@dataclass
class _Lanes:
    """One split's operands in input order (under `scan.agg`)."""
    n: int
    values: Dict[str, np.ndarray]               # int64[n], nulls as 0
    valid: Dict[str, Optional[np.ndarray]]      # bool[n] or None = all
    bounds: Dict[str, Tuple[int, int]]
    code: Optional[np.ndarray]                  # int32[n] group code
    groups: int
    dictionaries: List[pa.Array]                # per GROUP BY column
    radices: List[int]                          # codes per column


def _int_lane(arr: pa.Array) -> np.ndarray:
    """int64[n] of a column's lane values, nulls as 0."""
    t = arr.type
    if pa.types.is_decimal128(t):
        # 16 little-endian bytes a value; precision <= 18 fits the low
        # word, whose sign extension is the high one
        words = np.frombuffer(arr.buffers()[1], dtype=np.int64)
        v = np.ascontiguousarray(
            words[2 * arr.offset:2 * (arr.offset + len(arr)):2])
        if arr.null_count:
            v[~np.asarray(arr.is_valid())] = 0
        return v
    if pa.types.is_date32(t):
        arr = arr.cast(pa.int32())
    if arr.null_count:
        arr = pc.fill_null(arr, 0)
    return np.asarray(arr.cast(pa.int64()))


def _build_lanes(table: pa.Table, agg: ScanAggregate, exprs, pred
                 ) -> _Lanes:
    n = table.num_rows
    names = [c for e in exprs if e is not None for c in _expr_columns(e)]
    if pred is not None:
        names += _predicate_columns(pred)
    values, valid, bounds = {}, {}, {}
    for name in dict.fromkeys(names):
        arr = table.column(name).combine_chunks()
        v = _int_lane(arr)
        values[name] = v
        valid[name] = np.asarray(arr.is_valid()) if arr.null_count \
            else None
        bounds[name] = (int(v.min()), int(v.max())) if n else (0, 0)
    code, groups, dictionaries, radices = None, 1, [], []
    for g in agg.group_by:
        enc = pc.dictionary_encode(table.column(g).combine_chunks())
        idx = enc.indices
        radix = len(enc.dictionary) + (1 if idx.null_count else 0)
        c = np.asarray(pc.fill_null(idx, len(enc.dictionary))
                       if idx.null_count else idx).astype(np.int64)
        code = c if code is None else code * radix + c
        groups *= max(radix, 1)
        dictionaries.append(enc.dictionary)
        radices.append(max(radix, 1))
        if groups > (1 << 31) - 1:
            raise OverflowError("GROUP BY codes of one split exceed 2^31")
    if code is not None:
        code = code.astype(np.int32)
    return _Lanes(n, values, valid, bounds, code, groups, dictionaries,
                  radices)


# -- interval proof ----------------------------------------------------------

def _interval(e: tuple, bounds) -> Tuple[int, int]:
    kind = e[0]
    if kind == "col":
        return bounds[e[1]]
    if kind == "lit":
        return e[1], e[1]
    if kind == "neg":
        lo, hi = _interval(e[1], bounds)
        out = (-hi, -lo)
    else:
        (al, ah), (bl, bh) = _interval(e[1], bounds), _interval(e[2], bounds)
        if kind == "+":
            out = (al + bl, ah + bh)
        elif kind == "-":
            out = (al - bh, ah - bl)
        else:
            c = (al * bl, al * bh, ah * bl, ah * bh)
            out = (min(c), max(c))
    if out[0] < _I64_MIN or out[1] > _I64_MAX:
        raise OverflowError(e)
    return out


def fits_int64(measures: Sequence[Measure], exprs, bounds, rows: int
               ) -> bool:
    """Every intermediate of every row, and every sum over `rows` rows,
    stays inside int64 — proved from the columns' observed ranges."""
    try:
        for m, e in zip(measures, exprs):
            if e is None:
                continue
            lo, hi = _interval(e, bounds)
            if m.func == "sum" and \
                    max(abs(lo), abs(hi)) * max(rows, 1) > _I64_MAX:
                return False
    except OverflowError:
        return False
    return True


# -- the evaluator, shared by numpy and jax.numpy ----------------------------

def _and(a, b):
    if a is None:
        return b
    return a if b is None else a & b


def _eval(e: tuple, values, valid):
    """(int64 lane or Python int, validity lane or None)."""
    kind = e[0]
    if kind == "col":
        return values[e[1]], valid[e[1]]
    if kind == "lit":
        return e[1], None
    if kind == "neg":
        v, ok = _eval(e[1], values, valid)
        return -v, ok
    (a, oka), (b, okb) = _eval(e[1], values, valid), \
        _eval(e[2], values, valid)
    v = a + b if kind == "+" else a - b if kind == "-" else a * b
    return v, _and(oka, okb)


def _eval_predicate(p: tuple, values, valid):
    kind = p[0]
    if kind in ("and", "or"):
        out = _eval_predicate(p[1][0], values, valid)
        for c in p[1][1:]:
            x = _eval_predicate(c, values, valid)
            out = out & x if kind == "and" else out | x
        return out
    _, op, column, t = p
    return _and(_CMP[op](values[column], t), valid[column])


def _numbers(measures) -> int:
    """Numbers a group returns: its rows, then a count and a value a
    measure (count measures carry no value)."""
    return 1 + sum(1 if m.func == "count" else 2 for m in measures)


def _reduce_groups(sel, code, groups: int, measures, exprs, values,
                   valid):
    """int64[groups, numbers] by one-hot masks (traced): rows, then per
    measure its non-null inputs and (not for count) its sum / min /
    max."""
    if code is None:
        onehot = sel[None, :]
    else:
        onehot = (code[None, :] ==
                  jnp.arange(groups, dtype=code.dtype)[:, None]) \
            & sel[None, :]
    rows = onehot.sum(axis=1, dtype=jnp.int64)
    out = [rows]
    for m, e in zip(measures, exprs):
        if e is None:                                   # count(*)
            out.append(rows)
            continue
        v, ok = _eval(e, values, valid)
        hit = onehot if ok is None else onehot & ok[None, :]
        out.append(rows if ok is None
                   else hit.sum(axis=1, dtype=jnp.int64))
        if m.func == "count":
            continue
        v = jnp.broadcast_to(jnp.asarray(v, jnp.int64), sel.shape)
        if m.func == "sum":
            out.append(jnp.where(hit, v[None, :], 0)
                       .sum(axis=1, dtype=jnp.int64))
        elif m.func == "min":
            out.append(jnp.where(hit, v[None, :], _I64_MAX).min(axis=1))
        else:
            out.append(jnp.where(hit, v[None, :], _I64_MIN).max(axis=1))
    return jnp.stack(out, axis=1)


# -- host routes -------------------------------------------------------------

def _host_numbers(lanes: _Lanes, sel: np.ndarray, measures, exprs,
                  exact: bool) -> Tuple[List[int], List[List[int]]]:
    """(group codes that have a selected row, their numbers as Python
    integers).  `exact`: the proof failed, so the lanes become Python
    integers before any arithmetic."""
    values = lanes.values
    if exact:
        values = {k: v.astype(object) for k, v in values.items()}
    idx = np.flatnonzero(sel)
    if lanes.code is None:
        present, members = [0], [idx]
    else:
        order = idx[np.argsort(lanes.code[idx], kind="stable")]
        codes = lanes.code[order]
        starts = np.flatnonzero(np.concatenate(
            [[True], codes[1:] != codes[:-1]])) if len(codes) else []
        present = [int(codes[i]) for i in starts]
        members = np.split(order, starts[1:]) if len(codes) else []
    evaluated = [None if e is None else _eval(e, values, lanes.valid)
                 for e in exprs]
    out = []
    for rows in members:
        numbers = [len(rows)]
        for m, ev in zip(measures, evaluated):
            if ev is None:                              # count(*)
                numbers.append(len(rows))
                continue
            v, ok = ev
            hit = rows if ok is None else rows[ok[rows]]
            numbers.append(len(hit))
            if m.func == "count":
                continue
            taken = v[hit] if hasattr(v, "shape") \
                else np.full(len(hit), v, dtype=object if exact
                             else np.int64)
            if not len(hit):
                numbers.append(0)
            elif m.func == "sum":
                numbers.append(sum(taken.tolist()) if exact
                               else int(taken.sum(dtype=np.int64)))
            else:
                numbers.append(int(taken.min() if m.func == "min"
                                   else taken.max()))
        out.append(numbers)
    return present, out


def _host_selection(lanes: _Lanes, winners: Optional[np.ndarray],
                    live: Optional[np.ndarray], pred) -> np.ndarray:
    sel = np.ones(lanes.n, dtype=bool) if winners is None else winners
    if live is not None:
        sel = sel & live
    if pred is not None:
        sel = sel & _eval_predicate(pred, lanes.values, lanes.valid)
    return sel


# -- the device program ------------------------------------------------------

def _words_to_int64(words):
    if len(words) == 1:
        return words[0].astype(jnp.int64)
    hi, lo = words
    return (hi.astype(jnp.int64) << 32) | lo.astype(jnp.int64)


@jax.jit
def scan_agg_winners(packed):
    """bool[m]: each key's winner flag back in input order, from the
    merge's packed return (perm | winner << 31 a sorted position).
    perm is a permutation of the rows, so sorting perm << 1 | winner
    leaves row i's flag at position i — one word a row, no scatter.
    One program a padded size, whatever the statement."""
    return (jax.lax.sort(packed << 1 | packed >> 31) & 1) == 1


@lru_cache(maxsize=64)
def _program(names: Tuple[str, ...], pred: Optional[tuple],
             measures: Tuple[Measure, ...],
             exprs: Tuple[Optional[tuple], ...], groups: int):
    """The jitted epilogue for one shape of statement and split; it
    compiles in a second or two.  The sorts ahead of it do not vary
    with the statement: the router's own cached program (ops/merge.py
    `_merge_fn_packed`) and `scan_agg_winners`, whose returns stay on
    the device."""

    def scan_agg_epilogue(sel, live, code, words, valids):
        """`sel`: the rows that count before the filter — each key's
        winner, or for a raw-convertible split every live row."""
        values = {k: _words_to_int64(w) for k, w in zip(names, words)}
        valid = dict(zip(names, valids))
        sel = _and(sel, live)
        if pred is not None:
            sel = sel & _eval_predicate(pred, values, valid)
        return _reduce_groups(sel, code, groups, measures, exprs, values,
                              valid)

    return jax.jit(scan_agg_epilogue)


def _pad(a: np.ndarray, m: int) -> np.ndarray:
    out = np.zeros(m, dtype=a.dtype)
    out[:len(a)] = a
    return out


def _one_word(bounds: Tuple[int, int]) -> bool:
    """A lane whose observed range fits int32 goes up as one word."""
    return -(1 << 31) <= bounds[0] and bounds[1] < (1 << 31)


def _device_operands(lanes: _Lanes, live: Optional[np.ndarray], m: int):
    """Value lanes as 32-bit words, padded: one int32 word where the
    split's range allows, else the high and low words."""
    words, valids = [], []
    for name, v in lanes.values.items():
        if _one_word(lanes.bounds[name]):
            words.append((_pad(v.astype(np.int32), m),))
        else:
            u = v.view(np.uint64)
            words.append((_pad((u >> np.uint64(32)).astype(np.int32), m),
                          _pad(u.astype(np.uint32), m)))
        ok = lanes.valid[name]
        valids.append(None if ok is None else _pad(ok, m))
    code = None if lanes.code is None else _pad(lanes.code, m)
    live = None if live is None else _pad(live, m)
    nbytes = sum(w.nbytes for ws in words for w in ws) \
        + sum(v.nbytes for v in valids if v is not None) \
        + (0 if code is None else code.nbytes) \
        + (0 if live is None else live.nbytes)
    return tuple(words), tuple(valids), code, live, nbytes


def _epilogue_bytes_per_row(lanes: _Lanes, live) -> int:
    per_row = 0
    for name in lanes.values:
        per_row += 4 if _one_word(lanes.bounds[name]) else 8
        per_row += 0 if lanes.valid[name] is None else 1
    return per_row + (0 if lanes.code is None else 4) \
        + (0 if live is None else 1)


def _upload(tree):
    return jax.tree_util.tree_map(jnp.asarray, tree)


# -- one split ---------------------------------------------------------------

def _agg_span(rows: int, groups: int, aggregates: int, route: str):
    """`scan.agg`: the pushed aggregate's own host work for one split —
    value lanes and group codes before the merge, the partial table
    after it and, on the host routes, the reductions between."""
    return span("scan.agg", cat="scan", group="scan", metric=SCAN_AGG_MS,
                rows=rows, groups=groups, aggregates=aggregates,
                route=route)


def _live_rows(table: pa.Table) -> Optional[np.ndarray]:
    """bool[n]: the row's kind is +I or +U; None where every row's is
    (the cheap min/max test of `merge_runs`)."""
    if M.KIND_COL not in table.column_names:
        return None
    mm = pc.min_max(table.column(M.KIND_COL))
    lo, hi = mm["min"].as_py(), mm["max"].as_py()
    if lo == hi and lo in (RowKind.INSERT, RowKind.UPDATE_AFTER):
        return None
    kinds = np.asarray(table.column(M.KIND_COL).combine_chunks()
                       .cast(pa.int8()))
    return (kinds == RowKind.INSERT) | (kinds == RowKind.UPDATE_AFTER)


def split_partials(runs: Sequence[pa.Table], key_names: Sequence[str],
                   agg: ScanAggregate, predicate: Optional[P.Predicate],
                   fields: Dict[str, pa.DataType], key_encoder, *,
                   merge: bool, merge_engine: str = "deduplicate",
                   seq_fields=None, seq_desc: bool = False) -> pa.Table:
    """One split's partial aggregates: a row a group that has a
    qualifying row (one row always without GROUP BY) with the group's
    columns, `__rows`, and per measure k `__n<k>` and `__v<k>`.
    `merge` False: the split is raw-convertible, every live row counts."""
    scales = _Scales(fields)
    exprs = tuple(None if m.expr is None else _normalize(m.expr, scales)[0]
                  for m in agg.measures)
    pred = None if predicate is None \
        else _normalize_predicate(predicate, scales)
    measures = agg.measures
    if merge:
        op = M.merge_operands(runs, key_names, merge_engine, key_encoder,
                              seq_fields, seq_desc)
        table = op.table
    else:
        op = None
        table = pa.concat_tables(runs, promote_options="none")
    n = table.num_rows
    merge = merge and n > 0
    with _agg_span(n, 0, len(measures), "prep"):
        lanes = _build_lanes(table, agg, exprs, pred)
        live = _live_rows(table)
        proved = fits_int64(measures, exprs, lanes.bounds, n)
        groups = 1 << (lanes.groups - 1).bit_length()   # the program's
        d2h = 8 * groups * _numbers(measures)
    # a string key cut to the encoder's prefix needs the host's full-key
    # repair; else the router's terms: a raw-convertible split has no
    # sort to weigh, so it is routed as the merge of its rows would be
    to_host = not proved or groups > MAX_DEVICE_GROUPS or n == 0 \
        or (merge and op.any_truncated) \
        or M.route_to_host(
            n, key_encoder.num_lanes, op.num_order_lanes if merge else 0,
            True, _epilogue_bytes_per_row(lanes, live) * M._pad_size(n),
            d2h)
    if to_host:
        winners = None
        if merge:
            winners = np.zeros(n, dtype=bool)
            winners[_host_winners(op, key_names)] = True
        with _agg_span(n, lanes.groups, len(measures),
                       "host" if proved else "exact"):
            sel = _host_selection(lanes, winners, live, pred)
            present, numbers = _host_numbers(lanes, sel, measures, exprs,
                                             exact=not proved)
            out = _partial_table(agg, lanes, present, numbers)
        M.count_returned(8 * len(present) * _numbers(measures))
    else:
        numbers = _device_numbers(op if merge else None, lanes, live,
                                  pred, measures, exprs, groups, d2h)
        with _agg_span(n, lanes.groups, len(measures), "device"):
            out = _partial_table(agg, lanes, range(len(numbers)), numbers)
        M.count_returned(d2h)
    if metrics_enabled():
        global_registry().scan_metrics().counter(SCAN_AGG_BELOW_ROWS) \
            .inc(n)
    return out


def _host_winners(op: M.MergeOperands, key_names) -> np.ndarray:
    """Row indices of each key's winner by the merge's host route, as
    `merge_runs` takes them: keys that share a truncated prefix are told
    apart by the full key."""
    op.encode_host()
    truncated = op.any_truncated
    perm, winner, _ = M.host_sorted_winners(
        op.lanes, op.seq, op.keep, op.order_lanes, not truncated,
        op.packed, op.run_starts)
    with M.winners_span(op.n, "host"):
        if truncated:
            perm, winner, _ = M._winner_epilogue(*M.tiebreak_cut_keys(
                op.table, key_names, op.key_encoder, op.lanes,
                op.truncated, perm, op.seq, op.order_lanes), op.keep)
        return perm[np.flatnonzero(winner)]


def _device_numbers(op: Optional[M.MergeOperands], lanes: _Lanes, live,
                    pred, measures, exprs, groups: int, d2h: int
                    ) -> List[List[int]]:
    """The round trip (`merge.device`, route `agg`): operands up, the
    merge's sort and the winners' way back (neither for a
    raw-convertible split, `op` None), the epilogue, `[groups, numbers]`
    back."""
    n = lanes.n
    if op is None:
        m, up = M._pad_size(n), 0
    else:
        M.PATH_COUNTS["device"] += 1
        planes = op.device_planes()
        num_lanes, m = len(planes) - 3, len(planes[0])
        up = 4 * m * len(planes)
    with M.prep_span(n):
        if op is None and live is None:
            live = np.ones(n, dtype=bool)       # the pad's rows are not
        words, valids, code, live, nbytes = _device_operands(lanes, live, m)
    fn = _program(tuple(lanes.values), pred, measures, exprs, groups)
    with M.device_span("agg", n, m, up + nbytes, d2h):
        if op is None:
            sel, live = live, None
        else:
            sort = M._merge_fn_packed(num_lanes, op.keep, op.num_key_lanes)
            operands = [jnp.asarray(plane) for plane in planes]
            sel = scan_agg_winners(sort(tuple(operands[:num_lanes]),
                                        *operands[num_lanes:]))
        out = np.asarray(fn(*_upload((sel, live, code, words, valids))))
    return out.tolist()


def _partial_table(agg: ScanAggregate, lanes: _Lanes, codes,
                   numbers: List[List[int]]) -> pa.Table:
    """`numbers[i]` are group code `codes[i]`'s; a group without a row
    is left out, but for the one group of no GROUP BY."""
    keep = [(g, row) for g, row in zip(codes, numbers)
            if row[0] > 0 or not agg.group_by]
    cols = {}
    stride = lanes.groups
    for name, dictionary, radix in zip(agg.group_by, lanes.dictionaries,
                                       lanes.radices):
        stride //= radix
        idx = [(g // stride) % radix for g, _ in keep]
        cols[name] = dictionary.take(pa.array(
            [i if i < len(dictionary) else None for i in idx],
            pa.int32()))
    cols[ROWS_COL] = pa.array([row[0] for _, row in keep], pa.int64())
    at = 1
    for k, m in enumerate(agg.measures):
        counts = [row[at] for _, row in keep]
        cols[count_col(k)] = pa.array(counts, pa.int64())
        at += 1
        if m.func == "count":
            continue
        vals = [row[at] if c else None
                for (_, row), c in zip(keep, counts)]
        at += 1
        cols[value_col(k)] = pa.array(
            [None if v is None else decimal.Decimal(v) for v in vals],
            _SUM_TYPE) if m.func == "sum" else pa.array(vals, pa.int64())
    return pa.table(cols)
