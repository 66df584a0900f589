"""`ops/agg.py` `aggregate_sorted_segments` — the epilogue that takes each
segment's chosen rows from the unsorted window and gathers by the merge's
order only what a selection or a reduction reads — against a plain
oracle kept here: the whole window taken into sorted order
(`table.take(order)`), then every segment folded row by row."""

import numpy as np
import pyarrow as pa
import pytest

from paimon_tpu.options import CoreOptions
from paimon_tpu.ops import agg
from paimon_tpu.ops.merge import KIND_COL, SEQ_COL
from paimon_tpu.schema import Schema
from paimon_tpu.schema.table_schema import TableSchema
from paimon_tpu.types import (
    ArrayType, BigIntType, BooleanType, DoubleType, IntType, MapType,
    RowKind, VarCharType,
)

KEY = "_KEY_k"
STRING = VarCharType.string_type()
INSERT, BEFORE, AFTER, DELETE = (
    RowKind.INSERT, RowKind.UPDATE_BEFORE, RowKind.UPDATE_AFTER,
    RowKind.DELETE)


# -- the oracle -------------------------------------------------------------

def _adds(seg):
    return [r for r in seg if r[KIND_COL] not in (DELETE, BEFORE)]


def _valid_adds(seg, name):
    return [r for r in _adds(seg) if r[name] is not None]


def _last(rows, name):
    return rows[-1][name] if rows else None


def _first(rows, name):
    return rows[0][name] if rows else None


def _fold_sum(seg, name, ignore_retract=False):
    total, any_valid = 0, False
    for r in seg:
        retract = r[KIND_COL] in (DELETE, BEFORE)
        if r[name] is None or (retract and ignore_retract):
            continue
        any_valid = True
        total += -r[name] if retract else r[name]
    return total if any_valid else None


def _fold_values(fn):
    def fold(seg, name):
        vals = [r[name] for r in _valid_adds(seg, name)]
        return fn(vals) if vals else None
    return fold


def _fold_collect(seg, name):
    rows = _valid_adds(seg, name)
    return [v for r in rows for v in r[name]] if rows else None


def _fold_merge_map(seg, name):
    rows = _valid_adds(seg, name)
    merged = {}
    for r in rows:
        merged.update(dict(r[name]))
    return list(merged.items()) if rows else None


FOLDS = {
    "sum": _fold_sum,
    "sum_ignore_retract": lambda seg, name: _fold_sum(seg, name, True),
    "max": _fold_values(max),
    "min": _fold_values(min),
    "product": _fold_values(lambda vals: int(np.prod(vals))),
    "count": lambda seg, name: len(_valid_adds(seg, name)),
    "bool_and": lambda seg, name: all(
        r[name] for r in _valid_adds(seg, name)),
    "bool_or": lambda seg, name: any(
        r[name] for r in _valid_adds(seg, name)),
    "last_non_null_value": lambda seg, name: _last(
        _valid_adds(seg, name), name),
    "last_value": lambda seg, name: _last(_adds(seg), name),
    "first_non_null_value": lambda seg, name: _first(
        _valid_adds(seg, name), name),
    "first_value": lambda seg, name: _first(_adds(seg), name),
    "primary_key": lambda seg, name: _first(_valid_adds(seg, name), name),
    "listagg": _fold_values(",".join),
    "collect": _fold_collect,
    "merge_map": _fold_merge_map,
}


def _group_winner(seg, seq_fields):
    """The row with the largest non-null sequence tuple among the rows
    that add, the later of equals; None if no row qualifies."""
    best = None
    for r in _adds(seg):
        current = tuple(r[f] for f in seq_fields)
        if None in current:
            continue
        if best is None or current >= tuple(best[f] for f in seq_fields):
            best = r
    return best


def oracle(table, order, seg_id, funcs, groups, engine, remove_on_delete):
    """`funcs`: {value column: fold name}; `groups`: [(sequence fields,
    members)].  The rows of the merged table, as dicts, in key order."""
    window = table.take(pa.array(order)).to_pylist()
    out = []
    for s in range(int(seg_id[-1]) + 1 if len(seg_id) else 0):
        seg = [window[i] for i in np.flatnonzero(seg_id == s)]
        row = {name: seg[-1][name] for name in (KEY, SEQ_COL, KIND_COL, "k")}
        for name, func in funcs.items():
            row[name] = FOLDS[func](seg, name)
        for seq_fields, members in groups:
            winner = _group_winner(seg, seq_fields)
            for name in seq_fields + members:
                row[name] = None if winner is None else winner[name]
        if row[KIND_COL] == DELETE and (engine == "aggregation"
                                        or remove_on_delete):
            continue
        out.append(row)
    return out


# -- the windows ------------------------------------------------------------

PA_TYPES = {"bigint": pa.int64(), "int": pa.int32(), "double": pa.float64(),
            "bool": pa.bool_(), "string": pa.string(),
            "list": pa.list_(pa.int64()),
            "map": pa.map_(pa.string(), pa.int64())}
SQL_TYPES = {"bigint": BigIntType(), "int": IntType(),
             "double": DoubleType(), "bool": BooleanType(), "string": STRING,
             "list": ArrayType(BigIntType()),
             "map": MapType(STRING, BigIntType())}


def _draw(kind, rng, n, null_rate):
    small = rng.integers(1, 4, n)           # products and sums stay exact
    values = {
        "bigint": lambda: (small + (1 << 53)).tolist(),
        "int": lambda: small.astype(np.int32).tolist(),
        "double": lambda: (small * 0.25).tolist(),
        "bool": lambda: (small > 1).tolist(),
        "string": lambda: [f"s{v}" for v in small],
        "list": lambda: [list(range(v)) for v in small],
        "map": lambda: [[(f"m{v}", int(i))] for i, v in enumerate(small)],
    }[kind]()
    mask = rng.random(n) < null_rate
    return pa.array([None if m else v for v, m in zip(values, mask)],
                    PA_TYPES[kind])


def _window(rng, columns, runs, keys=60, null_rate=0.3, kinds=(INSERT,),
            dead_keys=()):
    """`runs` runs that each hold a random half or more of `keys` keys in
    key order, oldest first, in the KV layout; `columns`: [(name, kind)].
    For the keys in `dead_keys` every value of every run is null."""
    tables, seq = [], 0
    for _ in range(runs):
        ks = np.sort(rng.choice(keys, rng.integers(keys // 2, keys + 1),
                                replace=False))
        n = len(ks)
        cols = {KEY: pa.array(ks, pa.int64()),
                SEQ_COL: pa.array(np.arange(seq, seq + n), pa.int64()),
                KIND_COL: pa.array(rng.choice(kinds, n), pa.int8()),
                "k": pa.array(ks, pa.int64())}
        seq += n
        dead = np.isin(ks, dead_keys)
        for name, kind in columns:
            arr = _draw(kind, rng, n, null_rate)
            cols[name] = pa.array(
                [None if d else v for v, d in zip(arr.to_pylist(), dead)],
                arr.type)
        tables.append(pa.table(cols))
    return tables


def _sorted(table):
    """(order, seg_id, win_sorted) as the device sort hands them over:
    by key, then sequence."""
    key = np.asarray(table.column(KEY).combine_chunks())
    seq = np.asarray(table.column(SEQ_COL).combine_chunks())
    order = np.lexsort((seq, key)).astype(np.int64)
    sorted_key = key[order]
    win_sorted = np.ones(len(order), dtype=bool)
    win_sorted[:-1] = sorted_key[1:] != sorted_key[:-1]
    seg_id = np.zeros(len(order), dtype=np.int64)
    seg_id[1:] = np.cumsum(win_sorted[:-1])
    return order, seg_id, win_sorted


def _schema(columns, options):
    b = Schema.builder().column("k", BigIntType(False))
    for name, kind in columns:
        b = b.column(name, SQL_TYPES[kind])
    return TableSchema.from_schema(
        0, b.primary_key("k").options({"bucket": "1", **options}).build())


def _check(tables, columns, options, funcs, groups=(), chunked=True):
    """The epilogue's table equals the oracle's rows, column for column,
    in the declared order and the input's types."""
    engine = options["merge-engine"]
    table = pa.concat_tables(tables)
    if not chunked:
        table = table.combine_chunks()
    assert table.column("k").num_chunks == (len(tables) if chunked else 1)
    schema = _schema(columns, options)
    order, seg_id, win_sorted = _sorted(table)
    got = agg.aggregate_sorted_segments(
        table, order, seg_id, win_sorted, [KEY], schema,
        CoreOptions(schema.options))
    want = oracle(
        table, order, seg_id, funcs, list(groups), engine,
        options.get("partial-update.remove-record-on-delete") == "true")
    assert got.column_names == [KEY, SEQ_COL, KIND_COL, "k"] + \
        [name for name, _ in columns]
    for name in got.column_names:
        assert got.column(name).to_pylist() == [r[name] for r in want], name
    return got, want


# -- partial-update ---------------------------------------------------------

def _partial_update_case(n_groups):
    """`n_groups` sequence groups (the last over two sequence fields)
    of three members each, and three ungrouped columns."""
    columns, options, groups = [], {"merge-engine": "partial-update"}, []
    for g in range(n_groups):
        seq_fields = [f"g{g}_ts"] + ([f"g{g}_ts2"] if g == n_groups - 1
                                     else [])
        members = [f"g{g}_a", f"g{g}_b", f"g{g}_c"]
        columns += [(f, "bigint") for f in seq_fields]
        columns += list(zip(members, ("bigint", "double", "string")))
        options[f"fields.{','.join(seq_fields)}.sequence-group"] = \
            ",".join(members)
        groups.append((seq_fields, members))
    columns += [("u0", "bigint"), ("u1", "double"), ("u2", "int")]
    funcs = {u: "last_non_null_value" for u in ("u0", "u1", "u2")}
    return columns, options, funcs, groups


@pytest.mark.parametrize("chunked", [True, False],
                         ids=["five_runs", "one_chunk"])
@pytest.mark.parametrize("n_groups", [0, 1, 4])
def test_partial_update_equals_the_oracle(n_groups, chunked):
    columns, options, funcs, groups = _partial_update_case(n_groups)
    rng = np.random.default_rng(20 + n_groups)
    tables = _window(rng, columns, runs=5, dead_keys=(3, 17, 59))
    got, want = _check(tables, columns, options, funcs, groups, chunked)
    assert len(want) == 60
    by_key = {r["k"]: r for r in want}
    # a segment with no qualifying row: every column null, key kept
    assert all(by_key[3][name] is None for name, _ in columns)
    for seq_fields, members in groups:
        # a null sequence value skipped a later row somewhere, and a
        # winning row's null member overwrote
        assert any(r[seq_fields[0]] is not None and r[members[0]] is None
                   for r in want)
    if groups:
        ts = groups[0][0][0]
        last_by_arrival = {}
        for t in tables:
            last_by_arrival.update(zip(t.column("k").to_pylist(),
                                       t.column(ts).to_pylist()))
        assert sum(by_key[k][ts] != v for k, v in last_by_arrival.items()) \
            > 5


@pytest.mark.parametrize("remove", [False, True],
                         ids=["deletes_kept", "remove_record_on_delete"])
def test_partial_update_deletes(remove):
    columns, options, funcs, groups = _partial_update_case(1)
    if remove:
        options["partial-update.remove-record-on-delete"] = "true"
    rng = np.random.default_rng(31)
    tables = _window(rng, columns, runs=4, kinds=(INSERT, INSERT, DELETE))
    got, want = _check(tables, columns, options, funcs, groups)
    deleted = sum(r[KIND_COL] == DELETE for r in want)
    assert (deleted == 0 and len(want) < 60) if remove else deleted > 5


# -- aggregation ------------------------------------------------------------

AGGREGATES = [
    # (fold, the option's aggregate function, column kinds, extra options)
    ("sum", "sum", ("bigint", "int", "double"), {}),
    ("sum_ignore_retract", "sum", ("bigint", "double"),
     {"ignore-retract": "true"}),
    ("max", "max", ("bigint", "int", "double"), {}),
    ("min", "min", ("bigint", "int", "double"), {}),
    ("product", "product", ("int",), {}),
    ("count", "count", ("bigint", "double"), {}),
    ("bool_and", "bool_and", ("bool",), {}),
    ("bool_or", "bool_or", ("bool",), {}),
    ("last_non_null_value", "last_non_null_value",
     ("bigint", "double", "string", "list"), {}),
    ("last_value", "last_value", ("bigint", "string"), {}),
    ("first_non_null_value", "first_non_null_value",
     ("bigint", "double", "string"), {}),
    ("first_value", "first_value", ("bigint", "string"), {}),
    ("primary_key", "primary_key", ("bigint", "string"), {}),
    ("listagg", "listagg", ("string",), {}),
    ("collect", "collect", ("list",), {}),
    ("merge_map", "merge_map", ("map",), {}),
]


@pytest.mark.parametrize("chunked", [True, False],
                         ids=["four_runs", "one_chunk"])
@pytest.mark.parametrize("fold,function,kinds,extra", AGGREGATES,
                         ids=[a[0] for a in AGGREGATES])
def test_aggregation_equals_the_oracle(fold, function, kinds, extra,
                                       chunked):
    """Every aggregator family over nullable columns, with retracts
    (UPDATE_BEFORE, DELETE) among the rows and keys whose every value
    is null."""
    columns = [(f"v{i}", kind) for i, kind in enumerate(kinds)]
    options = {"merge-engine": "aggregation"}
    for name, _ in columns:
        options[f"fields.{name}.aggregate-function"] = function
        for key, value in extra.items():
            options[f"fields.{name}.{key}"] = value
    rng = np.random.default_rng(len(fold) + 100 * chunked)
    tables = _window(rng, columns, runs=4, dead_keys=(5, 41),
                     kinds=(INSERT, INSERT, AFTER, BEFORE, DELETE))
    got, want = _check(tables, columns, options,
                       {name: fold for name, _ in columns},
                       chunked=chunked)
    assert 20 < len(want) < 60              # deleted winners dropped
    for (name, _), col in zip(columns, got.columns[4:]):
        assert col.type == (pa.int64() if fold == "count"
                            else tables[0].column(name).type)
    if fold not in ("count", "bool_and", "bool_or"):
        values = [r["v0"] for r in want]
        assert None in values and any(v is not None for v in values)


def test_a_window_without_nulls_or_retracts():
    """No column has a null: no validity is gathered, the result is
    the same."""
    columns = [("s", "bigint"), ("m", "double"), ("l", "string")]
    options = {"merge-engine": "aggregation",
               "fields.s.aggregate-function": "sum",
               "fields.m.aggregate-function": "max",
               "fields.l.aggregate-function": "last_value"}
    tables = _window(np.random.default_rng(3), columns, runs=3,
                     null_rate=0.0)
    got, want = _check(tables, columns, options,
                       {"s": "sum", "m": "max", "l": "last_value"})
    assert got.num_rows == 60 and not any(c.null_count for c in got.columns)


@pytest.mark.parametrize("engine", ["aggregation", "partial-update"])
def test_an_empty_window(engine):
    columns, options, funcs, groups = _partial_update_case(1)
    if engine == "aggregation":
        options, groups = {"merge-engine": engine}, []
        funcs = {name: "last_non_null_value" for name, _ in columns}
    tables = _window(np.random.default_rng(1), columns, runs=1)
    got, want = _check([tables[0].slice(0, 0)], columns, options, funcs,
                       groups)
    assert got.num_rows == 0 and want == []
    assert got.schema.types[4:] == tables[0].schema.types[4:]
