"""Seeded TPC-H LINEITEM as a primary-key table (`tpch-lineitem-pk`).

numpy only, one vectorised draw a column, by the rules of TPC-H v3
clause 4.2.3 as the configuration's file states them.  The shape —
order keys, lines an order, which commit an order is dealt to, which
orders the refresh pair inserts and deletes — comes from `data.key_seed`,
the values from `--seed`: every seed writes other rows under the same
keys, so the files' row counts and the padded sizes of every device
program never depend on the seed (the rule `chipbench/data.py` keeps).

A commit is a dict of numpy columns: integers as they are, DECIMAL(15,2)
as unscaled int64 (cents), DATE as int32 days since 1970-01-01, the
fixed-vocabulary strings as uint8 codes into the lists below, the
comment as (offsets int32[n+1], bytes uint8), and `kind` int8 (0 = +I,
3 = -D).  `to_arrow` turns one into the Arrow table the writer takes.
"""

from __future__ import annotations

import datetime
from concurrent.futures import ThreadPoolExecutor

import numpy as np

RETURNFLAGS = ["A", "N", "R"]
LINESTATUS = ["F", "O"]
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]

_EPOCH = datetime.date(1970, 1, 1)
START_DAY = (datetime.date(1992, 1, 1) - _EPOCH).days
LAST_ORDER_DAY = (datetime.date(1998, 8, 2) - _EPOCH).days
CURRENT_DAY = (datetime.date(1995, 6, 17) - _EPOCH).days
KIND_INSERT, KIND_DELETE = 0, 3
_ALPHABET = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz     ", dtype=np.uint8)


def order_key(index: np.ndarray) -> np.ndarray:
    """Sparse order keys: of every 32 consecutive keys the first 8 are
    used (clause 4.2.3's O_ORDERKEY)."""
    return (index // 8) * 32 + index % 8 + 1


def unused_key(index: np.ndarray) -> np.ndarray:
    """The other 24 of every 32: where a refresh inserts new orders."""
    return (index // 24) * 32 + 8 + index % 24 + 1


def shape(data: dict):
    """From `data.key_seed` alone: per commit the order keys and lines
    an order, and the kind of each of the last commit's orders."""
    g = np.random.default_rng(np.random.SeedSequence(data["key_seed"]))
    orders, commits = data["orders"], data["load_commits"]
    refresh = max(1, data["refresh_pairs"] * orders // 1000)
    lines = g.integers(1, 8, orders)
    dealt = g.integers(0, commits, orders)
    out = []
    for c in range(commits):
        idx = np.flatnonzero(dealt == c)
        out.append({"orderkey": order_key(idx), "lines": lines[idx],
                    "kind": np.zeros(len(idx), np.int8)})
    new = np.sort(g.choice(3 * orders, refresh, replace=False))
    gone = np.sort(g.choice(orders, refresh, replace=False))
    out.append({
        "orderkey": np.concatenate([unused_key(new), order_key(gone)]),
        "lines": np.concatenate([g.integers(1, 8, refresh), lines[gone]]),
        "kind": np.concatenate([np.full(refresh, KIND_INSERT, np.int8),
                                np.full(refresh, KIND_DELETE, np.int8)])})
    return out


def _values(rng_streams, orderkey, lines, kind, parts: int, suppliers: int):
    """One commit's rows from its orders, one stream a column."""
    g = [np.random.default_rng(s) for s in rng_streams]
    n_orders = len(orderkey)
    rows = int(lines.sum())
    first = np.cumsum(lines) - lines            # an order's first row
    order_of = np.repeat(np.arange(n_orders), lines)
    linenumber = (np.arange(rows) - first[order_of] + 1).astype(np.int32)
    partkey = g[0].integers(1, parts + 1, rows)
    quantity = g[1].integers(1, 51, rows)
    retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    orderdate = g[2].integers(START_DAY, LAST_ORDER_DAY + 1, n_orders)
    shipdate = (orderdate[order_of] + g[3].integers(1, 122, rows))
    receiptdate = shipdate + g[4].integers(1, 31, rows)
    returned = g[5].integers(0, 2, rows) * 2            # A or R
    lengths = g[6].integers(10, 44, rows)
    offsets = np.zeros(rows + 1, np.int32)
    np.cumsum(lengths, out=offsets[1:])
    return {
        "l_orderkey": np.repeat(orderkey, lines),
        "l_partkey": partkey,
        "l_suppkey": (partkey + g[7].integers(0, 4, rows)
                      * (suppliers // 4 + (partkey - 1) // suppliers))
        % suppliers + 1,
        "l_linenumber": linenumber,
        "l_quantity": quantity * 100,
        "l_extendedprice": quantity * retail,
        "l_discount": g[8].integers(0, 11, rows),
        "l_tax": g[9].integers(0, 9, rows),
        "l_returnflag": np.where(receiptdate <= CURRENT_DAY, returned, 1)
        .astype(np.uint8),
        "l_linestatus": (shipdate > CURRENT_DAY).astype(np.uint8),
        "l_shipdate": shipdate.astype(np.int32),
        "l_commitdate": (orderdate[order_of]
                         + g[10].integers(30, 91, rows)).astype(np.int32),
        "l_receiptdate": receiptdate.astype(np.int32),
        "l_shipinstruct": g[11].integers(0, len(INSTRUCTIONS), rows)
        .astype(np.uint8),
        "l_shipmode": g[12].integers(0, len(MODES), rows).astype(np.uint8),
        "l_comment": (offsets, _ALPHABET[g[13].integers(
            0, len(_ALPHABET), int(offsets[-1]), dtype=np.uint8)]),
        "kind": np.repeat(kind, lines),
    }


def gen_commits(seed: int, data: dict):
    """The configuration's commits: `load_commits` batch commits that
    deal the population's orders at random (no key twice), then one
    commit of `refresh_pairs` refresh pairs — RF1's new orders on unused
    keys as +I rows and every line of RF2's old orders as -D rows."""
    shapes = shape(data)
    parts = max(1, data["orders"] * 2 // 15)        # SF x 200,000
    suppliers = max(4, data["orders"] // 150)       # SF x 10,000
    streams = np.random.SeedSequence(seed).spawn(len(shapes))
    with ThreadPoolExecutor(max_workers=len(shapes)) as pool:
        futures = [pool.submit(_values, s.spawn(14), sh["orderkey"],
                               sh["lines"], sh["kind"], parts, suppliers)
                   for s, sh in zip(streams, shapes)]
        return [f.result() for f in futures]


# -- the table and its Arrow form --------------------------------------------

def create_table(catalog, name: str, table_cfg: dict):
    from paimon_tpu.catalog.catalog import Identifier
    from paimon_tpu.schema import Schema
    from paimon_tpu.types import parse_data_type

    builder = Schema.builder()
    for column, sql in table_cfg["columns"]:
        builder = builder.column(column, parse_data_type(sql))
    schema = builder.primary_key(*table_cfg["primary_key"]).options(
        {"bucket": str(table_cfg["buckets"]),
         **table_cfg["options"]}).build()
    database, _, table = name.partition(".")
    catalog.create_database(database, ignore_if_exists=True)
    ident = Identifier(database, table)
    catalog.create_table(ident, schema, False)
    return catalog.get_table(ident)


def _decimal(unscaled: np.ndarray):
    import pyarrow as pa
    words = np.empty((len(unscaled), 2), np.int64)
    words[:, 0] = unscaled
    words[:, 1] = unscaled >> 63                    # the sign's extension
    return pa.Array.from_buffers(pa.decimal128(15, 2), len(unscaled),
                                 [None, pa.py_buffer(words)])


def to_arrow(commit: dict):
    """The commit as the writer takes it; `_ROW_KIND` only where a row
    is not an insert."""
    import pyarrow as pa

    def coded(codes, vocabulary):
        return pa.DictionaryArray.from_arrays(
            pa.array(codes, pa.int8()), pa.array(vocabulary)) \
            .cast(pa.string())

    def date(days):
        return pa.Array.from_buffers(pa.date32(), len(days),
                                     [None, pa.py_buffer(days)])

    offsets, chars = commit["l_comment"]
    cols = {
        "l_orderkey": pa.array(commit["l_orderkey"], pa.int64()),
        "l_partkey": pa.array(commit["l_partkey"], pa.int64()),
        "l_suppkey": pa.array(commit["l_suppkey"], pa.int64()),
        "l_linenumber": pa.array(commit["l_linenumber"], pa.int32()),
        "l_quantity": _decimal(commit["l_quantity"]),
        "l_extendedprice": _decimal(commit["l_extendedprice"]),
        "l_discount": _decimal(commit["l_discount"]),
        "l_tax": _decimal(commit["l_tax"]),
        "l_returnflag": coded(commit["l_returnflag"], RETURNFLAGS),
        "l_linestatus": coded(commit["l_linestatus"], LINESTATUS),
        "l_shipdate": date(commit["l_shipdate"]),
        "l_commitdate": date(commit["l_commitdate"]),
        "l_receiptdate": date(commit["l_receiptdate"]),
        "l_shipinstruct": coded(commit["l_shipinstruct"], INSTRUCTIONS),
        "l_shipmode": coded(commit["l_shipmode"], MODES),
        "l_comment": pa.Array.from_buffers(
            pa.string(), len(offsets) - 1,
            [None, pa.py_buffer(offsets), pa.py_buffer(chars)]),
    }
    if commit["kind"].any():
        cols["_ROW_KIND"] = pa.array(commit["kind"], pa.int8())
    return pa.table(cols)
