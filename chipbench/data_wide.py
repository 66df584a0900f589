"""Seeded snapshots of a wide, nullable partial-update table.

The row is whatever the configuration's `table` says: a key, sequence
groups (`fields.<ts>.sequence-group = <members>`) and ungrouped columns
over the four SQL types of `data._TYPES`.  A column of a snapshot is a
pair `(values, valid)` of numpy arrays; an invalid cell's value is 0.
The keys are drawn from `key_seed`, which belongs to the configuration,
and everything else from `--seed`, so the sizes never depend on it.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

_DRAWS = {"BIGINT": lambda g, shape: g.integers(0, 1 << 40, shape),
          "DOUBLE": lambda g, shape: g.random(shape),
          "INT": lambda g, shape: g.integers(0, 100, shape, dtype=np.int32)}


def layout(table_cfg: dict):
    """(key, [(sequence column, [members])], [ungrouped], {name: SQL
    type}) of the configuration's table, groups in declaration order."""
    types = dict(table_cfg["columns"])
    (key,) = table_cfg["primary_key"]
    groups = []
    for option, members in table_cfg["options"].items():
        if option.startswith("fields.") and \
                option.endswith(".sequence-group"):
            groups.append((option[len("fields."):-len(".sequence-group")],
                           members.split(",")))
    grouped = {key} | {c for ts, members in groups for c in [ts] + members}
    ungrouped = [name for name, _ in table_cfg["columns"]
                 if name not in grouped]
    return key, groups, ungrouped, types


def gen_snapshots(seed: int, keys: int, key_seed: int, table_cfg: dict,
                  pattern: dict):
    """`pattern["count"]` snapshots, each holding every key in [0, keys)
    exactly once in an order of its own.  Snapshot s writes the groups
    `pattern["writes"][s]` and leaves the others all null.  In a written
    group the sequence column is null with probability `ts_null` and
    each member with `member_null`; an ungrouped column is null with
    probability `ungrouped_null` in every snapshot.

    One vectorised draw per column (values, then validity, for all the
    snapshots that write it), each column on its own stream and its own
    thread (numpy draws without the interpreter lock)."""
    key, groups, ungrouped, types = layout(table_cfg)
    count = pattern["count"]
    every = list(range(count))
    plan = []                   # (name, null rate, snapshots that write it)
    for k, (ts, members) in enumerate(groups):
        writers = [s for s in every if k in pattern["writes"][s]]
        plan.append((ts, pattern["ts_null"], writers))
        plan += [(m, pattern["member_null"], writers) for m in members]
    plan += [(u, pattern["ungrouped_null"], every) for u in ungrouped]

    def draw(item, stream):
        name, p_null, writers = item
        g = np.random.default_rng(stream)
        shape = (len(writers), keys)
        values = _DRAWS[types[name]](g, shape)
        valid = g.random(shape) >= p_null
        values[~valid] = 0      # as the comparison expects of a null
        return values, valid

    key_streams = np.random.SeedSequence(key_seed).spawn(count)
    streams = np.random.SeedSequence(seed).spawn(len(plan))
    with ThreadPoolExecutor(max_workers=8) as pool:
        drawn = list(pool.map(draw, plan, streams))
        orders = list(pool.map(
            lambda s: np.random.default_rng(s).permutation(keys),
            key_streams))
    snapshots = [{key: (orders[s], np.ones(keys, dtype=bool))}
                 for s in every]
    for (name, _, writers), (values, valid) in zip(plan, drawn):
        for s in every:
            if s in writers:
                i = writers.index(s)
                snapshots[s][name] = (values[i], valid[i])
            else:
                snapshots[s][name] = (np.zeros(keys, dtype=values.dtype),
                                      np.zeros(keys, dtype=bool))
    return snapshots


def to_arrow(snapshot: dict, table_cfg: dict):
    """One snapshot as a pyarrow table in the table's column order."""
    import pyarrow as pa
    arrays = {}
    for name, _ in table_cfg["columns"]:
        values, valid = snapshot[name]
        kind = pa.from_numpy_dtype(values.dtype)
        if valid.all():
            arrays[name] = pa.array(values, kind)
        elif valid.any():
            arrays[name] = pa.array(values, kind, mask=~valid)
        else:
            arrays[name] = pa.nulls(len(values), kind)
    return pa.table(arrays)
