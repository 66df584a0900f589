"""The plain reference of partial-update with sequence groups, and the
null-aware comparison that decides `correct` for a nullable table.

Numpy only, independent of `paimon_tpu`.  A column is a pair `(values,
valid)` as `data_wide` makes them; an invalid cell's value is 0.

Semantics, from upstream `PartialUpdateMergeFunction.java` with
`fields.<ts>.sequence-group` and no aggregate function inside a group:
rows of a key are applied in arrival order; a row whose group sequence
is null leaves the group alone; otherwise it replaces the whole group —
sequence column and every member, nulls included — when its sequence is
greater than or equal to the one held (so of equal sequences the later
row stays); a column in no group keeps its last non-null value.  No
departure at the options the configuration sets; deletes are not
written, so `partial-update.remove-record-on-delete` never applies.
"""

from __future__ import annotations

import numpy as np

from chipbench.reference import Mismatch, check_checksum  # noqa: F401


def merged(snapshots, key: str, groups, ungrouped) -> dict:
    """The compacted table, sorted by key.  Every snapshot holds every
    key exactly once, so sorted by key the snapshots line up row for row
    and arrival order is snapshot order."""
    by_key = [np.argsort(s[key][0], kind="stable") for s in snapshots]
    keys = snapshots[0][key][0][by_key[0]]
    for s, order in zip(snapshots, by_key):
        if not np.array_equal(s[key][0][order], keys) or \
                (len(keys) > 1 and (keys[1:] == keys[:-1]).any()):
            raise ValueError("a snapshot does not hold every key once")
    n = len(keys)

    def lined_up(name):
        return [(s[name][0][o], s[name][1][o])
                for s, o in zip(snapshots, by_key)]

    out = {key: (keys, np.ones(n, dtype=bool))}
    for ts, members in groups:
        best = np.zeros(n, dtype=np.int64)
        winner = np.full(n, -1, dtype=np.int8)  # the snapshot that stays
        for s, (values, valid) in enumerate(lined_up(ts)):
            take = valid & ((winner < 0) | (values >= best))
            best[take] = values[take]
            winner[take] = s
        for name in [ts] + members:
            columns = lined_up(name)
            values = np.zeros(n, dtype=columns[0][0].dtype)
            valid = np.zeros(n, dtype=bool)
            for s, (v, ok) in enumerate(columns):
                won = winner == s
                values[won] = v[won]
                valid[won] = ok[won]
            out[name] = (values, valid)
    for name in ungrouped:
        columns = lined_up(name)
        values = np.zeros(n, dtype=columns[0][0].dtype)
        valid = np.zeros(n, dtype=bool)
        for v, ok in columns:
            values[ok] = v[ok]
            valid |= ok
        out[name] = (values, valid)
    return out


def _bits(a) -> np.ndarray:
    """Values compare and sum by their bits: equal means identical."""
    if a.dtype == np.float64:
        return a.view(np.uint64)
    return a.astype(np.int64).view(np.uint64)


def _weights(keys) -> np.ndarray:
    """An odd multiplier per row from its key: a checksum weighted by it
    needs no sort, and still changes when two rows swap their cells."""
    return _bits(np.asarray(keys)) * np.uint64(2) + np.uint64(1)


def _column_sum(values, valid, weights) -> tuple:
    """(weighted sum of the valid values' bits, weighted sum over the
    nulls) modulo 2**64, and the count of nulls."""
    with np.errstate(over="ignore"):
        held = (_bits(values) * weights)[valid].sum(dtype=np.uint64)
        nulls = weights[~valid].sum(dtype=np.uint64)
    return int(held), int(nulls), int(len(valid) - valid.sum())


def checksum(cols: dict, key: str) -> dict:
    """Row count and, per column, `_column_sum`: independent of row
    order, and no null can pass for a zero."""
    weights = _weights(cols[key][0])
    out = {"rows": len(weights)}
    for name, (values, valid) in cols.items():
        out[name] = _column_sum(values, valid, weights)
    return out


def columns_of(arrow_table) -> dict:
    """A pyarrow table as `(values, valid)` columns; nulls hold 0."""
    import pyarrow.compute as pc
    out = {}
    for name in arrow_table.column_names:
        col = arrow_table.column(name).combine_chunks()
        valid = np.asarray(pc.is_valid(col))
        values = col.fill_null(0) if col.null_count else col
        out[name] = (values.to_numpy(zero_copy_only=False), valid)
    return out


def table_checksum(arrow_table, key: str) -> dict:
    """`checksum` of a pyarrow table."""
    return checksum(columns_of(arrow_table), key)


def check_equal(got: dict, want: dict, key: str, what: str):
    """Every cell of `got` equals the reference after sorting by key:
    validity first, then the bits of the valid values."""
    if len(got[key][0]) != len(want[key][0]):
        raise Mismatch(f"{what}: {len(got[key][0])} rows, reference has "
                       f"{len(want[key][0])}")
    order = np.argsort(got[key][0], kind="stable")
    keys = got[key][0][order]
    for name, (ref, ref_valid) in want.items():
        have, have_valid = got[name][0][order], got[name][1][order]
        if have.dtype != ref.dtype:
            raise Mismatch(f"{what}: {name} is {have.dtype}, reference "
                           f"{ref.dtype}")
        bad = np.flatnonzero(have_valid != ref_valid)
        if len(bad):
            i = int(bad[0])
            raise Mismatch(
                f"{what}: {len(bad)} cells of {name} are null on one side "
                f"only; first at key {int(keys[i])}: got "
                f"{'a value' if have_valid[i] else 'null'}")
        bad = np.flatnonzero(ref_valid & (_bits(have) != _bits(ref)))
        if len(bad):
            i = int(bad[0])
            raise Mismatch(f"{what}: {len(bad)} rows differ in {name}; "
                           f"first at key {int(keys[i])}: got {have[i]!r}, "
                           f"reference {ref[i]!r}")
