"""Keyed diff of two merged (key-unique) KV tables -> changelog rows.

This is the data-parallel heart of the compaction changelog producers:

- changelog-producer=full-compaction diffs the previous top level against
  the new full-compaction result (reference
  FullChangelogMergeTreeCompactRewriter / FullChangelogMergeFunctionWrapper)
- changelog-producer=lookup diffs the pre-compaction visible state of
  levels >0 against the post-compaction state, restricted to the keys
  touched by the incoming L0 records (reference
  LookupChangelogMergeFunctionWrapper.java:54 + LookupLevels.lookup)

Keys are compared via JOINT integer ranks: the key lanes of every input
table go through one np.unique(axis=0) so equal keys share a rank across
tables (exact — prefix-truncated string keys get a disambiguation column
ranked on the full key).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from paimon_tpu.ops.merge import KIND_COL
from paimon_tpu.ops.normkey import NormalizedKeyEncoder
from paimon_tpu.types import RowKind

__all__ = ["joint_key_ranks", "keyed_changelog_diff"]


def joint_key_ranks(tables: Sequence[pa.Table], key_cols: Sequence[str],
                    encoder: NormalizedKeyEncoder) -> List[np.ndarray]:
    """Rank the keys of several tables in ONE order-preserving space:
    equal keys (across tables) share a rank; rank order == key order.
    Truncated string keys are disambiguated by full-key sub-ranks."""
    if encoder.packs_single_key:
        # the key packs into one order-preserving u64: a 1-D unique
        # instead of a row-wise one, the same ranks
        packed = [encoder.encode_table_ex(t, key_cols)[2]
                  if t.num_rows else np.zeros(0, np.uint64)
                  for t in tables]
        _, inv = np.unique(np.concatenate(packed) if packed else
                           np.zeros(0, np.uint64), return_inverse=True)
        out, pos = [], 0
        for u in packed:
            out.append(inv[pos:pos + len(u)].astype(np.int64))
            pos += len(u)
        return out
    lanes_list, trunc_list = [], []
    for t in tables:
        lanes, trunc = encoder.encode_table(t, key_cols)
        lanes_list.append(lanes)
        trunc_list.append(trunc)
    sizes = [len(x) for x in lanes_list]
    all_lanes = np.concatenate(lanes_list) if sizes else \
        np.zeros((0, encoder.num_lanes), np.uint32)
    all_trunc = np.concatenate(trunc_list) if sizes else \
        np.zeros(0, bool)
    extra = np.zeros((len(all_lanes), 1), np.int64)
    if all_trunc.any():
        fulls = []
        for t, trunc in zip(tables, trunc_list):
            if not trunc.any():
                continue
            cols = [t.column(c) for c in key_cols]
            for i in np.flatnonzero(trunc):
                fulls.append(tuple(str(c[int(i)].as_py()) for c in cols))
        uniq = sorted(set(fulls))
        rank_of = {k: r + 1 for r, k in enumerate(uniq)}
        pos = 0
        fi = 0
        for t, trunc, n in zip(tables, trunc_list, sizes):
            for i in np.flatnonzero(trunc):
                extra[pos + int(i), 0] = rank_of[fulls[fi]]
                fi += 1
            pos += n
    mat = np.concatenate([all_lanes.astype(np.int64), extra], axis=1)
    _, inv = np.unique(mat, axis=0, return_inverse=True)
    out = []
    pos = 0
    for n in sizes:
        out.append(inv[pos:pos + n].astype(np.int64))
        pos += n
    return out


def keyed_changelog_diff(before: Optional[pa.Table], after: pa.Table,
                         key_cols: Sequence[str],
                         encoder: NormalizedKeyEncoder,
                         value_cols: Sequence[str],
                         restrict_table: Optional[pa.Table] = None,
                         keep_unchanged: bool = False) -> pa.Table:
    """Diff two key-unique KV tables (same KV layout) into changelog rows
    with _VALUE_KIND set to +I / -U / +U / -D.

    `restrict_table`: optional KV table; only keys occurring in it are
    diffed (the lookup producer's "keys touched by L0").
    `keep_unchanged`: a key in both tables gives -U/+U also where its
    values are equal (the lookup producer without
    `changelog-producer.row-deduplicate`).
    Output ordered with each -U immediately before its +U."""
    if before is None:
        before = after.slice(0, 0)
    tables = [before, after] + ([restrict_table]
                                if restrict_table is not None else [])
    ranks = joint_key_ranks(tables, key_cols, encoder)
    rk_before, rk_after = ranks[0], ranks[1]

    if restrict_table is not None:
        allowed = np.unique(ranks[2])
        keep_b = np.isin(rk_before, allowed)
        keep_a = np.isin(rk_after, allowed)
        before = before.filter(pa.array(keep_b))
        after = after.filter(pa.array(keep_a))
        rk_before = rk_before[keep_b]
        rk_after = rk_after[keep_a]

    # align: both inputs are key-sorted and key-unique
    pos = np.searchsorted(rk_before, rk_after)
    pos_clipped = np.minimum(pos, max(len(rk_before) - 1, 0))
    in_before = np.zeros(len(rk_after), dtype=bool)
    if len(rk_before):
        in_before = rk_before[pos_clipped] == rk_after
    matched_before_pos = pos_clipped[in_before]
    only_before = np.ones(len(rk_before), dtype=bool)
    only_before[matched_before_pos] = False

    inserts = after.filter(pa.array(~in_before))
    deletes = before.filter(pa.array(only_before))

    # matched keys: emit -U/+U only when the value actually changed
    a_m = after.filter(pa.array(in_before))
    b_m = before.take(pa.array(matched_before_pos))
    if a_m.num_rows and not keep_unchanged:
        differs = np.zeros(a_m.num_rows, dtype=bool)
        for c in value_cols:
            ca = a_m.column(c).combine_chunks()
            cb = b_m.column(c).combine_chunks()
            eq = pc.equal(ca, cb)
            both_null = pc.and_(pc.is_null(ca), pc.is_null(cb))
            same = pc.or_kleene(eq, both_null)
            if pa.types.is_floating(ca.type):
                # NaN != NaN under IEEE; an unchanged NaN is not a diff
                both_nan = pc.and_(pc.is_nan(ca.fill_null(0.0)),
                                   pc.is_nan(cb.fill_null(0.0)))
                same = pc.or_kleene(same, both_nan)
            differs |= ~np.asarray(same.fill_null(False))
        a_m = a_m.filter(pa.array(differs))
        b_m = b_m.filter(pa.array(differs))

    def _with_kind(t: pa.Table, kind: int) -> pa.Table:
        kinds = pa.array(np.full(t.num_rows, kind, np.int8), pa.int8())
        return t.set_column(t.column_names.index(KIND_COL), KIND_COL, kinds)

    parts: List[pa.Table] = []
    if deletes.num_rows:
        parts.append(_with_kind(deletes, RowKind.DELETE))
    if inserts.num_rows:
        parts.append(_with_kind(inserts, RowKind.INSERT))
    if a_m.num_rows:
        ub = _with_kind(b_m, RowKind.UPDATE_BEFORE)
        ua = _with_kind(a_m, RowKind.UPDATE_AFTER)
        idx = np.arange(a_m.num_rows)
        pair = pa.concat_tables([ub, ua], promote_options="none")
        order = np.empty(2 * a_m.num_rows, dtype=np.int64)
        order[0::2] = idx                   # -U
        order[1::2] = idx + a_m.num_rows    # +U
        parts.append(pair.take(pa.array(order)))
    if not parts:
        return after.slice(0, 0)
    return pa.concat_tables(parts, promote_options="none")
