"""Segmented-reduce merge engines: aggregation and partial-update.

reference: mergetree/compact/PartialUpdateMergeFunction.java,
AggregateMergeFunction + 24 FieldAggregators (mergetree/compact/aggregate/).

The record-at-a-time accumulate loop becomes: device sort by (key, seq)
(shared kernel in ops/merge.py) -> per-key segment ids -> per-column
segmented reduction. The sort leaves each key's rows contiguous, so the
ids are ascending and dense (the contract `_segment_ends` states and
checks) and a reduction is a segmented scan: numeric
sum/max/min/count/product run on device as a few element-wise passes
over the rows (`_sorted_segment_scan`; no scatter, which the chip would
execute row by row), float64 columns by `reduceat` on the host (the chip
has no 64-bit float, see _host_segment_reduce); order-based aggregates
(last/first[-non-null] value,
listagg, strings) reduce to a per-segment index selection computed on
device and a host-side Arrow take, so variable-length data never crosses
to HBM.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from paimon_tpu.metrics import MERGE_AGG_MS, MERGE_MASK_MS, MERGE_SELECT_MS
from paimon_tpu.obs.trace import span
from paimon_tpu.options import CoreOptions, MergeEngine
from paimon_tpu.ops.merge import (
    KIND_COL, SEQ_COL, device_sorted_winners, device_trip, gather,
    gather_values, prep_span, tiebreak_cut_keys, winners_span,
)
from paimon_tpu.ops.normkey import NormalizedKeyEncoder
from paimon_tpu.schema.table_schema import TableSchema
from paimon_tpu.types import RowKind

__all__ = ["merge_runs_agg", "field_aggregators",
           "aggregate_sorted_segments"]

_NUMERIC_DEVICE_AGGS = {"sum", "max", "min", "product", "count"}


def field_aggregators(schema: TableSchema,
                      options: CoreOptions) -> Dict[str, str]:
    """Resolve per-field aggregate function from options
    (`fields.<name>.aggregate-function`), reference
    CoreOptions.fieldAggFunc."""
    default = options.options.get_or("fields.default-aggregate-function",
                                     None)
    engine = options.merge_engine
    out = {}
    pk = set(schema.primary_keys)
    for f in schema.fields:
        if f.name in pk:
            continue
        func = options.options.get_or(
            f"fields.{f.name}.aggregate-function", None)
        if func is None:
            if engine == MergeEngine.PARTIAL_UPDATE:
                func = "last_non_null_value"
            else:
                func = default or "last_non_null_value"
        out[f.name] = func
    return out


def sequence_groups(schema: TableSchema,
                    options: CoreOptions) -> Dict[str, List[str]]:
    """`fields.<a,b>.sequence-group = c,d` -> {seq_field_key: [cols]}
    (reference PartialUpdateMergeFunction sequence groups)."""
    groups = {}
    for key in options.options.keys():
        if key.startswith("fields.") and key.endswith(".sequence-group"):
            seq_fields = key[len("fields."):-len(".sequence-group")]
            cols = [c.strip()
                    for c in options.options.get(key).split(",")]
            groups[seq_fields] = cols
    return groups


def _segment_ids_from_sort(lanes: np.ndarray, seq: np.ndarray,
                           truncated: Optional[np.ndarray] = None,
                           cut_keys=None, order_lanes=None,
                           packed: Optional[np.ndarray] = None,
                           run_starts: Optional[np.ndarray] = None):
    """Shared device sort -> (order over real rows, segment ids).

    If some rows' string keys were cut to the lane prefix (`truncated`),
    the sort's segments may join prefix-equal keys; `cut_keys` (the
    table, its key column names and the encoder) then puts the order in
    exact key order and cuts the segments by full-key equality
    (ops/merge.py `tiebreak_cut_keys`)."""
    n = lanes.shape[0]
    perm, winner, _ = device_sorted_winners(
        lanes, seq, "last", order_lanes, packed=packed,
        run_starts=run_starts if order_lanes is None else None)
    with winners_span(n, "agg"):
        if truncated is not None and truncated.any() \
                and cut_keys is not None:
            order, same = tiebreak_cut_keys(*cut_keys, lanes, truncated,
                                            perm, seq, order_lanes)
            seg_id = np.zeros(len(order), dtype=np.int64)
            np.cumsum(~same, out=seg_id[1:])
            win_sorted = np.ones(len(order), dtype=bool)
            win_sorted[:-1] = ~same
            return order, seg_id, win_sorted
        real = perm < n
        order = perm[real].astype(np.int64)
        win_sorted = winner[real]
        seg_end = win_sorted.copy()
        if len(seg_end):
            seg_end[-1] = True
        seg_id = np.concatenate([[0], np.cumsum(seg_end[:-1])]) \
            if len(seg_end) else np.zeros(0, np.int64)
        seg_id = seg_id.astype(np.int64)
    return order, seg_id, win_sorted


def _segment_ends(seg_ids: np.ndarray, num_seg: int):
    """The sorted-segment contract of this module, stated once: the rows
    of a reduction arrive in sorted order and `seg_ids` is ascending and
    dense — run k of equal ids carries id k, for k in [0, num_seg) — as
    the device sort's winner flags give it (`_segment_ids_from_sort`,
    the mesh engine's epilogue).  Both the host and the device reduce
    rely on it and neither can tell wrong ids from their result, so it
    is checked here: ValueError otherwise.

    Returns (is_end, ends): bool[n], True at the last row of each
    segment, and those rows' positions."""
    n = len(seg_ids)
    is_end = np.ones(n, dtype=bool)
    np.not_equal(seg_ids[1:], seg_ids[:-1], out=is_end[:n - 1])
    ends = np.flatnonzero(is_end)
    if len(ends) != num_seg or not np.array_equal(
            seg_ids[ends], np.arange(num_seg, dtype=seg_ids.dtype)):
        raise ValueError(f"segment ids not ascending and dense: "
                         f"{len(ends)} runs for {num_seg} segments")
    return is_end, ends


def _sorted_segment_scan(op, vals, starts):
    """Segmented inclusive scan of `vals` under the binary `op`; a
    segment begins at each True of `starts` (row 0 is one).  Afterwards
    the last row of a segment holds the segment's reduction.

    Doubling shifts: pass d folds row i-d into row i unless a segment
    start lies in (i-d, i]; `f` carries that OR over the 2d-1 rows up to
    i, so rows before d (f[0] is set) never take a rolled-around value.
    The loop ends once every row has seen its start: ceil(log2(longest
    segment)) element-wise passes, no scatter and no gather.  Only rows
    of one segment are ever combined, so integer results are exact
    (wrap-around included) and floats associate as a tree.

    A 64-bit result leaves as its two 32-bit words, uint32[2, n] (low,
    high): the chip keeps an int64 as such a pair, and fetched as int64
    it crosses the link several times slower than its words do."""
    def fold(carry):
        d, f, v = carry
        return (d * 2, f | jnp.roll(f, d),
                jnp.where(f, v, op(jnp.roll(v, d), v)))

    _, _, v = jax.lax.while_loop(lambda carry: ~jnp.all(carry[1]), fold,
                                 (jnp.int32(1), starts, vals))
    if v.dtype.itemsize == 8:
        return jnp.stack([v.astype(jnp.uint32),
                          (v >> 32).astype(jnp.uint32)])
    return v


# one XLA module each, under these names: the benchmark's
# segreduce_kernel_roofline finds its work by the `_seg_` in them
@jax.jit
def _seg_sum_jit(vals, starts):
    return _sorted_segment_scan(jnp.add, vals, starts)


@jax.jit
def _seg_max_jit(vals, starts):
    return _sorted_segment_scan(jnp.maximum, vals, starts)


@jax.jit
def _seg_min_jit(vals, starts):
    return _sorted_segment_scan(jnp.minimum, vals, starts)


@jax.jit
def _seg_prod_jit(vals, starts):
    return _sorted_segment_scan(jnp.multiply, vals, starts)


def _host_segment_reduce(ufunc, vals: np.ndarray, seg_ids: np.ndarray,
                         num_seg: int) -> np.ndarray:
    """Segmented reduce on the host, for the one value type the chip
    cannot hold: XLA's TPU backend keeps a float64 as a pair of
    float32, so 1e300 arrives as inf, 1e-300 as 0 and pi without its
    low bits — a reduce there cannot be bit-identical.  Relies on the
    sorted-segment contract (`_segment_ends`)."""
    with span("agg.host", cat="merge", rows=len(vals)):
        _, ends = _segment_ends(seg_ids, num_seg)
        starts = np.zeros(num_seg, dtype=np.intp)
        starts[1:] = ends[:-1] + 1
        return ufunc.reduceat(vals, starts)


def _padded_seg(fn_jit, ufunc):
    """Sorted-segment reduce `call(vals, seg_ids, num_seg) -> np.ndarray`
    under the contract of `_segment_ends`, which it checks.

    The device program (`_sorted_segment_scan`) needs boundaries, not
    ids: what crosses the link is the values and one start flag a row,
    and the whole scan comes back, of which the host keeps each
    segment's last row.  The row count pads to a power of two, so a
    compaction compiles O(log) shapes instead of one per window (a
    streamed merge emits hundreds of distinct row counts); every
    padding row is a segment of its own past the real ones, so it costs
    no pass and its value never touches a real segment.

    float64 values reduce on the host (`_host_segment_reduce`).  The
    fetch sits inside the `agg.device` span (bounds, pad, upload,
    program, download, take)."""
    def call(vals, seg_ids, num_seg):
        vals = np.asarray(vals)
        seg_ids = np.asarray(seg_ids)
        n = len(vals)
        if vals.dtype == np.float64:
            return _host_segment_reduce(ufunc, vals, seg_ids, num_seg)
        with device_trip("agg.device", rows=n, segments=num_seg):
            is_end, ends = _segment_ends(seg_ids, num_seg)
            m = 1 << max(10, int(n - 1).bit_length())
            padded = np.zeros(m, dtype=vals.dtype)
            padded[:n] = vals
            starts = np.ones(m, dtype=bool)
            starts[1:n] = is_end[:n - 1]
            out = np.asarray(fn_jit(jnp.asarray(padded),
                                    jnp.asarray(starts)))
            if out.ndim == 1:
                return out[ends]
            # a 64-bit scan comes back as its words: (low, high)
            joined = out[1][ends].astype(np.uint64)
            joined <<= np.uint64(32)
            joined |= out[0][ends]
            return joined.view(vals.dtype)
    return call


_seg_sum = _padded_seg(_seg_sum_jit, np.add)
_seg_max = _padded_seg(_seg_max_jit, np.maximum)
_seg_min = _padded_seg(_seg_min_jit, np.minimum)
_seg_prod = _padded_seg(_seg_prod_jit, np.multiply)


def _last_index_where(mask: np.ndarray, seg_id: np.ndarray,
                      num_seg: int) -> np.ndarray:
    """Per segment, the position (into sorted order) of the last True;
    -1 if none. Vectorized with segment_max over masked positions."""
    with _mask_span(len(mask)):
        pos = np.arange(len(mask), dtype=np.int64)
        masked = np.where(mask, pos, -1)
    return np.asarray(_seg_max(masked, seg_id, num_seg))


def _first_index_where(mask: np.ndarray, seg_id: np.ndarray,
                       num_seg: int) -> np.ndarray:
    n = len(mask)
    with _mask_span(n):
        pos = np.arange(n, dtype=np.int64)
        masked = np.where(mask, pos, n + 1)
    out = np.asarray(_seg_min(masked, seg_id, num_seg))
    return np.where(out > n, -1, out)


# order-based aggregates -> (index selection, over non-null rows only)
_INDEX_SELECTIONS = {
    "last_non_null_value": (_last_index_where, True),
    "last_value": (_last_index_where, False),
    "first_non_null_value": (_first_index_where, True),
    "first_value": (_first_index_where, False),
    # reference FieldPrimaryKeyAgg: the first value sticks
    "primary_key": (_first_index_where, True),
}


def _select_span(rows: int, groups: int, columns: int):
    """`agg.select`: which row of each segment gives a column its value
    — a sequence group's resolution (`groups` of them over `columns`
    members) or one order-based aggregate (`groups` 0) — masks on the
    host around the `agg.device` / `agg.host` spans of its reductions."""
    return span("agg.select", cat="merge", group="merge",
                metric=MERGE_SELECT_MS, rows=rows, groups=groups,
                columns=columns)


def _mask_span(rows: int, column: str = ""):
    """`agg.mask`: the epilogue's own numpy over a window of `rows` —
    the winners' positions, the retract mask, a column's validity and
    contribution mask, its values with nulls filled, the signed or
    masked operand of its reduction, its result as an Arrow array, the
    output table's assembly — between the `merge.gather` leaves and the
    `agg.device` / `agg.host` spans, never around one.  `column`: the
    column it works for, empty for the window's."""
    return span("agg.mask", cat="merge", group="merge",
                metric=MERGE_MASK_MS, rows=rows, column=column)


def _masked_numeric(result: np.ndarray, any_valid: np.ndarray,
                    out_type: pa.DataType) -> pa.Array:
    """Vectorized (values, null-mask) -> typed Arrow array; a per-row
    `.item()` comprehension here was the agg plane's hottest line."""
    arr = pa.array(result, mask=~any_valid)
    if arr.type != out_type:
        arr = arr.cast(out_type)
    return arr


_JAX_NUMERIC = {
    pa.int8(): np.int32, pa.int16(): np.int32, pa.int32(): np.int64,
    pa.int64(): np.int64, pa.float32(): np.float32,
    pa.float64(): np.float64, pa.bool_(): np.int32,
}


def merge_runs_agg(runs: Sequence[pa.Table], key_cols: Sequence[str],
                   schema: TableSchema, options: CoreOptions,
                   key_encoder: Optional[NormalizedKeyEncoder] = None,
                   seq_fields: Optional[Sequence[str]] = None
                   ) -> pa.Table:
    """Merge runs under aggregation / partial-update semantics.
    Returns a KV-shaped table (keys + sys cols + aggregated values),
    sorted by key."""
    with prep_span(sum(r.num_rows for r in runs)):
        table = pa.concat_tables(runs, promote_options="none")
        n = table.num_rows
        if n == 0:
            return table
        if key_encoder is None:
            key_encoder = NormalizedKeyEncoder(
                [table.schema.field(k).type for k in key_cols],
                nullable=[table.schema.field(k).nullable
                          for k in key_cols])
        lanes, truncated, packed = key_encoder.encode_table_ex(table,
                                                               key_cols)
        seq = np.asarray(
            table.column(SEQ_COL).combine_chunks().cast(pa.int64()))
        from paimon_tpu.ops.merge import user_seq_order_lanes
        order_lanes = user_seq_order_lanes(
            table, seq_fields, options.sequence_field_descending) \
            if seq_fields else None
        run_starts = np.concatenate(
            [[0], np.cumsum([r.num_rows for r in runs])]).astype(np.int64)
    order, seg_id, win_sorted = _segment_ids_from_sort(
        lanes, seq, truncated, (table, key_cols, key_encoder), order_lanes,
        packed=packed, run_starts=run_starts)
    return aggregate_sorted_segments(table, order, seg_id, win_sorted,
                                     key_cols, schema, options)


def aggregate_sorted_segments(table: pa.Table, order: np.ndarray,
                              seg_id: np.ndarray, win_sorted: np.ndarray,
                              key_cols: Sequence[str],
                              schema: TableSchema,
                              options: CoreOptions) -> pa.Table:
    """Engine-parameterized aggregation epilogue shared by the
    single-chip merge (``merge_runs_agg``, which computes the sort
    itself) and the mesh window engine (parallel/mesh_engine.py, whose
    [B, window] kernel hands back each lane's sorted order).

    `order`: positions into `table` in (key, user-seq, seq, arrival)
    order; `seg_id`: per-sorted-row key-segment id (ascending, dense);
    `win_sorted`: True at the last row of each segment.  Folds every
    segment per the table's merge engine and returns the KV-shaped
    merged rows in key order."""
    with span("agg.reduce", cat="merge", group="merge",
              metric=MERGE_AGG_MS, rows=len(order)):
        return _aggregate_sorted_segments(table, order, seg_id,
                                          win_sorted, key_cols, schema,
                                          options)


def _sorted_validity(column: pa.ChunkedArray, order: np.ndarray,
                     name: str) -> np.ndarray:
    """The column's validity in the merge's order, a bool a row.  A
    column without nulls is not read."""
    with _mask_span(len(order), name):
        if column.null_count == 0:
            return np.ones(len(order), dtype=bool)
        valid = np.asarray(pc.is_valid(column))
    return gather_values(valid, order)


def _sorted_values(column: pa.ChunkedArray, order: np.ndarray,
                   name: str, fill=0) -> np.ndarray:
    """A fixed-width column's values in the merge's order, nulls as
    `fill`."""
    with _mask_span(len(order), name):
        values = np.asarray(column.combine_chunks().fill_null(fill))
    return gather_values(values, order)


def _aggregate_sorted_segments(table, order, seg_id, win_sorted, key_cols,
                               schema, options) -> pa.Table:
    """No copy of the window in sorted order is made.  A column whose
    value is one chosen row of each segment (a key, the sequence, the
    kind, a sequence-group member, an order-based aggregate) is taken
    from the unsorted `table` at those rows alone; what a selection or
    a reduction reads over the window (the kinds, a sequence field, a
    validity, a summed column) is gathered by `order` column by column,
    when it is reached; a collection aggregate gathers its one column.
    Every one of these is a `merge.gather`."""
    n = len(order)
    with _mask_span(n):
        num_seg = int(seg_id[-1]) + 1 if len(seg_id) else 0
        win_pos = np.flatnonzero(win_sorted)       # last row of each segment
        kinds = np.asarray(table.column(KIND_COL).combine_chunks()
                           .cast(pa.int8()))
    kinds_sorted = gather_values(kinds, order)
    with _mask_span(n):
        retract = (kinds_sorted == RowKind.DELETE) | \
                  (kinds_sorted == RowKind.UPDATE_BEFORE)
        add_mask = ~retract

    aggs = field_aggregators(schema, options)
    remove_on_delete = options.get(
        CoreOptions.PARTIAL_UPDATE_REMOVE_RECORD_ON_DELETE)

    out_cols: Dict[str, pa.ChunkedArray] = {}

    def take_winners(columns: List[str], idx: np.ndarray) -> None:
        """`out_cols[name]`, for each of `columns`: the column's value
        at sorted position `idx` of each segment, null where `idx` < 0
        (a null index takes a null)."""
        with _mask_span(n, columns[0]):
            missing = idx < 0
            rows = pa.array(order[idx],
                            mask=missing if missing.any() else None)
        taken = gather(table.select(columns), rows)
        out_cols.update(zip(columns, taken.columns))

    def sorted_column(name: str) -> pa.ChunkedArray:
        return gather(table.select([name]), order).column(name)

    # the output's columns, in order: keys, sequence and kind, then the
    # schema's; those with no aggregator come from the segment winner row
    names = list(dict.fromkeys(list(key_cols) + [SEQ_COL, KIND_COL]
                               + [f.name for f in schema.fields]))
    take_winners([name for name in names if name not in aggs], win_pos)

    # sequence groups (partial-update): each group's member columns take
    # their values from the row with the LARGEST group-sequence value
    # instead of the global sequence order (reference
    # PartialUpdateMergeFunction sequence groups; ties -> later row wins)
    group_of: Dict[str, int] = {}      # member or sequence column -> group
    if options.merge_engine == MergeEngine.PARTIAL_UPDATE:
        groups = [([s.strip() for s in gkey.split(",")], cols)
                  for gkey, cols in sequence_groups(schema, options).items()]
        for g, (seq_fields, cols) in enumerate(groups):
            for colname in list(cols) + seq_fields:
                if options.options.get_or(
                        f"fields.{colname}.aggregate-function",
                        None) is not None:
                    raise NotImplementedError(
                        f"aggregate-function on sequence-group member "
                        f"{colname!r} (reference: aggregation within "
                        f"sequence groups) is not supported yet")
                group_of[colname] = g
        views = [[_sorted_sequence_field(table, s, order)
                  for s in seq_fields] for seq_fields, _ in groups]
        with _select_span(n, len(groups),
                          sum(len(cols) for _, cols in groups)):
            group_idx = [_seq_group_winner_index(fields, seg_id, num_seg,
                                                 add_mask)
                         for fields in views]
        del views
        for g, idx in enumerate(group_idx):
            members = [name for name in names
                       if name in aggs and group_of.get(name) == g]
            if members:
                take_winners(members, idx)

    for f in schema.fields:
        name = f.name
        if name not in aggs or name in group_of:
            continue
        func = aggs[name]
        column = table.column(name)
        ctype = column.type
        valid = _sorted_validity(column, order, name)
        with _mask_span(n, name):
            contrib_mask = valid & add_mask
        if func in _NUMERIC_DEVICE_AGGS and ctype in _JAX_NUMERIC:
            np_dtype = _JAX_NUMERIC[ctype]
            if func == "count":
                with _mask_span(n, name):
                    counted = contrib_mask.astype(np.int64)
                result = np.asarray(_seg_sum(counted, seg_id, num_seg))
                with _mask_span(n, name):
                    out_cols[name] = pa.array(result, pa.int64())
                continue
            vals = _sorted_values(column, order, name)
            if func == "sum":
                ignore_retract = options.options.get_or(
                    f"fields.{name}.ignore-retract", "false") == "true"
                with _mask_span(n, name):
                    vals = vals.astype(np_dtype, copy=False)
                    if ignore_retract:
                        # reference FieldIgnoreRetractAgg: retracts are
                        # no-ops instead of subtracting, and do not count
                        # as a contribution (all-retract segment -> null)
                        signed = np.where(retract, 0, vals)
                        contributed = valid & ~retract
                    else:
                        signed = np.where(retract, -vals, vals)
                        contributed = valid
                    signed = np.where(valid, signed, 0)
                    contributed = contributed.astype(np.int32)
                result = np.asarray(_seg_sum(signed, seg_id, num_seg))
                any_valid = np.asarray(
                    _seg_max(contributed, seg_id, num_seg))
                with _mask_span(n, name):
                    out_cols[name] = _masked_numeric(result, any_valid > 0,
                                                     ctype)
                continue
            if func in ("max", "min", "product"):
                with _mask_span(n, name):
                    vals = vals.astype(np_dtype, copy=False)
                    ident = {"max": _np_min_ident(np_dtype),
                             "min": _np_max_ident(np_dtype),
                             "product": np_dtype(1)}[func]
                    masked = np.where(contrib_mask, vals, ident)
                    contributed = contrib_mask.astype(np.int32)
                dev = {"max": _seg_max, "min": _seg_min,
                       "product": _seg_prod}[func](masked, seg_id,
                                                   num_seg)
                result = np.asarray(dev)
                any_valid = np.asarray(
                    _seg_max(contributed, seg_id, num_seg))
                with _mask_span(n, name):
                    out_cols[name] = _masked_numeric(result, any_valid > 0,
                                                     ctype)
                continue
        # order-based aggregates: pick an index per segment, host gather
        if func in _INDEX_SELECTIONS:
            pick, non_null = _INDEX_SELECTIONS[func]
            with _select_span(n, 0, 1):
                idx = pick(contrib_mask if non_null else add_mask,
                           seg_id, num_seg)
            take_winners([name], idx)
        elif func == "listagg":
            out_cols[name] = _listagg(sorted_column(name), contrib_mask,
                                      seg_id, num_seg, options, name)
        elif func == "collect":
            if not pa.types.is_list(ctype) and \
                    not pa.types.is_large_list(ctype):
                raise ValueError(
                    f"collect aggregate requires field {name!r} to be "
                    f"declared ARRAY<...>, got {f.type} (reference "
                    f"FieldCollectAgg)")
            out_cols[name] = _collect(sorted_column(name), contrib_mask,
                                      seg_id, num_seg, options, name)
        elif func == "merge_map":
            out_cols[name] = _merge_map(sorted_column(name),
                                        contrib_mask, seg_id, num_seg)
        elif func in ("rbm32", "rbm64"):
            out_cols[name] = _rbm_agg(sorted_column(name), contrib_mask,
                                      seg_id, num_seg, func, name)
        elif func in ("hll_sketch", "theta_sketch"):
            out_cols[name] = _sketch_agg(sorted_column(name),
                                         contrib_mask, seg_id, num_seg,
                                         func, name)
        elif func == "nested_update":
            out_cols[name] = _nested_update(sorted_column(name),
                                            contrib_mask, seg_id,
                                            num_seg, options, name, f)
        elif func in ("bool_and", "bool_or"):
            vals = _sorted_values(column, order, name, fill=False)
            with _mask_span(n, name):
                if func == "bool_or":
                    masked = vals & contrib_mask
                else:
                    masked = vals | ~contrib_mask
                masked = masked.astype(np.int32)
            dev = (_seg_max if func == "bool_or" else _seg_min)(
                masked, seg_id, num_seg)
            with _mask_span(n, name):
                out_cols[name] = pa.array(np.asarray(dev).astype(bool),
                                          pa.bool_())
        else:
            raise ValueError(f"Unknown aggregate function {func!r} "
                             f"for field {name}")

    with _mask_span(n):
        out = pa.table({name: out_cols[name] for name in names})
        if options.merge_engine == MergeEngine.PARTIAL_UPDATE \
                and not remove_on_delete:
            return out  # deletes ignored (retracts folded per column)
        # delete handling: drop segments whose winner is a retract
        winner_kinds = np.asarray(out.column(KIND_COL).combine_chunks()
                                  .cast(pa.int8()))
        drop = (winner_kinds == RowKind.DELETE)
        if drop.any():
            out = out.filter(pa.array(~drop))
        return out


def _sequence_values(fname: str, arr: pa.Array) -> np.ndarray:
    """A sequence field's values, nulls as 0, each type as the values
    its order is compared on: integers and temporals as int64 (so
    values above 2^53 stay distinct), floats as float64, decimals as
    their unscaled Python integers."""
    t = arr.type
    if pa.types.is_date32(t) or pa.types.is_time32(t):
        # 32-bit temporals -> int64 is not a direct arrow cast
        return np.asarray(arr.cast(pa.int32()).fill_null(0)) \
            .astype(np.int64)
    if pa.types.is_integer(t) or pa.types.is_temporal(t):
        return np.asarray(arr.cast(pa.int64()).fill_null(0))
    if pa.types.is_floating(t):
        return np.asarray(arr.cast(pa.float64()).fill_null(0))
    if pa.types.is_decimal(t):
        return np.array([0 if v is None else int(v.scaleb(t.scale))
                         for v in arr.to_pylist()], dtype=object)
    raise ValueError(f"sequence-group field {fname!r} must be numeric or "
                     f"temporal, got {t}")


def _sorted_sequence_field(table: pa.Table, fname: str, order: np.ndarray):
    """(values, validity) of one sequence field in the merge's order,
    as `_seq_group_winner_index` reads them."""
    column = table.column(fname)
    with _mask_span(len(order), fname):
        values = _sequence_values(fname, column.combine_chunks())
    return (gather_values(values, order),
            _sorted_validity(column, order, fname))


def _seq_group_winner_index(fields, seg_id: np.ndarray, num_seg: int,
                            add_mask: np.ndarray) -> np.ndarray:
    """Per segment: position (into sorted order) of the row with the
    largest non-null group-sequence tuple, the later row of equals; -1
    if no row qualifies.  Rows with any null sequence field never update
    the group (reference PartialUpdateMergeFunction: null sequence ->
    skip).

    `fields`: a (values, validity) pair of numpy arrays in sorted order
    for each sequence field, in declaration order (`_sequence_values`).
    The lexicographic maximum, field by field: the segment maximum of
    the field among the rows still in the running, which then keeps the
    rows that equal it.  Nothing is sorted or ranked; each field is
    compared on its native values."""
    n = len(add_mask)
    with _mask_span(n):
        running = add_mask.copy()
        for _, valid in fields:
            running &= valid
    for vals, _ in fields:
        # unscaled decimals (dtype object) are Python integers, wider
        # than any word the device holds: their maxima stay on the host
        on_host = vals.dtype == object
        with _mask_span(n):
            masked = np.where(running, vals, vals.min() - 1 if on_host
                              else _np_min_ident(vals.dtype.type))
        best = _host_segment_reduce(np.maximum, masked, seg_id, num_seg) \
            if on_host else _seg_max(masked, seg_id, num_seg)
        with _mask_span(n):
            best = best[seg_id]
            at_best = vals == best
            if vals.dtype.kind == "f":
                # a NaN is the largest value and equals itself, as a sort
                # would have it; `maximum` propagates it into `best`
                at_best |= np.isnan(vals) & np.isnan(best)
            running &= at_best
    return _last_index_where(running, seg_id, num_seg)


def _collect(col_sorted, mask, seg_id, num_seg, options, name):
    """reference aggregate/FieldCollectAgg: gather values into an array
    (fields.<name>.distinct=true dedups)."""
    distinct = options.options.get_or(f"fields.{name}.distinct",
                                      "false") == "true"
    vals = col_sorted.to_pylist()
    acc: List[Optional[list]] = [None] * num_seg
    for i in np.flatnonzero(mask):
        g = seg_id[i]
        if acc[g] is None:
            acc[g] = []
        v = vals[i]
        if isinstance(v, list):
            acc[g].extend(v)
        else:
            acc[g].append(v)
    if distinct:
        def _dedup(a):
            try:
                return list(dict.fromkeys(a))
            except TypeError:       # unhashable elements (nested types)
                seen, out = set(), []
                for v in a:
                    r = repr(v)
                    if r not in seen:
                        seen.add(r)
                        out.append(v)
                return out
        acc = [None if a is None else _dedup(a) for a in acc]
    return pa.array(acc, col_sorted.type if pa.types.is_list(
        col_sorted.type) else pa.list_(col_sorted.type))


def _seg_bounds(seg_id: np.ndarray, num_seg: int):
    """[start, end) of each segment in the (seg-sorted) row order."""
    starts = np.searchsorted(seg_id, np.arange(num_seg))
    ends = np.searchsorted(seg_id, np.arange(num_seg), side="right")
    return starts, ends


def _rbm_agg(col_sorted, mask, seg_id, num_seg, func: str, name: str):
    """Roaring-bitmap OR-union aggregate over pre-serialized bitmap
    blobs (reference FieldRoaringBitmap32Agg / FieldRoaringBitmap64Agg;
    wire format index/roaring.py)."""
    from paimon_tpu.index.roaring import (
        deserialize_roaring32, deserialize_roaring64,
        serialize_roaring32, serialize_roaring64,
    )
    deser = deserialize_roaring32 if func == "rbm32" \
        else deserialize_roaring64
    ser = serialize_roaring32 if func == "rbm32" else serialize_roaring64
    t = col_sorted.type
    if not (pa.types.is_binary(t) or pa.types.is_large_binary(t)):
        raise ValueError(f"{func} aggregate requires field {name!r} to "
                         f"be VARBINARY of serialized bitmaps")
    vals = col_sorted.combine_chunks().to_pylist()
    starts, ends = _seg_bounds(seg_id, num_seg)
    out = []
    for s, e in zip(starts, ends):
        parts = [deser(vals[i]) for i in range(s, e)
                 if mask[i] and vals[i] is not None]
        out.append(None if not parts
                   else bytes(ser(np.unique(np.concatenate(parts)))))
    return pa.array(out, t)


def _sketch_agg(col_sorted, mask, seg_id, num_seg, func: str, name: str):
    """HLL / theta sketch union aggregate (reference FieldHllSketchAgg,
    FieldThetaSketchAgg; wire format ops/sketch.py)."""
    from paimon_tpu.ops.sketch import hll_union, theta_union
    union = hll_union if func == "hll_sketch" else theta_union
    t = col_sorted.type
    if not (pa.types.is_binary(t) or pa.types.is_large_binary(t)):
        raise ValueError(f"{func} aggregate requires field {name!r} to "
                         f"be VARBINARY of serialized sketches")
    vals = col_sorted.combine_chunks().to_pylist()
    starts, ends = _seg_bounds(seg_id, num_seg)
    out = []
    for s, e in zip(starts, ends):
        merged = union(vals[i] for i in range(s, e)
                       if mask[i] and vals[i] is not None)
        out.append(merged)
    return pa.array(out, t)


def _nested_update(col_sorted, mask, seg_id, num_seg, options,
                   name: str, field):
    """ARRAY<ROW> accumulation (reference FieldNestedUpdateAgg):
    concatenate nested rows across versions; with
    `fields.<name>.nested-key = a,b` rows dedup by that key, last
    writer wins."""
    t = col_sorted.type
    if not (pa.types.is_list(t) or pa.types.is_large_list(t)) or \
            not pa.types.is_struct(t.value_type):
        raise ValueError(f"nested_update requires field {name!r} to be "
                         f"ARRAY<ROW<...>>, got {field.type}")
    keys_opt = options.options.get_or(f"fields.{name}.nested-key", None)
    nested_keys = [k.strip() for k in keys_opt.split(",")] \
        if keys_opt else None
    if nested_keys:
        struct_fields = {t.value_type.field(i).name
                         for i in range(t.value_type.num_fields)}
        unknown = [k for k in nested_keys if k not in struct_fields]
        if unknown:
            raise ValueError(
                f"fields.{name}.nested-key names {unknown} not in the "
                f"nested row {sorted(struct_fields)} (reference "
                f"FieldNestedUpdateAgg key resolution)")
    vals = col_sorted.combine_chunks().to_pylist()
    starts, ends = _seg_bounds(seg_id, num_seg)
    out = []
    for s, e in zip(starts, ends):
        acc: list = []
        seen = {}
        any_val = False
        for i in range(s, e):
            if not mask[i] or vals[i] is None:
                continue
            any_val = True
            for row in vals[i]:
                if nested_keys is None:
                    acc.append(row)
                    continue
                k = tuple(row.get(c) for c in nested_keys)
                if k in seen:
                    acc[seen[k]] = row    # in-place update keeps order
                else:
                    seen[k] = len(acc)
                    acc.append(row)
        out.append(acc if any_val else None)
    return pa.array(out, t)


def _merge_map(col_sorted, mask, seg_id, num_seg):
    """reference aggregate/FieldMergeMapAgg: later maps overwrite earlier
    keys."""
    vals = col_sorted.to_pylist()
    acc: List[Optional[dict]] = [None] * num_seg
    for i in np.flatnonzero(mask):
        g = seg_id[i]
        v = vals[i]
        if v is None:
            continue
        if acc[g] is None:
            acc[g] = {}
        acc[g].update(dict(v))
    return pa.array([None if a is None else list(a.items()) for a in acc],
                    col_sorted.type)


def _listagg(col_sorted, mask, seg_id, num_seg, options, name):
    sep = options.options.get_or(f"fields.{name}.list-agg-delimiter", ",")
    vals = col_sorted.to_pylist()
    acc: List[Optional[str]] = [None] * num_seg
    for i in np.flatnonzero(mask):
        s = vals[i]
        g = seg_id[i]
        acc[g] = s if acc[g] is None else acc[g] + sep + s
    return pa.array(acc, pa.string())


def _np_min_ident(dt):
    if np.issubdtype(dt, np.integer):
        return np.iinfo(dt).min
    return dt(-np.inf)


def _np_max_ident(dt):
    if np.issubdtype(dt, np.integer):
        return np.iinfo(dt).max
    return dt(np.inf)
