"""The write route: bucket assignment, group-by, hand-over.

`group_by_partition_bucket` against a dict-of-lists reference; the
fixed-bucket dispatch hands a one-group batch on without a copy (and
without aliasing the caller's kinds), hands a many-group batch's writers
its key columns taken and its value columns in place (a `write.take`
leaf, gathered by the flushes), says which in the `write.route` span and
the `write` registry group, and commits the same rows to the same
buckets either way.
"""

import numpy as np
import pyarrow as pa
import pytest

from paimon_tpu import obs
from paimon_tpu.core import write as write_mod
from paimon_tpu.core.bucket import bucket_of
from paimon_tpu.core.write import group_by_partition_bucket
from paimon_tpu.metrics import (
    WRITE_DEFERRED_GATHER_ROWS, WRITE_HASH_ROWS, WRITE_HASH_VECTOR_ROWS,
    WRITE_ROUTE_NOCOPY_ROWS, global_registry,
)
from paimon_tpu.obs.trace import metrics_enabled
from paimon_tpu.schema import Schema
from paimon_tpu.table import FileStoreTable
from paimon_tpu.types import BigIntType, DoubleType, RowKind, VarCharType
from tests.store_oracle import StoreOracle


@pytest.fixture(autouse=True)
def _clean_tracer():
    was_tracing = obs.tracing_enabled()
    obs.collector().clear()
    yield
    (obs.enable_tracing if was_tracing else obs.disable_tracing)()
    obs.collector().clear()


# -- the group-by --------------------------------------------------------------

def _reference_groups(table, buckets, partition_keys):
    """{(partition tuple, bucket): [row index, ...]} by a plain loop,
    and the group order the writers rely on: ascending bucket, then each
    partition key's values in their order of first appearance."""
    cols = [table.column(k).to_pylist() for k in partition_keys]
    groups, seen = {}, [[] for _ in cols]
    for i, b in enumerate(buckets.tolist()):
        part = tuple(c[i] for c in cols)
        for values, v in zip(seen, part):
            if v not in values:
                values.append(v)
        groups.setdefault((part, b), []).append(i)
    order = sorted(groups, key=lambda g: (
        g[1], *(values.index(v) for values, v in zip(seen, g[0]))))
    return groups, order


def _batch(n, seed):
    rng = np.random.default_rng(seed)
    return pa.table({
        "p": pa.array(rng.integers(0, 3, n), pa.int64()),
        "q": pa.array([("x", "y")[i] for i in rng.integers(0, 2, n)]),
        "id": pa.array(np.arange(n), pa.int64())})


@pytest.mark.parametrize("partition_keys", [[], ["p"], ["p", "q"]])
@pytest.mark.parametrize("num_buckets", [1, 2, 8])
def test_groups_match_a_dict_of_lists(num_buckets, partition_keys):
    n = 500
    table = _batch(n, seed=num_buckets)
    buckets = np.random.default_rng(num_buckets + 10) \
        .integers(0, num_buckets, n).astype(np.int32)
    got = group_by_partition_bucket(table, buckets, partition_keys)
    want, order = _reference_groups(table, buckets, partition_keys)
    assert [key for key, _ in got] == order
    for key, idx in got:
        assert idx.tolist() == want[key]


@pytest.mark.parametrize("partition_keys", [[], ["p"]])
def test_an_empty_batch_has_no_groups(partition_keys):
    assert group_by_partition_bucket(
        _batch(0, 0), np.empty(0, dtype=np.int32), partition_keys) == []


@pytest.mark.parametrize("bucket", [0, 5, -2])
def test_a_batch_of_one_group_is_the_batch_in_its_order(bucket):
    """Also away from bucket 0 (one hot bucket; -2 is postpone mode)."""
    table = _batch(40, 1).set_column(
        0, "p", pa.array(np.full(40, 7), pa.int64()))
    got = group_by_partition_bucket(
        table, np.full(40, bucket, dtype=np.int32), ["p"])
    assert [key for key, _ in got] == [((7,), bucket)]
    assert got[0][1].tolist() == list(range(40))


def test_negative_and_sparse_bucket_numbers_keep_their_order():
    buckets = np.array([70000, -2, 3, -2, 70000, 3, 3], dtype=np.int32)
    got = group_by_partition_bucket(_batch(7, 2), buckets, [])
    assert [(key, idx.tolist()) for key, idx in got] == [
        (((), -2), [1, 3]), (((), 3), [2, 5, 6]), (((), 70000), [0, 4])]


def test_a_null_partition_value_is_refused():
    table = pa.table({"p": pa.array([1, None, 2, 1], pa.int64())})
    with pytest.raises(ValueError, match="partition key 'p'"):
        group_by_partition_bucket(table, np.zeros(4, dtype=np.int32),
                                  ["p"])


def test_a_group_code_that_would_overflow_is_made_dense(monkeypatch):
    """Many partition keys of many values: the running code is ranked
    before it could leave an int64 (here: before it passes 16)."""
    monkeypatch.setattr(write_mod, "_MAX_GROUP_CODE", 16)
    n = 300
    table = _batch(n, 3)
    buckets = np.random.default_rng(4).integers(0, 8, n).astype(np.int32)
    got = group_by_partition_bucket(table, buckets, ["p", "q"])
    want, order = _reference_groups(table, buckets, ["p", "q"])
    assert [key for key, _ in got] == order
    assert all(idx.tolist() == want[key] for key, idx in got)


# -- the dispatch --------------------------------------------------------------

def _pk_table(path, buckets, extra=None):
    opts = {"bucket": str(buckets), "write-only": "true"}
    opts.update(extra or {})
    return FileStoreTable.create(
        str(path), Schema.builder()
        .column("id", BigIntType(False)).column("v", DoubleType())
        .primary_key("id").options(opts).build())


def _nocopy_rows():
    return global_registry().write_metrics() \
        .counter(WRITE_ROUTE_NOCOPY_ROWS).count


def _deferred_rows():
    return global_registry().write_metrics() \
        .counter(WRITE_DEFERRED_GATHER_ROWS).count


@pytest.mark.parametrize("parallelism", ["1", "4"])
def test_one_bucket_slice_with_reused_kinds_commits_what_it_was_given(
        tmp_path, parallelism):
    """The batch is handed on uncopied, so it must neither alias the
    caller's kinds array nor read past the slice it was given."""
    table = _pk_table(tmp_path / "t", 1,
                      {"write.flush.parallelism": parallelism})
    whole = pa.table({"id": pa.array(np.arange(100), pa.int64()),
                      "v": pa.array(np.arange(100) * 0.5)})
    part = whole.slice(30, 40)                     # ids 30..69
    kinds = np.full(40, RowKind.INSERT, dtype=np.int8)
    wb = table.new_batch_write_builder()
    with wb.new_write() as w:
        w.write_arrow(part, row_kinds=kinds)
        kinds[:] = RowKind.DELETE                  # the caller's array
        wb.new_commit().commit(w.prepare_commit())
    got = table.to_arrow().sort_by("id")
    assert got.column("id").to_pylist() == list(range(30, 70))
    assert got.column("v").to_pylist() == [i * 0.5 for i in range(30, 70)]


@pytest.mark.parametrize("num_buckets", ["1", "8"])
def test_a_fixed_seed_commit_reads_back_as_the_oracle_says(
        tmp_path, num_buckets):
    oracle = StoreOracle(str(tmp_path / "t"), seed=33, bucket=num_buckets,
                         partitioned=False, key_space=400,
                         allow_schema_add=False)
    for _ in range(6):
        oracle.step_write()
    oracle.check_now(f"{num_buckets} bucket(s)")
    table = oracle.table
    rt = table.schema.logical_row_type()
    keys = table.schema.bucket_keys()
    types = [rt.get_field(k).type for k in keys]
    rb = table.new_read_builder()
    splits = rb.new_scan().plan().splits
    assert {s.bucket for s in splits} <= set(range(int(num_buckets)))
    rows = 0
    for split in splits:
        for row in rb.new_read().to_arrow([split]).to_pylist():
            assert bucket_of([row[k] for k in keys], types,
                             int(num_buckets)) == split.bucket
            rows += 1
    assert rows == table.to_arrow().num_rows > 0
    if num_buckets == "8":
        assert len({s.bucket for s in splits}) > 1


@pytest.mark.parametrize("num_buckets,copied", [(1, False), (8, True)])
def test_the_span_and_the_counter_say_whether_the_batch_was_copied(
        tmp_path, num_buckets, copied):
    table = _pk_table(tmp_path / "t", num_buckets)
    n = 300
    batch = pa.table({"id": pa.array(np.arange(n), pa.int64()),
                      "v": pa.array(np.zeros(n))})
    obs.enable_tracing(max_spans=10_000)
    before, deferred = _nocopy_rows(), _deferred_rows()
    wb = table.new_batch_write_builder()
    with wb.new_write() as w:
        w.write_arrow(batch)
        wb.new_commit().commit(w.prepare_commit())
    spans = obs.take_spans()
    routes = [s for s in spans if s.name == "write.route"]
    assert len(routes) == 1
    attrs = routes[0].attrs
    assert attrs["rows"] == n
    assert attrs["copied_rows"] == (n if copied else 0)
    assert attrs["groups"] == (8 if copied else 1)
    assert _nocopy_rows() - before == (0 if copied else n)
    # several groups: the key columns and kinds taken in a leaf under
    # the route, the value columns gathered by the flushes from the batch
    takes = [s for s in spans if s.name == "write.take"]
    assert [s.parent_id for s in takes] == \
        ([routes[0].span_id] if copied else [])
    assert not any(s.parent_id == t.span_id for t in takes for s in spans)
    if metrics_enabled():
        assert _deferred_rows() - deferred == (n if copied else 0)


def _hash_counts():
    group = global_registry().write_metrics()
    return (group.counter(WRITE_HASH_ROWS).count,
            group.counter(WRITE_HASH_VECTOR_ROWS).count)


@pytest.mark.parametrize("key_type,num_buckets", [
    (BigIntType(False), 1), (BigIntType(False), 8),
    (VarCharType(False, 100), 8)])
def test_the_hash_span_and_counters_say_what_was_hashed(
        tmp_path, key_type, num_buckets):
    """One bucket hashes nothing: no `write.hash` leaf inside the route
    and nothing counted; more buckets hash every row, a BIGINT or a
    string key alike on a vectorised path."""
    from paimon_tpu.obs.trace import metrics_enabled
    if not metrics_enabled():
        pytest.skip("metrics are off in this process")
    table = FileStoreTable.create(
        str(tmp_path / "t"), Schema.builder()
        .column("id", key_type).column("v", DoubleType())
        .primary_key("id").options({"bucket": str(num_buckets),
                                    "write-only": "true"}).build())
    n = 300
    ids = pa.array(np.arange(n), pa.int64())
    if not isinstance(key_type, BigIntType):
        ids = ids.cast(pa.string())
    batch = pa.table({"id": ids, "v": pa.array(np.zeros(n))})
    obs.enable_tracing(max_spans=10_000)
    before = _hash_counts()
    wb = table.new_batch_write_builder()
    with wb.new_write() as w:
        w.write_arrow(batch)
        wb.new_commit().commit(w.prepare_commit())
    hashes = [s for s in obs.take_spans() if s.name == "write.hash"]
    hashed = 0 if num_buckets == 1 else n
    assert [s.attrs["rows"] for s in hashes] == ([n] if hashed else [])
    assert tuple(a - b for a, b in zip(_hash_counts(), before)) \
        == (hashed, hashed)
    assert table.to_arrow().num_rows == n
