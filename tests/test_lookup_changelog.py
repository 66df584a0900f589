"""`changelog-producer=lookup` on the writer's own path: every commit
compacts its level-0 files (ForceUpLevel0Compaction) and carries the
changelog of the keys they touch, looked up in the bucket writer's
levels index (lookup/levels_index.py) by the device probe
(ops/lookup_probe.py).  Held to the plain reference in
tests/lookup_changelog_reference.py."""

import random
import zlib

import numpy as np
import pyarrow as pa
import pytest

from paimon_tpu.core.read import ROW_KIND_COL
from paimon_tpu.metrics import LOOKUP_LEVEL_ROWS_DECODED, global_registry
from paimon_tpu.schema import Schema
from paimon_tpu.table import FileStoreTable
from paimon_tpu.types import (
    BigIntType, DoubleType, IntType, RowKind, VarCharType,
)
from tests.lookup_changelog_reference import LookupChangelogReference

KEYS = {
    "bigint": [("id", BigIntType(False))],
    "composite": [("a", BigIntType(False)), ("b", IntType(False))],
    "string": [("k", VarCharType.string_type().copy(False))],
}
VALUES = [("v1", BigIntType()), ("v2", DoubleType())]

# 18-23-byte keys, most of them sharing their 16-byte lane prefix with
# others, so every probe of them is confirmed by its full bytes
_BASES = ["1234567890123", "1234567890124", "98765432101"]
STRING_KEYS = [f"user{b}{i:0{4 + (i % 3)}d}" for b in _BASES
               for i in range(16)]


def _key(kind, i):
    if kind == "bigint":
        return {"id": i * 7 - 40}
    if kind == "composite":
        return {"a": i // 5, "b": (i % 5) - 2}
    return {"k": STRING_KEYS[i % len(STRING_KEYS)]}


def _table(path, kind, engine, lookup_wait, extra=None):
    b = Schema.builder()
    for name, t in KEYS[kind] + VALUES:
        b = b.column(name, t)
    opts = {"bucket": "2", "changelog-producer": "lookup",
            "merge-engine": engine, "lookup-wait": str(lookup_wait).lower(),
            # the run-count trigger fires inside the sequence
            "num-sorted-run.compaction-trigger": "3"}
    opts.update(extra or {})
    return FileStoreTable.create(
        path, b.primary_key(*[k for k, _ in KEYS[kind]])
        .options(opts).build())


def _commits(seed, kind, engine, n_commits=10, key_space=40):
    rng = random.Random(seed)
    out = []
    for _ in range(n_commits):
        rows, kinds = [], []
        for _ in range(rng.randint(1, 30)):
            row = _key(kind, rng.randrange(key_space))
            row["v1"] = rng.randrange(5) if rng.random() > 0.2 else None
            row["v2"] = float(rng.randrange(3)) \
                if rng.random() > 0.2 else None
            rows.append(row)
            kinds.append(RowKind.DELETE if engine == "deduplicate"
                         and rng.random() < 0.15 else RowKind.INSERT)
        out.append((rows, kinds))
    return out


def _drain(scan, read):
    rows = []
    while True:
        plan = scan.plan()
        if plan is None:
            return rows
        rows.extend(read.to_arrow(plan).to_pylist())


def _sorted_changelog(rows, key_fields, kind_field):
    """(kind, row) pairs ordered by key then kind; -U is checked to sit
    right before its +U in the order the system emitted."""
    for i, r in enumerate(rows):
        if r[kind_field] == RowKind.UPDATE_BEFORE:
            nxt = rows[i + 1]
            assert nxt[kind_field] == RowKind.UPDATE_AFTER
            assert all(nxt[k] == r[k] for k in key_fields)
    pairs = [(r[kind_field], {k: v for k, v in r.items()
                              if k != kind_field}) for r in rows]
    return sorted(pairs, key=lambda p: (tuple(p[1][k] for k in key_fields),
                                        p[0]))


@pytest.mark.parametrize("kind", ["bigint", "composite", "string"])
@pytest.mark.parametrize("engine", ["deduplicate", "partial-update",
                                    "first-row"])
@pytest.mark.parametrize("lookup_wait", [True, False])
def test_lookup_changelog_matches_reference(tmp_path, kind, engine,
                                            lookup_wait):
    """Every commit's changelog and the table after it equal the plain
    reference: deletes, keys written several times in one commit, keys
    spread over several runs, universal compactions in the sequence;
    with lookup-wait=false a commit's changelog comes with the next."""
    table = _table(str(tmp_path / "t"), kind, engine, lookup_wait)
    key_fields = [k for k, _ in KEYS[kind]]
    ref = LookupChangelogReference(key_fields, [v for v, _ in VALUES],
                                   engine)
    wb = table.new_stream_write_builder()
    scan = table.copy({"scan.mode": "latest"}) \
        .new_read_builder().new_stream_scan()
    scan.plan()
    read = table.new_read_builder().new_read()
    commits = _commits(zlib.crc32(f"{kind}/{engine}".encode()), kind,
                       engine)
    pending = []
    with wb.new_write() as w:
        commit = wb.new_commit()
        for n, (rows, kinds) in enumerate(commits + [([], [])]):
            if rows:
                w.write_dicts(rows, row_kinds=kinds)
            commit.commit(w.prepare_commit(), commit_identifier=n)
            got = _drain(scan, read)
            want = ref.commit(rows, kinds)
            if not lookup_wait:
                pending, want = want, pending
            assert _sorted_changelog(got, key_fields, ROW_KIND_COL) == \
                sorted(want, key=lambda p: (
                    tuple(p[1][k] for k in key_fields), p[0])), \
                f"commit {n}"
            got_rows = sorted(table.to_arrow().to_pylist(),
                              key=lambda r: tuple(r[k] for k in key_fields))
            assert got_rows == ref.rows(), f"table after commit {n}"
    levels = {e.file.level for e in table.new_scan().read_entries(
        table.snapshot_manager.latest_snapshot())}
    assert 0 not in levels


def _bucket_writers(w):
    return w._write._writers


def test_index_after_compactions_equals_rebuilt(tmp_path):
    """A writer's index, updated by each compaction from memory, equals
    an index built from the bucket's files after the sequence."""
    from paimon_tpu.compact.manager import MergeTreeCompactManager
    table = _table(str(tmp_path / "t"), "bigint", "deduplicate", True)
    wb = table.new_stream_write_builder()
    with wb.new_write() as w:
        commit = wb.new_commit()
        for n, (rows, kinds) in enumerate(_commits(5, "bigint",
                                                   "deduplicate", 12)):
            w.write_dicts(rows, row_kinds=kinds)
            commit.commit(w.prepare_commit(), commit_identifier=n)
        snapshot = table.snapshot_manager.latest_snapshot()
        scan = table.new_scan()
        by_bucket = {}
        for e in scan.read_entries(snapshot):
            by_bucket.setdefault(e.bucket, []).append(e.file)
        checked = 0
        for (part, bucket), bw in _bucket_writers(w).items():
            files = by_bucket[bucket]
            fresh = MergeTreeCompactManager(
                table.file_io, table.path, table.schema, table.options,
                part, bucket, files).synced_index()
            kept = bw.lookup_index
            assert kept.levels == fresh.levels
            for level in fresh.levels:
                np.testing.assert_array_equal(kept.run_lanes(level),
                                              fresh.run_lanes(level))
            for f in files:
                if f.level > 0:
                    assert kept.table_of(f).equals(fresh.table_of(f))
                    checked += 1
        assert checked >= 2


def test_no_level_file_decoded_once_built(tmp_path):
    """A writer's first commit builds its index, decoding the upper
    levels once; the commits after it decode none (the `lookup` /
    `level_rows_decoded` counter)."""
    table = _table(str(tmp_path / "t"), "bigint", "deduplicate", True)
    wb = table.new_batch_write_builder()
    with wb.new_write() as w:
        w.write_arrow(pa.table({
            "id": pa.array(np.arange(0, 4000, 2), pa.int64()),
            "v1": pa.array(np.arange(2000), pa.int64()),
            "v2": pa.array(np.zeros(2000))}))
        wb.new_commit().commit(w.prepare_commit())
    counter = global_registry().lookup_metrics().counter(
        LOOKUP_LEVEL_ROWS_DECODED)
    swb = table.new_stream_write_builder()
    with swb.new_write() as w:
        commit = swb.new_commit()
        for n in range(6):
            ids = np.arange(n * 300, n * 300 + 900, 3)
            w.write_arrow(pa.table({
                "id": pa.array(ids, pa.int64()),
                "v1": pa.array(ids + n, pa.int64()),
                "v2": pa.array(np.ones(len(ids)))}))
            before = counter.count
            commit.commit(w.prepare_commit(), commit_identifier=n)
            assert counter.count - before == (2000 if n == 0 else 0), \
                f"commit {n}"
    got = table.to_arrow().sort_by("id")
    assert got.num_rows == len(set(range(0, 4000, 2))
                               | set(range(0, 2400, 3)))


_ONES = 0xFFFFFFFF


def _in_order(keys):
    return keys[np.lexsort(keys.T[::-1])]


def _probe_cases(case):
    """(run, probes) pairs of `uint32[n, L]` lanes, the run in key
    order, for one case of the probe test."""
    if case in ("2", "4"):
        lanes = int(case)
        rng = np.random.default_rng(lanes)
        keys = np.unique(rng.integers(0, 40, (5000, lanes)).astype(
            np.uint32), axis=0)
        queries = rng.integers(0, 40, (777, lanes)).astype(np.uint32)
        queries[:50] = keys[rng.integers(0, len(keys), 50)]
        return [(keys, queries)]
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    if case == "ragged_n":
        # every second value held: hits, misses between them, probes
        # below the first row and above the last; n < cap throughout
        out = []
        for n in (1, 127, 129, 1000, 1023, 3001):
            keys = np.stack([np.full(n, 7), 2 * np.arange(n) + 2],
                            axis=1).astype(np.uint32)
            v = np.arange(2 * n + 4)
            queries = np.concatenate([
                np.stack([np.full(len(v), 7), v], axis=1),
                [[6, _ONES], [8, 0], [0, 0], [_ONES, _ONES]]]).astype(
                    np.uint32)
            out.append((keys, queries[rng.permutation(len(queries))]))
        return out
    if case == "fences":
        # probes equal to a block's first row or last, one below or
        # above either, below the whole run and above it
        keys = np.unique(rng.integers(1, 1 << 20, (3000, 2)).astype(
            np.uint32), axis=0)
        edges = np.concatenate([keys[::128], keys[127::128]])
        less, more = edges.copy(), edges.copy()
        less[:, 1] -= 1
        more[:, 1] += 1
        queries = np.concatenate([
            edges, less, more, keys[:1] - 1, keys[-1:] + 1,
            [[0, 0], [_ONES, _ONES]]]).astype(np.uint32)
        return [(keys, queries[rng.permutation(len(queries))])]
    if case == "all_ones":
        # the padding's lanes as probes, against runs without such a
        # key, with it as the last row, and filling the capacity
        out = []
        for n, last in ((700, False), (701, True), (1024, True)):
            keys = np.unique(rng.integers(0, 1 << 16, (n * 2, 2)).astype(
                np.uint32), axis=0)[:n]
            if last:
                keys[-1] = _ONES
            queries = np.array([[_ONES, _ONES], [_ONES, 0], [0, _ONES],
                                keys[0], keys[-1], keys[n // 2]],
                               dtype=np.uint32)
            out.append((keys, queries))
        return out
    if case == "cut_runs":
        # runs of equal lanes, as keys cut to their prefix give them:
        # some span three blocks or more, some start or end on a fence
        sizes = [5, 400, 1, 122, 128, 256, 3, 640, 2, 129, 1]
        values = np.sort(rng.choice(1 << 20, len(sizes), replace=False))
        keys = np.repeat(np.stack([values // 7, values], axis=1),
                         sizes, axis=0).astype(np.uint32)
        near = np.stack([values // 7, values], axis=1)
        queries = np.concatenate([near, near - [0, 1], near + [0, 1],
                                  [[0, 0], [_ONES, _ONES]]]).astype(
                                      np.uint32)
        return [(keys, queries)]
    assert case == "one_probe"
    keys = np.unique(rng.integers(0, 1 << 12, (2000, 4)).astype(
        np.uint32), axis=0)
    return [(keys, q[None]) for q in
            (keys[0], keys[len(keys) // 3], keys[-1], keys[5] + 1,
             np.zeros(4, np.uint32), np.full(4, _ONES, np.uint32))]


@pytest.mark.parametrize("case", [
    pytest.param("2", id="2"), pytest.param("4", id="4"), "ragged_n",
    "fences", "all_ones", "cut_runs", "one_probe"])
def test_probe_kernel_matches_searchsorted(case):
    """Rows and ends as a binary search over the run's key bytes gives
    them, at the edges of the blocks the probe reads too."""
    from paimon_tpu.ops.lookup_probe import device_lanes, probe
    for keys, queries in _probe_cases(case):
        keys = _in_order(keys)
        lanes = keys.shape[1]

        def as_bytes(a):
            return np.ascontiguousarray(a.astype(">u4")).view(
                np.dtype((np.void, 4 * lanes))).ravel()

        kb, qb = as_bytes(keys), as_bytes(queries)
        lo = np.searchsorted(kb, qb, "left")
        hi = np.searchsorted(kb, qb, "right")
        index = device_lanes(keys)
        rows, ends = probe(index, len(keys), queries, upper=True)
        np.testing.assert_array_equal(rows, np.where(hi > lo, lo, -1))
        np.testing.assert_array_equal(ends, hi)
        only, none = probe(index, len(keys), queries)
        assert none is None
        np.testing.assert_array_equal(only, rows)


@pytest.mark.parametrize("upper", [False, True])
def test_probe_program_keeps_its_name(upper):
    """Every program of the probe is named `jit_lookup_probe`: the
    benchmark's roofline of the probe finds its device time by it."""
    import jax
    from paimon_tpu.ops.lookup_probe import (
        _lane_major, device_lanes, lookup_probe,
    )
    keys = np.arange(600, dtype=np.uint32).reshape(300, 2)
    lowered = lookup_probe.lower(device_lanes(keys), np.int32(300),
                                 jax.device_put(_lane_major(keys, 1024)),
                                 upper=upper)
    assert "@jit_lookup_probe" in lowered.as_text()
    assert lowered.compile().as_text().startswith(
        "HloModule jit_lookup_probe")


@pytest.mark.parametrize("key", ["packed", "nullable_int"])
def test_joint_key_ranks_dense_key_order(key):
    """A key that packs into one u64 is ranked on it, any other on its
    lanes: either way the ranks are the keys' dense order across the
    tables."""
    from paimon_tpu.ops.diff import joint_key_ranks
    from paimon_tpu.ops.normkey import NormalizedKeyEncoder
    rng = np.random.default_rng(3)
    if key == "packed":
        encoder = NormalizedKeyEncoder([pa.int64()], nullable=[False])
        cols = [rng.integers(-50, 50, n) for n in (40, 0, 25)]
        tables = [pa.table({"k": pa.array(c, pa.int64())}) for c in cols]
    else:
        encoder = NormalizedKeyEncoder([pa.int32()], nullable=[True])
        cols = [rng.integers(-50, 50, n) for n in (40, 0, 25)]
        tables = [pa.table({"k": pa.array(c, pa.int32())}) for c in cols]
    ranks = joint_key_ranks(tables, ["k"], encoder)
    _, want = np.unique(np.concatenate(cols), return_inverse=True)
    np.testing.assert_array_equal(np.concatenate(ranks), want)
