"""String primary keys longer than the key lanes' prefix, held to a
plain full-bytes reference.

The lanes carry a key's first 16 bytes; `truncated` marks the rows
whose lanes do not determine the key, and `ops/merge.py`
`tiebreak_cut_keys` puts a sort by lanes into the exact order.  Every
merge entry point that sorts keys — `sort_table`, `merge_runs`
(deduplicate, first-row, `with_prev`) and `ops/agg.py` `merge_runs_agg`
(partial-update, aggregation) — is checked here on keys built to
collide: equal 16-byte prefixes, one key a prefix of another, a zero
byte the padding hides, multi-byte UTF-8 across the prefix's end, a
difference in the last byte, and a hot key of 5,000 versions; on the
host route and on the device programs (`PAIMON_FORCE_*` pins).  The
end-to-end test writes, scans and compacts a small YCSB-shaped table
through `FileStoreTable`."""

import zlib

import numpy as np
import pyarrow as pa
import pytest

from paimon_tpu.ops import merge as M
from paimon_tpu.ops.agg import merge_runs_agg
from paimon_tpu.ops.merge import KIND_COL, SEQ_COL, merge_runs, sort_table
from paimon_tpu.ops.normkey import NormalizedKeyEncoder
from paimon_tpu.types import RowKind

ROUTES = ["host", "device"]
PREFIX = "user123456789012"            # exactly the lanes' 16 bytes
assert len(PREFIX.encode()) == 16


@pytest.fixture(params=ROUTES)
def route(request, monkeypatch):
    monkeypatch.delenv("PAIMON_FORCE_HOST_SORT", raising=False)
    monkeypatch.delenv("PAIMON_FORCE_DEVICE_SORT", raising=False)
    monkeypatch.setenv("PAIMON_FORCE_HOST_SORT" if request.param == "host"
                       else "PAIMON_FORCE_DEVICE_SORT", "1")
    return request.param


def _colliding(kind: str):
    """Distinct keys (str, or bytes for `binary`) that defeat a 16-byte
    prefix every way we know."""
    if kind == "binary":
        head = bytes(range(240, 256))
        return [head, head + b"\x00", head + b"\x00\x00", head + b"\x01",
                head + b"\xff", head[:15], head[:15] + b"\x00", b"",
                b"\x00", b"\x00\x00", b"\x7f", b"\x80", b"\xff" * 20,
                head + b"\x80abc", head + b"\x7fabc", b"ab", b"ab\x00"]
    keys = [PREFIX, PREFIX + "0", PREFIX + "00", PREFIX + "1",
            PREFIX + "9", PREFIX + "\x00", PREFIX + "0\x00",
            PREFIX[:15], PREFIX[:15] + "\x00", PREFIX + "abcdefg",
            PREFIX + "abcdefh", PREFIX + "é", PREFIX + "ée",
            "é" * 8, "é" * 8 + "a", "é" * 9, "é" * 7 + "e" + "é",
            "ab", "ab\x00", "", "\x00", "user", "user1"]
    if kind == "ycsb":
        rng = np.random.default_rng(7)
        keys += [f"user{v}" for v in rng.integers(0, 1 << 63, 60)]
        # YCSB keys that share "user" + 12 digits
        keys += [f"user{PREFIX[4:]}{v}" for v in range(1000, 1040)]
    return keys


def _draw_keys(kind: str, n: int, rng, hot: int = 0):
    """n keys from the colliding pool, the first `hot` of them all one
    key (placed at random), as an Arrow array."""
    pool = _colliding(kind)
    keys = [pool[i] for i in rng.integers(0, len(pool), n)]
    if hot:
        for i in rng.choice(n, hot, replace=False):
            keys[i] = pool[1]
    return pa.array(keys, pa.binary() if kind == "binary" else pa.string())


def _ref_key(value):
    """A key as the reference orders it: nulls last, else its bytes."""
    if value is None:
        return (1, b"")
    return (0, value.encode() if isinstance(value, str) else value)


def _ref_keys(table, names):
    cols = [table.column(k).to_pylist() for k in names]
    return [tuple(_ref_key(c[i]) for c in cols)
            for i in range(table.num_rows)]


def _kv(keys: pa.Array, seq, kinds=None, extra=None):
    n = len(keys)
    cols = {"k": keys, SEQ_COL: pa.array(seq, pa.int64()),
            KIND_COL: pa.array(np.zeros(n, np.int8) if kinds is None
                               else kinds, pa.int8()),
            "v": pa.array(np.arange(n), pa.int64())}
    cols.update(extra or {})
    return pa.table(cols)


def _sorted_runs(table, names, num_runs: int, rng):
    """The table cut into `num_runs` runs (arrival order kept across
    them), each sorted by (full key, seq) as a flush writes it."""
    bounds = np.sort(rng.choice(np.arange(1, table.num_rows), num_runs - 1,
                                replace=False))
    runs = []
    for lo, hi in zip([0, *bounds], [*bounds, table.num_rows]):
        part = table.slice(lo, hi - lo)
        keys, seq = _ref_keys(part, names), part.column(SEQ_COL).to_pylist()
        runs.append(part.take(pa.array(sorted(
            range(part.num_rows), key=lambda i: (keys[i], seq[i], i)))))
    return runs


# -- the encoder's flag --------------------------------------------------------

def test_a_trailing_zero_byte_marks_the_key_as_cut():
    enc = NormalizedKeyEncoder([pa.string()], nullable=[False])
    lanes, cut = enc.encode_columns(
        [pa.array(["ab", "ab\x00", "", "\x00", PREFIX, PREFIX + "x",
                   "a\x00b"])])
    assert cut.tolist() == [False, True, False, True, False, True, False]
    assert (lanes[0] == lanes[1]).all() and (lanes[2] == lanes[3]).all()


# -- sort_table ---------------------------------------------------------------

@pytest.mark.parametrize("kind", ["string", "ycsb", "binary"])
@pytest.mark.parametrize("seq_ties", [False, True])
def test_sort_table_is_the_full_bytes_order(route, kind, seq_ties):
    rng = np.random.default_rng(zlib.crc32(f"{kind}{seq_ties}".encode()))
    n = 3000
    seq = np.arange(n) // 3 if seq_ties else rng.permutation(n)
    table = _kv(_draw_keys(kind, n, rng), seq)
    keys = _ref_keys(table, ["k"])
    want = sorted(range(n), key=lambda i: (keys[i], seq[i], i))
    assert sort_table(table, ["k"]).tolist() == want


def test_sort_table_with_a_hot_key_of_5000_versions(route):
    rng = np.random.default_rng(11)
    n = 7000
    seq = rng.permutation(n)
    table = _kv(_draw_keys("ycsb", n, rng, hot=5000), seq)
    keys = _ref_keys(table, ["k"])
    want = sorted(range(n), key=lambda i: (keys[i], seq[i], i))
    assert sort_table(table, ["k"]).tolist() == want


@pytest.mark.parametrize("key_order", [("s", "b"), ("b", "s")])
def test_sort_table_with_a_composite_key(route, key_order):
    """A cut string before an integer column: the lanes after the cut
    one may order two keys against their full bytes."""
    rng = np.random.default_rng(len(key_order[0]) * 5)
    n = 2500
    table = _kv(_draw_keys("string", n, rng), np.arange(n),
                extra={"b": pa.array(rng.integers(-3, 3, n), pa.int64())})
    table = table.rename_columns(["s" if c == "k" else c
                                  for c in table.column_names])
    names = list(key_order)
    keys = _ref_keys(table, names)
    want = sorted(range(n), key=lambda i: (keys[i], i))
    assert sort_table(table, names).tolist() == want


def test_sort_table_with_nulls_in_a_nullable_key(route):
    rng = np.random.default_rng(4)
    n = 2000
    keys = _draw_keys("string", n, rng).to_pylist()
    for i in rng.choice(n, 200, replace=False):
        keys[i] = None
    table = _kv(pa.array(keys, pa.string()), np.arange(n))
    ref = _ref_keys(table, ["k"])
    want = sorted(range(n), key=lambda i: (ref[i], i))
    assert sort_table(table, ["k"]).tolist() == want


# -- merge_runs ---------------------------------------------------------------

def _winners(table, names, keep: str, drop_deletes: bool):
    """(winner row, previous version or -1) per key in key order, by
    (seq, arrival); deletes dropped where asked."""
    keys = _ref_keys(table, names)
    seq = table.column(SEQ_COL).to_pylist()
    kinds = table.column(KIND_COL).to_pylist()
    rows = {}
    for i in range(table.num_rows):
        rows.setdefault(keys[i], []).append(i)
    out = []
    for key in sorted(rows):
        versions = sorted(rows[key], key=lambda i: (seq[i], i))
        win = versions[-1] if keep == "last" else versions[0]
        prev = versions[-2] if keep == "last" and len(versions) > 1 \
            else -1
        if drop_deletes and kinds[win] in (RowKind.DELETE,
                                           RowKind.UPDATE_BEFORE):
            continue
        out.append((win, prev))
    return out


@pytest.mark.parametrize("case", [
    ("string", "deduplicate", False), ("ycsb", "deduplicate", False),
    ("binary", "deduplicate", False), ("ycsb", "first-row", False),
    ("string", "deduplicate", True), ("ycsb", "deduplicate", True)])
def test_merge_runs_is_the_full_bytes_merge(route, case):
    kind, engine, with_prev = case
    rng = np.random.default_rng(zlib.crc32(repr(case).encode()))
    n = 3000
    kinds = np.where(rng.random(n) < 0.1, RowKind.DELETE, RowKind.INSERT)
    table = _kv(_draw_keys(kind, n, rng, hot=700), np.arange(n) // 2,
                kinds=kinds.astype(np.int8))
    runs = _sorted_runs(table, ["k"], 4, rng)
    res = merge_runs(runs, ["k"], merge_engine=engine,
                     with_prev=with_prev)
    want = _winners(res.table, ["k"],
                    "first" if engine == "first-row" else "last", True)
    assert res.indices.tolist() == [w for w, _ in want]
    if with_prev:
        assert res.prev_indices.tolist() == [p for _, p in want]


def test_merge_runs_of_one_unsorted_buffer_with_a_hot_key(route):
    """A deduplicate flush: one run in arrival order."""
    rng = np.random.default_rng(21)
    n = 7000
    table = _kv(_draw_keys("ycsb", n, rng, hot=5000), np.arange(n))
    res = merge_runs([table], ["k"], drop_deletes=False)
    assert res.indices.tolist() == [
        w for w, _ in _winners(table, ["k"], "last", False)]


# -- merge_runs_agg -----------------------------------------------------------

def _agg_schema(engine: str):
    from paimon_tpu.options import CoreOptions
    from paimon_tpu.schema import Schema
    from paimon_tpu.schema.table_schema import TableSchema
    from paimon_tpu.types import BigIntType, VarCharType
    options = {"bucket": "1", "merge-engine": engine}
    if engine == "aggregation":
        options["fields.n.aggregate-function"] = "sum"
    schema = (Schema.builder()
              .column("k", VarCharType(VarCharType.MAX_LENGTH, False))
              .column("f0", VarCharType(VarCharType.MAX_LENGTH))
              .column("f1", VarCharType(VarCharType.MAX_LENGTH))
              .column("n", BigIntType())
              .primary_key("k").options(options).build())
    return TableSchema.from_schema(0, schema), CoreOptions(schema.options)


def _one_field_updates(keys: pa.Array, rng):
    """YCSB's update: one field set, the others null."""
    n = len(keys)
    which = rng.integers(0, 3, n)
    text = [f"v{i}" for i in range(n)]
    return pa.table({
        "_KEY_k": keys, SEQ_COL: pa.array(np.arange(n) // 2, pa.int64()),
        KIND_COL: pa.array(np.zeros(n, np.int8), pa.int8()), "k": keys,
        "f0": pa.array([t if w == 0 else None
                        for t, w in zip(text, which)]),
        "f1": pa.array([t if w == 1 else None
                        for t, w in zip(text, which)]),
        "n": pa.array([int(i) if w == 2 else None
                       for i, w in enumerate(which)], pa.int64())})


def _ref_fold(table, engine: str):
    """Per key in full-bytes order: each string field its last non-null
    value by (seq, arrival); `n` the same, or its sum (aggregation)."""
    keys = _ref_keys(table, ["_KEY_k"])
    seq = table.column(SEQ_COL).to_pylist()
    cols = {c: table.column(c).to_pylist() for c in ("k", "f0", "f1", "n")}
    rows = {}
    for i in range(table.num_rows):
        rows.setdefault(keys[i], []).append(i)
    out = {c: [] for c in cols}
    for key in sorted(rows):
        versions = sorted(rows[key], key=lambda i: (seq[i], i))
        for c, vals in cols.items():
            seen = [vals[i] for i in versions if vals[i] is not None]
            if c == "n" and engine == "aggregation":
                out[c].append(sum(seen) if seen else None)
            else:
                out[c].append(seen[-1] if seen else None)
    return out


@pytest.mark.parametrize("engine", ["partial-update", "aggregation"])
@pytest.mark.parametrize("kind", ["string", "ycsb"])
def test_merge_runs_agg_folds_each_full_key(route, engine, kind):
    rng = np.random.default_rng(zlib.crc32(f"{engine}{kind}".encode()))
    n = 3000
    table = _one_field_updates(_draw_keys(kind, n, rng, hot=600), rng)
    runs = _sorted_runs(table, ["_KEY_k"], 3, rng)
    schema, options = _agg_schema(engine)
    got = merge_runs_agg(runs, ["_KEY_k"], schema, options)
    want = _ref_fold(pa.concat_tables(runs), engine)
    for c in ("k", "f0", "f1", "n"):
        assert got.column(c).to_pylist() == want[c], c


# -- what the tie-break counts, and who never enters it -----------------------

def _counts():
    from paimon_tpu.metrics import global_registry
    g = global_registry().group("merge")
    return (g.counter("tiebreak_rows").count,
            g.counter("tiebreak_resorted_rows").count)


def test_the_counters_tell_compared_rows_from_resorted_ones():
    from paimon_tpu.obs.trace import metrics_enabled
    if not metrics_enabled():
        pytest.skip("metrics are off in this process")
    # versions of keys under prefixes of their own: compared, never
    # resorted
    keys = pa.array([c * 20 for c in "aabbbc"])
    before = _counts()
    sort_table(_kv(keys, np.arange(6)), ["k"])
    compared, resorted = (a - b for a, b in zip(_counts(), before))
    assert (compared, resorted) == (5, 0)
    # two keys under one prefix, interleaved: the group is resorted
    keys = pa.array([PREFIX + "b", PREFIX + "a", PREFIX + "b", "x"])
    before = _counts()
    order = sort_table(_kv(keys, np.arange(4)), ["k"])
    compared, resorted = (a - b for a, b in zip(_counts(), before))
    assert order.tolist() == [1, 0, 2, 3] and (compared, resorted) == (3, 3)


@pytest.mark.parametrize("keys", [
    pa.array([3, 1, 2, 1], pa.int64()),
    pa.array(["b", "a", "c", "a"], pa.string()),
    pa.array([PREFIX[:15], PREFIX[:14], PREFIX[:15]], pa.string())])
def test_keys_the_lanes_determine_never_enter_the_tiebreak(route, keys,
                                                           monkeypatch):
    from paimon_tpu.ops import agg

    def refuse(*a, **k):
        raise AssertionError("tiebreak_cut_keys entered")
    monkeypatch.setattr(M, "tiebreak_cut_keys", refuse)
    monkeypatch.setattr(agg, "tiebreak_cut_keys", refuse)
    table = _kv(keys, np.arange(len(keys)))
    sort_table(table, ["k"])
    merge_runs([table], ["k"], with_prev=True)
    schema, options = _agg_schema("partial-update")
    if pa.types.is_string(keys.type):
        merge_runs_agg([_one_field_updates(keys, np.random.default_rng(0))],
                       ["_KEY_k"], schema, options)


# -- end to end: a YCSB-shaped table -------------------------------------------

def _fnv_keys(records: np.ndarray):
    """YCSB's key: "user" + the decimal digits of FNV-64 of the record
    number (CoreWorkload, insertorder=hashed), a signed long's magnitude."""
    h = np.full(len(records), 0xCBF29CE484222325, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for k in range(8):
            h ^= (records.astype(np.uint64) >> np.uint64(8 * k)) \
                & np.uint64(0xFF)
            h *= np.uint64(0x100000001B3)
    return [f"user{int(v) & ((1 << 63) - 1)}" for v in h]


def test_a_ycsb_table_written_scanned_and_compacted(tmp_path):
    from paimon_tpu.schema import Schema
    from paimon_tpu.table import FileStoreTable
    from paimon_tpu.types import VarCharType
    string = VarCharType(VarCharType.MAX_LENGTH)
    fields = [f"field{i}" for i in range(4)]
    builder = Schema.builder().column(
        "YCSB_KEY", VarCharType(VarCharType.MAX_LENGTH, False))
    for f in fields:
        builder = builder.column(f, string)
    schema = builder.primary_key("YCSB_KEY").options({
        "bucket": "4", "merge-engine": "partial-update",
        "write-only": "true"}).build()
    table = FileStoreTable.create(str(tmp_path / "usertable"), schema)
    rng = np.random.default_rng(39)
    pool = _fnv_keys(np.arange(800)) + [PREFIX + s for s in
                                        ("", "0", "00", "1", "\x00")]
    want = {}
    for commit in range(4):
        n = 1500
        ranks = np.minimum(rng.zipf(1.3, n) - 1, len(pool) - 1)
        which = rng.integers(0, len(fields), n)
        rows = {"YCSB_KEY": [pool[r] for r in ranks]}
        for j, f in enumerate(fields):
            rows[f] = [f"c{commit}r{i}" if w == j else None
                       for i, w in enumerate(which)]
        for i in range(n):
            state = want.setdefault(rows["YCSB_KEY"][i], {})
            f = fields[which[i]]
            state[f] = rows[f][i]
        wb = table.new_batch_write_builder()
        with wb.new_write() as w:
            w.write_arrow(pa.table(rows, schema=pa.schema(
                [("YCSB_KEY", pa.string())] + [(f, pa.string())
                                               for f in fields])))
            wb.new_commit().commit(w.prepare_commit())

    def check(got):
        got = got.sort_by("YCSB_KEY").to_pylist()
        assert [r["YCSB_KEY"] for r in got] == sorted(want, key=str.encode)
        for r in got:
            assert {f: r[f] for f in fields} == {
                f: want[r["YCSB_KEY"]].get(f) for f in fields}

    check(FileStoreTable.load(str(tmp_path / "usertable")).to_arrow())
    loaded = FileStoreTable.load(str(tmp_path / "usertable"))
    assert loaded.compact(full=True) is not None
    check(FileStoreTable.load(str(tmp_path / "usertable")).to_arrow())
