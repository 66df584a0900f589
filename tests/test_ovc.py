"""Offset-value coded merge (ops/ovc.py + native tree-of-losers).

Oracle discipline: every OVC result is compared against the sort-based
paths it replaces (PAIMON_DISABLE_OVC twin runs, np.lexsort ground
truth), across engines, key shapes (packed u64 and multi-lane string
prefixes), tie densities, and contract violations (unsorted runs MUST
fall back, never mis-merge).
"""

import os

import numpy as np
import pyarrow as pa
import pytest

from paimon_tpu.ops.merge import PATH_COUNTS, merge_runs
from paimon_tpu.ops.normkey import NormalizedKeyEncoder
from paimon_tpu.ops.ovc import OVC_OFF_SENTINEL, run_ovc_offsets


@pytest.fixture
def no_ovc(monkeypatch):
    def off():
        monkeypatch.setenv("PAIMON_DISABLE_OVC", "1")

    def on():
        monkeypatch.delenv("PAIMON_DISABLE_OVC", raising=False)
    on()
    return off, on


def _int_runs(seed, k=8, per=4_000, space=3_000, kinds=True):
    rng = np.random.default_rng(seed)
    runs = []
    base = 0
    for _ in range(k):
        ids = np.sort(rng.integers(0, space, per))
        runs.append(pa.table({
            "_KEY_id": pa.array(ids, pa.int64()),
            "_SEQUENCE_NUMBER": pa.array(
                np.arange(base, base + per), pa.int64()),
            "_VALUE_KIND": pa.array(
                rng.integers(0, 4, per).astype(np.int8) if kinds
                else np.zeros(per, np.int8), pa.int8()),
            "v": pa.array(rng.random(per), pa.float64()),
        }))
        base += per
    return runs


def _str_runs(seed, k=6, per=3_000):
    rng = np.random.default_rng(seed)
    runs = []
    base = 0
    for _ in range(k):
        keys = sorted(f"key-{x:07d}" for x in rng.integers(0, per, per))
        runs.append(pa.table({
            "_KEY_s": pa.array(keys, pa.string()),
            "_SEQUENCE_NUMBER": pa.array(
                np.arange(base, base + per), pa.int64()),
            "_VALUE_KIND": pa.array(np.zeros(per, np.int8), pa.int8()),
        }))
        base += per
    return runs


_INT_ENC = NormalizedKeyEncoder([pa.int64()], nullable=[False])
_STR_ENC = NormalizedKeyEncoder([pa.string()], nullable=[False])


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_dedup_equals_sort_path(no_ovc, monkeypatch, seed):
    off, on = no_ovc
    runs = _int_runs(seed)
    before = PATH_COUNTS["ovc"]
    got = merge_runs(runs, ["_KEY_id"], key_encoder=_INT_ENC).take()
    assert PATH_COUNTS["ovc"] == before + 1
    off()
    ref = merge_runs(runs, ["_KEY_id"], key_encoder=_INT_ENC).take()
    assert got.equals(ref)


@pytest.mark.parametrize("engine", ["deduplicate", "first-row"])
def test_engines_and_prev(no_ovc, engine):
    off, on = no_ovc
    runs = _int_runs(11, kinds=(engine == "deduplicate"))
    got = merge_runs(runs, ["_KEY_id"], merge_engine=engine,
                     key_encoder=_INT_ENC, with_prev=True,
                     drop_deletes=False)
    off()
    ref = merge_runs(runs, ["_KEY_id"], merge_engine=engine,
                     key_encoder=_INT_ENC, with_prev=True,
                     drop_deletes=False)
    assert np.array_equal(got.indices, ref.indices)
    assert np.array_equal(got.prev_indices, ref.prev_indices)


@pytest.mark.parametrize("seed", [0, 1])
def test_multilane_string_keys(no_ovc, seed):
    """The lane-matrix OVC path (wide keys — where single-int compares
    replace an L-key lexsort)."""
    off, on = no_ovc
    runs = _str_runs(seed)
    before = PATH_COUNTS["ovc"]
    got = merge_runs(runs, ["_KEY_s"], key_encoder=_STR_ENC).take()
    assert PATH_COUNTS["ovc"] == before + 1
    off()
    ref = merge_runs(runs, ["_KEY_s"], key_encoder=_STR_ENC).take()
    assert got.equals(ref)


def test_heavy_duplicate_ties(no_ovc):
    """All-equal and two-key windows: the code-tie fallthrough path
    (equal codes -> lane compares -> seq/run order) dominates here."""
    off, on = no_ovc
    base = 0
    runs = []
    for r in range(5):
        n = 2_000
        ids = np.sort(np.repeat([7, 9], n // 2))
        runs.append(pa.table({
            "_KEY_id": pa.array(ids, pa.int64()),
            "_SEQUENCE_NUMBER": pa.array(
                np.arange(base, base + n), pa.int64()),
            "_VALUE_KIND": pa.array(np.zeros(n, np.int8), pa.int8()),
        }))
        base += n
    got = merge_runs(runs, ["_KEY_id"], key_encoder=_INT_ENC,
                     with_prev=True, drop_deletes=False)
    off()
    ref = merge_runs(runs, ["_KEY_id"], key_encoder=_INT_ENC,
                     with_prev=True, drop_deletes=False)
    assert np.array_equal(got.indices, ref.indices)


def test_unsorted_run_falls_back(no_ovc):
    """A caller violating the sorted-run contract silently takes the
    sort path — identical answer, no mis-merge."""
    off, on = no_ovc
    rng = np.random.default_rng(2)
    runs = [t.take(pa.array(rng.permutation(t.num_rows)))
            for t in _int_runs(5, k=3, per=800)]
    before_host = PATH_COUNTS["host"]
    got = merge_runs(runs, ["_KEY_id"], key_encoder=_INT_ENC).take()
    assert PATH_COUNTS["host"] > before_host     # fell back
    off()
    ref = merge_runs(runs, ["_KEY_id"], key_encoder=_INT_ENC).take()
    assert got.equals(ref)


def test_agg_path_equivalence(no_ovc):
    from paimon_tpu.ops.agg import merge_runs_agg
    from paimon_tpu.options import CoreOptions
    from paimon_tpu.schema import Schema
    from paimon_tpu.types import BigIntType, DoubleType

    schema_obj = (Schema.builder()
                  .column("id", BigIntType(False))
                  .column("v", DoubleType())
                  .primary_key("id")
                  .options({"bucket": "1", "merge-engine": "aggregation",
                            "fields.v.aggregate-function": "sum"})
                  .build())
    from paimon_tpu.schema.table_schema import TableSchema
    ts = TableSchema.from_schema(0, schema_obj)
    options = CoreOptions(schema_obj.options)
    runs = []
    base = 0
    rng = np.random.default_rng(3)
    for _ in range(6):
        n = 2_000
        ids = np.sort(rng.integers(0, 500, n))
        runs.append(pa.table({
            "_KEY_id": pa.array(ids, pa.int64()),
            "_SEQUENCE_NUMBER": pa.array(
                np.arange(base, base + n), pa.int64()),
            "_VALUE_KIND": pa.array(np.zeros(n, np.int8), pa.int8()),
            "id": pa.array(ids, pa.int64()),
            "v": pa.array(rng.random(n), pa.float64()),
        }))
        base += n
    got = merge_runs_agg(runs, ["_KEY_id"], ts, options,
                         key_encoder=_INT_ENC)
    os.environ["PAIMON_DISABLE_OVC"] = "1"
    try:
        ref = merge_runs_agg(runs, ["_KEY_id"], ts, options,
                             key_encoder=_INT_ENC)
    finally:
        del os.environ["PAIMON_DISABLE_OVC"]
    assert got.equals(ref)


# ---------------------------------------------------------------------------
# code-level semantics
# ---------------------------------------------------------------------------


def test_native_merge_matches_lexsort_ground_truth():
    from paimon_tpu import native
    if native.load() is None:
        pytest.skip("no native runtime")
    rng = np.random.default_rng(1)
    k, per = 7, 5_000
    keys = np.concatenate([
        np.sort(rng.integers(0, 8_000, per).astype(np.uint64))
        for _ in range(k)])
    seq = np.arange(k * per, dtype=np.int64)
    starts = np.arange(0, k * per + 1, per, dtype=np.int64)
    perm, code = native.ovc_merge_u64(keys, seq, starts)
    gt = np.lexsort((seq, keys))
    assert np.array_equal(perm, gt)
    ks = keys[perm]
    assert np.array_equal(code[1:] == 0, ks[1:] == ks[:-1])
    # first output is never coded "equal to predecessor"
    assert code[0] != 0


def test_run_codes_reference_semantics():
    """The C initial-code pass (the ONE implementation — the merge
    entries run it internally) against hand-computed codes."""
    from paimon_tpu import native
    if native.load() is None:
        pytest.skip("no native runtime")
    run_codes_u64 = native.ovc_codes_u64
    run_codes_lanes = native.ovc_codes_lanes
    keys = np.array([(2 << 32) | 5, (2 << 32) | 5, (2 << 32) | 9,
                     (3 << 32) | 1], dtype=np.uint64)
    seq = np.arange(4, dtype=np.int64)
    starts = np.array([0, 4], dtype=np.int64)
    codes = run_codes_u64(keys, seq, starts)
    assert codes is not None
    assert codes[0] == (np.uint64(2) << np.uint64(32)) | np.uint64(2)
    assert codes[1] == 0                          # equal to predecessor
    assert codes[2] == (np.uint64(1) << np.uint64(32)) | np.uint64(9)
    assert codes[3] == (np.uint64(2) << np.uint64(32)) | np.uint64(3)
    # violation: descending keys
    bad = run_codes_u64(keys[::-1].copy(), seq, starts)
    assert bad is None
    # violation: equal keys, descending seq
    bad2 = run_codes_u64(
        np.array([5, 5], np.uint64), np.array([3, 1], np.int64),
        np.array([0, 2], np.int64))
    assert bad2 is None

    lanes = np.array([[1, 1, 1], [1, 1, 1], [1, 2, 0], [2, 0, 0]],
                     dtype=np.uint32)
    codes = run_codes_lanes(lanes, np.arange(4, dtype=np.int64),
                            np.array([0, 4], np.int64))
    assert codes is not None
    assert codes[0] == (np.uint64(3) << np.uint64(32)) | np.uint64(1)
    assert codes[1] == 0
    assert codes[2] == (np.uint64(2) << np.uint64(32)) | np.uint64(2)
    assert codes[3] == (np.uint64(3) << np.uint64(32)) | np.uint64(2)


def test_run_ovc_offsets_semantics():
    lanes = np.array([[1, 1], [1, 1], [1, 2], [3, 0], [3, 0]],
                     dtype=np.uint32)
    starts = np.array([0, 3, 5], np.int64)
    off = run_ovc_offsets(lanes, starts)
    assert off[0] == OVC_OFF_SENTINEL              # run 0 start
    assert off[1] == 2                             # all lanes equal
    assert off[2] == 1                             # differs at lane 1
    assert off[3] == OVC_OFF_SENTINEL              # run 1 start
    assert off[4] == 2


def test_device_kernel_ovc_equivalence(monkeypatch):
    """Forced device sort with run_starts exercises the OVC-aware
    winner-select — identical to the host path, including
    run-boundary equal keys that the sentinel must send through the
    lane-compare fallthrough."""
    runs = [
        pa.table({"_KEY_id": pa.array([1, 2, 7], pa.int64()),
                  "_SEQUENCE_NUMBER": pa.array([0, 1, 2], pa.int64()),
                  "_VALUE_KIND": pa.array([0, 0, 0], pa.int8())}),
        pa.table({"_KEY_id": pa.array([7, 8, 9], pa.int64()),
                  "_SEQUENCE_NUMBER": pa.array([3, 4, 5], pa.int64()),
                  "_VALUE_KIND": pa.array([0, 0, 0], pa.int8())}),
    ]
    monkeypatch.setenv("PAIMON_FORCE_DEVICE_SORT", "1")
    dev = merge_runs(runs, ["_KEY_id"], key_encoder=_INT_ENC,
                     with_prev=True, drop_deletes=False)
    monkeypatch.setenv("PAIMON_FORCE_HOST_SORT", "1")
    monkeypatch.delenv("PAIMON_FORCE_DEVICE_SORT")
    host = merge_runs(runs, ["_KEY_id"], key_encoder=_INT_ENC,
                      with_prev=True, drop_deletes=False)
    assert np.array_equal(dev.indices, host.indices)
    assert dev.indices.tolist()[-3:] == [3, 4, 5]  # 7 deduped to seq 3


def test_large_k_tree_path_matches_lexsort():
    """k > 64 takes the loser TREE (the scan path handles k <= 64):
    both must equal the lexsort ground truth."""
    from paimon_tpu import native
    if native.load() is None:
        pytest.skip("no native runtime")
    rng = np.random.default_rng(4)
    k, per = 100, 300
    keys = np.concatenate([
        np.sort(rng.integers(0, 2_000, per).astype(np.uint64))
        for _ in range(k)])
    seq = np.arange(k * per, dtype=np.int64)
    starts = np.arange(0, k * per + 1, per, dtype=np.int64)
    perm, code = native.ovc_merge_u64(keys, seq, starts)
    gt = np.lexsort((seq, keys))
    assert np.array_equal(perm, gt)
    ks = keys[perm]
    assert np.array_equal(code[1:] == 0, ks[1:] == ks[:-1])
    # lanes variant through the tree too
    lanes = np.stack([(keys >> 32).astype(np.uint32),
                      (keys & 0xFFFFFFFF).astype(np.uint32),
                      (keys % 7).astype(np.uint32)], axis=1)
    parts = []
    for j in range(k):
        sl = lanes[starts[j]:starts[j + 1]]
        order = np.lexsort((sl[:, 2], sl[:, 1], sl[:, 0]))
        parts.append(sl[order])
    lanes = np.ascontiguousarray(np.concatenate(parts))
    perm2, code2 = native.ovc_merge_lanes(lanes, seq, starts)
    gt2 = np.lexsort((seq, lanes[:, 2], lanes[:, 1], lanes[:, 0]))
    assert np.array_equal(perm2, gt2)


def test_window_rows_cap_bounds_windows_and_preserves_rows():
    """iter_merge_windows with a window cap yields BOUNDED windows
    whose concatenation equals the uncapped stream, with keys still
    never straddling windows."""
    from paimon_tpu.ops.merge_stream import iter_merge_windows

    rng = np.random.default_rng(6)
    k, per = 5, 20_000

    def run_iters():
        its = []
        base = 0
        for i in range(k):
            ids = np.sort(rng.integers(0, 30_000, per))
            t = pa.table({
                "_KEY_id": pa.array(ids, pa.int64()),
                "_SEQUENCE_NUMBER": pa.array(
                    np.arange(base + i * per, base + (i + 1) * per),
                    pa.int64()),
                "_VALUE_KIND": pa.array(np.zeros(per, np.int8),
                                        pa.int8())})
            its.append(iter([t]))
        return its

    rng = np.random.default_rng(6)
    capped = list(iter_merge_windows(run_iters(), ["_KEY_id"],
                                     _INT_ENC, window_rows=1_000))
    rng = np.random.default_rng(6)
    uncapped = list(iter_merge_windows(run_iters(), ["_KEY_id"],
                                       _INT_ENC))
    assert len(capped) > len(uncapped)
    sizes = [sum(it[0].num_rows for it in w) for w in capped]
    # ~k x window_rows bound (generous slack for duplicate groups)
    assert max(sizes) <= k * 1_000 + 1_000

    def flat_ids(windows):
        return np.concatenate([
            np.asarray(it[0].column("_KEY_id")) for w in windows
            for it in w])
    assert np.array_equal(np.sort(flat_ids(capped)),
                          np.sort(flat_ids(uncapped)))
    # key-window invariant: windows partition the keyspace in order
    prev_max = -1
    for w in capped:
        ids = np.concatenate([np.asarray(it[0].column("_KEY_id"))
                              for it in w])
        assert ids.min() > prev_max
        prev_max = ids.max()
