"""The dedicated-compaction deployment (`chipbench/configs/
mor50m-dedup-mesh4.json` at a tier-1 size): a write-only deduplicate
table of 8 buckets and 8 overlapping commits, compacted by the mesh
engine through the normal entry point and through `compact_table_mesh`
on 1, 3, 4 and the suite's 8 virtual lanes.  Every result is held row
for row, doubles by their bits, to a numpy reference written here (no
`paimon_tpu` code computes it) and to the one-chip path's output; a
second test holds the spans and counters the benchmark reads.
"""

import shutil

import numpy as np
import pyarrow as pa
import pytest

import jax

from paimon_tpu import obs
from paimon_tpu.metrics import global_registry
from paimon_tpu.ops import merge as M
from paimon_tpu.parallel import bucket_mesh, compact_table_mesh
from paimon_tpu.schema import Schema
from paimon_tpu.table import FileStoreTable
from paimon_tpu.types import BigIntType, DoubleType, IntType, VarCharType

COMMITS = 8


def _commits(rows, key_space, seed, string_keys=False):
    """`COMMITS` batches with overlapping keys, as numpy columns."""
    rng = np.random.default_rng(seed)
    per = rows // COMMITS
    out = []
    for _ in range(COMMITS):
        ids = rng.integers(0, key_space, per)
        if string_keys:
            # most keys fit the 16-byte normalized prefix; the keys of
            # the top twentieth share one and are cut by it, so the
            # windows at the end of every bucket hold truncated keys
            cut = ids >= key_space - key_space // 20
            ids = np.where(cut,
                           np.char.add("zzzzzzzzzzzzzzzz-long-",
                                       ids.astype(str)),
                           np.char.add("k", np.char.zfill(ids.astype(str),
                                                          7)))
        out.append({"id": ids,
                    "v1": rng.integers(0, 1 << 40, per),
                    "v2": rng.random(per),
                    "v3": rng.integers(0, 100, per, dtype=np.int32)})
    return out


def _reference(commits):
    """Deduplicate: the last written row of every key, in commit order;
    sorted by key.  Plain numpy."""
    cols = {k: np.concatenate([c[k] for c in commits]) for k in commits[0]}
    order = np.argsort(cols["id"], kind="stable")
    ids = cols["id"][order]
    last = np.ones(len(ids), dtype=bool)
    last[:-1] = ids[1:] != ids[:-1]
    return {k: v[order][last] for k, v in cols.items()}


def _build(path, commits, buckets, string_keys=False):
    key_type = VarCharType(nullable=False) if string_keys \
        else BigIntType(False)
    schema = (Schema.builder().column("id", key_type)
              .column("v1", BigIntType()).column("v2", DoubleType())
              .column("v3", IntType()).primary_key("id")
              .options({"bucket": str(buckets), "write-only": "true",
                        "merge-engine": "deduplicate"}).build())
    table = FileStoreTable.create(path, schema)
    for c in commits:
        wb = table.new_batch_write_builder()
        with wb.new_write() as w:
            w.write_arrow(pa.table({
                "id": pa.array(c["id"]) if string_keys
                else pa.array(c["id"], pa.int64()),
                "v1": pa.array(c["v1"], pa.int64()),
                "v2": pa.array(c["v2"], pa.float64()),
                "v3": pa.array(c["v3"], pa.int32())}))
            wb.new_commit().commit(w.prepare_commit())
    return table


def _columns(table):
    """The table's rows sorted by key, doubles as their bits."""
    got = table.to_arrow()
    cols = {"id": np.asarray(got.column("id").to_pylist())
            if pa.types.is_string(got.schema.field("id").type)
            else got.column("id").to_numpy(),
            "v1": got.column("v1").to_numpy(),
            "v2": got.column("v2").to_numpy(),
            "v3": got.column("v3").to_numpy()}
    order = np.argsort(cols["id"], kind="stable")
    cols = {k: v[order] for k, v in cols.items()}
    cols["v2"] = cols["v2"].view(np.uint64)
    return cols


def _assert_rows(got, want, what):
    want = dict(want, v2=want["v2"].view(np.uint64))
    for k in want:
        assert len(got[k]) == len(want[k]), (what, k)
        assert np.array_equal(got[k], want[k]), (what, k)


def _counter(name):
    return global_registry().group("compaction").counter(name).count


# name -> (table, lanes or None for `compact(full=True)` under the
# option, dynamic options of the compaction)
CASES = {
    "normal_path_8_lanes": ("wide", None, {}),
    "mesh_1_lane": ("wide", 1, {}),
    "mesh_3_lanes_one_drains_first": ("wide", 3, {}),
    "mesh_4_lanes": ("wide", 4, {}),
    "fewer_buckets_than_lanes": ("two_buckets", 4, {}),
    "truncated_keys_host_route": ("strings", 4,
                                  {"tpu.merge.window-rows": "512"}),
    "three_windows_a_bucket": ("wide", 4,
                               {"tpu.merge.window-rows": "1024"}),
}


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    """The tables, built once: {name: (path, reference columns)}."""
    assert len(jax.devices()) >= 8, "conftest should give 8 CPU devices"
    root = tmp_path_factory.mktemp("mesh_deployment")
    specs = {"wide": dict(rows=200_000, key_space=100_000, buckets=8),
             "two_buckets": dict(rows=40_000, key_space=20_000, buckets=2),
             "strings": dict(rows=40_000, key_space=20_000, buckets=4,
                             string_keys=True)}
    out = {}
    for seed, (name, spec) in enumerate(specs.items()):
        commits = _commits(spec["rows"], spec["key_space"], 3200 + seed,
                           spec.get("string_keys", False))
        path = str(root / name)
        _build(path, commits, spec["buckets"],
               spec.get("string_keys", False))
        out[name] = (path, _reference(commits))
    return out


def _clone(deployment, name, tmp_path, leaf):
    path = str(tmp_path / leaf)
    shutil.copytree(deployment[name][0], path)
    return FileStoreTable.load(path)


@pytest.mark.parametrize("case", CASES)
def test_mesh_route_equals_the_reference_and_the_one_chip_path(
        deployment, tmp_path, case):
    name, lanes, dynamic = CASES[case]
    want = deployment[name][1]
    single = _clone(deployment, name, tmp_path, "single")
    assert single.compact(full=True) is not None
    one_chip = _columns(single)
    _assert_rows(one_chip, want, "one-chip path")

    meshed = _clone(deployment, name, tmp_path, "mesh").copy(dynamic)
    steps_before, paths_before = _counter("mesh_steps"), dict(M.PATH_COUNTS)
    if lanes is None:
        routed = meshed.copy({"tpu.mesh.compact": "true"})
        assert routed.compact(full=True) is not None
        buckets, windows = 8, None
    else:
        stats = compact_table_mesh(meshed, mesh=bucket_mesh(lanes))
        assert stats.snapshot_id is not None and stats.lanes == lanes
        assert stats.output_rows == len(want["id"])
        buckets, windows = stats.buckets, stats.windows
        assert len(stats.lane_rows) == lanes
        assert sum(stats.lane_rows) == stats.input_rows
    assert meshed.latest_snapshot().commit_kind == "COMPACT"
    got = _columns(meshed)
    _assert_rows(got, want, "mesh route")
    for k in got:
        assert np.array_equal(got[k], one_chip[k]), k
    # fully compacted: one run at the top level in every bucket
    top = meshed.options.num_levels - 1
    for s in meshed.new_read_builder().new_scan().plan().splits:
        assert all(f.level == top for f in s.data_files)

    kernel_windows = M.PATH_COUNTS["device"] - paths_before["device"]
    host_windows = sum(M.PATH_COUNTS[k] - paths_before[k]
                       for k in ("host", "ovc"))
    assert _counter("mesh_steps") > steps_before and kernel_windows > 0
    if windows is not None:
        assert kernel_windows == windows
    if case == "mesh_3_lanes_one_drains_first":
        assert sorted(stats.lane_rows)[0] < 0.8 * max(stats.lane_rows)
    if case == "fewer_buckets_than_lanes":
        assert buckets == 2 and sorted(stats.lane_rows)[:2] == [0, 0]
    if case == "truncated_keys_host_route":
        assert host_windows > 0     # the host merge inside a mesh run
    else:
        assert host_windows == 0
    if case == "three_windows_a_bucket":
        assert windows >= 3 * buckets


@pytest.fixture
def ring():
    """Tracing is process-global: the ring on for one test, and the
    switches as they were after it."""
    was_tracing, size = obs.tracing_enabled(), obs.collector().max_spans
    obs.collector().clear()
    obs.enable_tracing(max_spans=50_000)
    yield
    (obs.enable_tracing if was_tracing else obs.disable_tracing)()
    obs.collector().resize(size)
    obs.collector().clear()


def test_a_mesh_compaction_leaves_the_normal_paths_spans(
        deployment, tmp_path, ring):
    table = _clone(deployment, "wide", tmp_path, "t").copy(
        {"tpu.mesh.compact": "true", "tpu.merge.window-rows": "2048"})
    gather_bytes = global_registry().group("merge").counter("gather_bytes")
    before = (_counter("mesh_steps"), _counter("mesh_padded_rows"),
              M.PATH_COUNTS["device"], gather_bytes.count)
    assert table.compact(full=True) is not None
    spans = obs.take_spans()
    by_id = {s.span_id: s for s in spans}

    def named(name):
        return [s for s in spans if s.name == name]

    def ancestors(s):
        while s.parent_id is not None:
            s = by_id[s.parent_id]
            yield s.name

    root, = named("compact.table")
    lanes = len(jax.devices())
    assert root.thread == "MainThread" and root.parent_id is None
    assert (root.attrs["groups"], root.attrs["workers"]) == (8, lanes)
    task, = named("compact.task")
    assert task.parent_id == root.span_id and task.thread == "MainThread"
    assert task.attrs["route"] == "mesh" and task.attrs["buckets"] == 8
    assert task.attrs["lanes"] == lanes
    assert sum(task.attrs["lane_rows"]) == task.attrs["rows"] \
        == root.attrs["rows"]
    assert task.attrs["skew"] >= 1.0

    windows, devices = named("compaction.window"), named("merge.device")
    assert len(windows) == len(devices) >= 3
    for w, d in zip(windows, devices):
        assert w.parent_id == task.span_id and d.parent_id == w.span_id
        assert d.attrs["route"] == "mesh"
        assert 1 <= d.attrs["lanes"] == w.attrs["lanes"] <= lanes
        assert d.attrs["padded_rows"] == lanes * M._pad_size(w.attrs["rows"])
        assert d.attrs["rows"] <= d.attrs["padded_rows"]
        assert d.attrs["h2d_bytes"] == 4 * 6 * d.attrs["padded_rows"]
        assert d.attrs["d2h_bytes"] == 5 * d.attrs["padded_rows"]
    # a step's assembly on the calling thread, the lane encode on the
    # prefetch threads: both `merge.prep`, both under the task
    preps = named("merge.prep")
    assert {p.thread for p in preps} >= {"MainThread"}
    assert any(p.thread.startswith("paimon-prefetch") for p in preps)
    gathers = named("merge.gather")
    assert len(gathers) == sum(d.attrs["lanes"] for d in devices)
    for s in preps + gathers + named("decode") + named("encode"):
        assert "compact.task" in set(ancestors(s)), s.name

    assert _counter("mesh_steps") - before[0] == len(windows)
    assert _counter("mesh_padded_rows") - before[1] == \
        sum(d.attrs["padded_rows"] for d in devices)
    assert M.PATH_COUNTS["device"] - before[2] == len(gathers)
    assert gather_bytes.count - before[3] == \
        sum(g.attrs["bytes"] for g in gathers) > 0


def test_a_one_chip_compaction_still_opens_one_compact_table(
        deployment, tmp_path, ring):
    table = _clone(deployment, "wide", tmp_path, "t")
    steps = _counter("mesh_steps")
    assert table.compact(full=True) is not None
    spans = obs.take_spans()
    roots = [s for s in spans if s.name == "compact.table"]
    assert len(roots) == 1 and roots[0].attrs["groups"] == 8
    tasks = [s for s in spans if s.name == "compact.task"]
    assert len(tasks) == 8
    assert all(t.parent_id == roots[0].span_id for t in tasks)
    assert all("route" not in t.attrs for t in tasks)
    assert not [s for s in spans if s.name == "compaction.window"]
    assert _counter("mesh_steps") == steps
