"""The wide partial-update deployment (chipbench's
`partial-update-wide64`: 64 columns, 5 snapshots, four sequence groups)
through the normal path at a small size, held cell for cell — nulls
included — to `chipbench/reference_wide.py`; and that reference against
a row-at-a-time replay, so the yardstick is checked too."""

import copy
import json
import os

import numpy as np
import pytest

from chipbench import data, data_wide, reference_wide
from paimon_tpu.table import FileStoreTable

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "chipbench", "configs",
                       "partial-update-wide64.json")) as _f:
    CONFIG = json.load(_f)
KEY, GROUPS, UNGROUPED, _ = data_wide.layout(CONFIG["table"])
SEEDS = [3_000_000_019, 7, 2**31 + 5]       # the driver's pass 2**31
BIG = 1 << 41                               # above every drawn sequence


def _snapshots(seed, keys=2_000, pattern=None):
    return data_wide.gen_snapshots(seed, keys, CONFIG["data"]["key_seed"],
                                   CONFIG["table"],
                                   pattern or CONFIG["snapshots"])


def _build(path, snapshots, buckets=1):
    cfg = dict(CONFIG["table"], buckets=buckets)
    table = data.create_table(path, cfg)
    for snapshot in snapshots:
        data.write_commit(table, data_wide.to_arrow(snapshot, cfg))
    return FileStoreTable.load(path)


def _read(table, mode):
    """`compact`: full compaction, then the scan of its one run a
    bucket; `mor`: merge-on-read of the five uncompacted runs."""
    if mode == "compact":
        assert table.compact(full=True) is not None
        table = FileStoreTable.load(table.path)
        assert all(len(s.data_files) == 1
                   for s in table.new_scan().plan().splits)
    return reference_wide.columns_of(table.to_arrow())


def _reference(snapshots):
    return reference_wide.merged(snapshots, KEY, GROUPS, UNGROUPED)


def test_the_configuration_is_the_sources_shape():
    assert len(CONFIG["table"]["columns"]) == 64
    assert CONFIG["snapshots"]["count"] == 5 and len(GROUPS) == 4
    assert all(len(members) == 14 for _, members in GROUPS)
    assert UNGROUPED == ["u0", "u1", "u2"]
    assert not [k for k in CONFIG["table"]["options"]
                if k.startswith("tpu.")]


@pytest.mark.parametrize("mode", ["compact", "mor"])
@pytest.mark.parametrize("buckets", [1, 2])
@pytest.mark.parametrize("seed", SEEDS)
def test_table_equals_the_reference_cell_for_cell(tmp_path, seed, buckets,
                                                  mode):
    snapshots = _snapshots(seed)
    want = _reference(snapshots)
    # the draw exercises what it is meant to: sequence order against
    # arrival order, skipped rows, nulls that overwrite
    last = snapshots[4]
    arrival = last["g0_ts"][0][np.argsort(last[KEY][0])]
    assert 0.4 < (want["g0_ts"][0] != arrival).mean() < 0.9
    assert not last["g0_ts"][1].all()
    assert 0.05 < (~want["g0_c00"][1]).mean() < 0.2
    got = _read(_build(str(tmp_path / "t"), snapshots, buckets), mode)
    assert len(got[KEY][0]) == 2_000
    reference_wide.check_equal(got, want, KEY, f"{mode}, {buckets} buckets")
    reference_wide.check_checksum(reference_wide.checksum(got, KEY),
                                  reference_wide.checksum(want, KEY), mode)


def _row_of(snapshot, key):
    return int(np.flatnonzero(snapshot[KEY][0] == key)[0])


def _set(snapshot, key, name, value):
    """One cell of one snapshot; `None` makes it null."""
    values, valid = snapshot[name]
    i = _row_of(snapshot, key)
    values[i], valid[i] = (0, False) if value is None else (value, True)


def _equal_sequences(snaps):
    for s in (3, 4):                        # both write g0
        _set(snaps[s], 5, "g0_ts", BIG)
        _set(snaps[s], 5, "g0_c00", 100 + s)
    return "g0_c00", 104                    # the later snapshot's


def _null_sequence_on_the_largest_row(snaps):
    _set(snaps[0], 5, "g0_ts", 1)
    _set(snaps[3], 5, "g0_ts", 7)
    _set(snaps[3], 5, "g0_c00", 103)
    _set(snaps[4], 5, "g0_ts", None)        # the last, and skipped
    _set(snaps[4], 5, "g0_c00", 104)
    return "g0_c00", 103


def _winning_rows_null_overwrites(snaps):
    _set(snaps[0], 5, "g1_ts", 3)
    _set(snaps[0], 5, "g1_c01", 0.5)
    _set(snaps[1], 5, "g1_ts", BIG)         # wins over snapshot 4 too
    _set(snaps[1], 5, "g1_c01", None)
    return "g1_c01", None


def _ungrouped_null_in_every_snapshot(snaps):
    for snap in snaps:
        _set(snap, 5, "u1", None)
    return "u1", None


def _group_no_snapshot_wrote(snaps):
    return "g3_c00", None                   # see `_NO_G3` below


_NO_G3 = dict(CONFIG["snapshots"],
              writes=[[0, 1], [1, 2], [2, 0], [0, 1], [1, 2]])
FORCED = [_equal_sequences, _null_sequence_on_the_largest_row,
          _winning_rows_null_overwrites, _ungrouped_null_in_every_snapshot,
          _group_no_snapshot_wrote]


@pytest.mark.parametrize("mode", ["compact", "mor"])
@pytest.mark.parametrize("force", FORCED, ids=lambda f: f.__name__[1:])
def test_what_the_random_draw_makes_rare(tmp_path, force, mode):
    snapshots = _snapshots(
        11, keys=300,
        pattern=_NO_G3 if force is _group_no_snapshot_wrote else None)
    column, expected = force(snapshots)
    want = _reference(snapshots)
    values, valid = want[column]
    i = int(np.flatnonzero(want[KEY][0] == 5)[0])
    if expected is None:
        assert not valid[i]
    else:
        assert valid[i] and values[i] == expected
    if force is _group_no_snapshot_wrote:
        for name in ["g3_ts"] + dict(GROUPS)["g3_ts"]:
            assert not want[name][1].any()
    got = _read(_build(str(tmp_path / "t"), snapshots), mode)
    reference_wide.check_equal(got, want, KEY, force.__name__)


def _replay(snapshots):
    """PartialUpdateMergeFunction one row at a time: {key: {column:
    value or None}}."""
    state = {}
    for snap in snapshots:
        cell = {name: [v if ok else None for v, ok in
                       zip(values.tolist(), valid.tolist())]
                for name, (values, valid) in snap.items()}
        for i, key in enumerate(cell[KEY]):
            row = state.setdefault(
                key, {name: None for name in snap if name != KEY})
            for ts, members in GROUPS:
                new = cell[ts][i]
                if new is None:
                    continue                # a null sequence updates nothing
                if row[ts] is None or new >= row[ts]:
                    for name in [ts] + members:
                        row[name] = cell[name][i]
            for name in UNGROUPED:
                if cell[name][i] is not None:
                    row[name] = cell[name][i]
    return state


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_against_a_row_at_a_time_replay(seed):
    snapshots = _snapshots(seed, keys=200)
    forced = copy.deepcopy(snapshots)
    for force in FORCED[:4]:
        force(forced)
    for snaps in (snapshots, forced):
        want = _reference(snaps)
        state = _replay(snaps)
        assert want[KEY][0].tolist() == sorted(state)
        for name, (values, valid) in want.items():
            if name == KEY:
                continue
            have = [v if ok else None
                    for v, ok in zip(values.tolist(), valid.tolist())]
            assert have == [state[k][name] for k in sorted(state)], name
