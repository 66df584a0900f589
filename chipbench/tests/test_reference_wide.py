"""The wide generator and the null-aware reference: deterministic in the
seed, sizes from the configuration alone, and a comparison that misses
neither a flipped null, nor a flipped bit, nor two rows that swapped."""

import json
import os

import numpy as np
import pytest

from chipbench import data_wide, reference_wide as R

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "chipbench", "configs",
                       "partial-update-wide64.json")) as _f:
    CONFIG = json.load(_f)
KEY, GROUPS, UNGROUPED, _ = data_wide.layout(CONFIG["table"])
SEED = 3_000_000_019            # the driver's seeds pass 2**31


def _snapshots(seed, key_seed=24, keys=1_500):
    return data_wide.gen_snapshots(seed, keys, key_seed, CONFIG["table"],
                                   CONFIG["snapshots"])


@pytest.fixture(scope="module")
def want():
    return R.merged(_snapshots(SEED), KEY, GROUPS, UNGROUPED)


def _copy(cols):
    return {k: (v.copy(), ok.copy()) for k, (v, ok) in cols.items()}


def _shuffled(cols, seed=1):
    order = np.random.default_rng(seed).permutation(len(cols[KEY][0]))
    return {k: (v[order], ok[order]) for k, (v, ok) in cols.items()}


def test_generator_is_deterministic_in_the_seed_and_keeps_its_sizes():
    a, b, c = _snapshots(SEED), _snapshots(SEED), _snapshots(SEED + 1)
    assert len(a) == 5 and all(len(s) == 64 for s in a)
    for sa, sb, sc in zip(a, b, c):
        for name in sa:
            assert np.array_equal(sa[name][0], sb[name][0])
            assert np.array_equal(sa[name][1], sb[name][1])
        # every seed writes other rows under the same keys, in the same
        # order: the files' sizes and every padded program stay
        assert np.array_equal(sa[KEY][0], sc[KEY][0])
        assert sorted(sa[KEY][0].tolist()) == list(range(1_500))
    assert not np.array_equal(a[0]["g0_ts"][0], c[0]["g0_ts"][0])
    assert not np.array_equal(a[0][KEY][0],
                              _snapshots(SEED, key_seed=25)[0][KEY][0])
    assert a[0]["g0_c02"][0].dtype == np.int32
    assert a[0]["g0_c01"][0].dtype == np.float64


def test_generator_writes_the_groups_the_configuration_says():
    snaps = _snapshots(SEED)
    for s, snap in enumerate(snaps):
        for k, (ts, members) in enumerate(GROUPS):
            written = k in CONFIG["snapshots"]["writes"][s]
            for name in [ts] + members:
                values, valid = snap[name]
                assert valid.any() == written
                assert not values[~valid].any()     # a null holds 0
            if written:
                assert 0.0 < (~snap[ts][1]).mean() < 0.06
                assert 0.05 < (~snap[members[0]][1]).mean() < 0.16
        for name in UNGROUPED:
            assert 0.4 < snap[name][1].mean() < 0.6
    table = data_wide.to_arrow(snaps[0], CONFIG["table"])
    assert table.column_names == [c for c, _ in CONFIG["table"]["columns"]]
    assert table.column("g2_ts").null_count == table.num_rows
    assert table.column(KEY).null_count == 0


def test_comparison_accepts_any_row_order_and_arrow(want):
    got = _shuffled(want)
    R.check_equal(got, want, KEY, "shuffled")
    R.check_checksum(R.checksum(got, KEY), R.checksum(want, KEY),
                     "shuffled")
    import pyarrow as pa
    halves = [pa.table({k: pa.array(v[sl], mask=~ok[sl])
                        for k, (v, ok) in got.items()})
              for sl in (slice(0, 700), slice(700, None))]
    table = pa.concat_tables(halves)
    R.check_equal(R.columns_of(table), want, KEY, "arrow")
    assert R.table_checksum(table, KEY) == R.checksum(want, KEY)


@pytest.mark.parametrize("to_null", [True, False])
def test_comparison_fails_on_one_flipped_null(want, to_null):
    got = _copy(_shuffled(want))
    values, valid = got["g1_c03"]
    i = int(np.flatnonzero(valid == to_null)[17])
    valid[i] = not to_null          # the value's bits stay as they were
    with pytest.raises(R.Mismatch, match="g1_c03.*null"):
        R.check_equal(got, want, KEY, "flipped null")
    with pytest.raises(R.Mismatch, match="g1_c03"):
        R.check_checksum(R.checksum(got, KEY), R.checksum(want, KEY),
                         "flipped null")


def test_comparison_fails_on_one_flipped_low_bit_of_a_double(want):
    got = _copy(_shuffled(want))
    values, valid = got["g0_c01"]
    i = int(np.flatnonzero(valid)[17])
    before = values.copy()
    values.view(np.uint64)[i] ^= np.uint64(1)
    assert np.isclose(values, before, rtol=0, atol=1e-15).all()
    with pytest.raises(R.Mismatch, match="g0_c01"):
        R.check_equal(got, want, KEY, "flipped bit")
    with pytest.raises(R.Mismatch, match="g0_c01"):
        R.check_checksum(R.checksum(got, KEY), R.checksum(want, KEY),
                         "flipped bit")


def test_comparison_fails_on_a_swapped_pair_of_rows(want):
    """Two keys that exchanged their rows: every column still holds the
    same multiset of cells, and both comparisons still see it."""
    got = _copy(_shuffled(want))
    keys = got[KEY][0]
    keys[[3, 4]] = keys[[4, 3]]
    with pytest.raises(R.Mismatch):
        R.check_equal(got, want, KEY, "swapped")
    with pytest.raises(R.Mismatch):
        R.check_checksum(R.checksum(got, KEY), R.checksum(want, KEY),
                         "swapped")


def test_comparison_fails_on_one_missing_row(want):
    got = {k: (v[:-1], ok[:-1]) for k, (v, ok) in want.items()}
    with pytest.raises(R.Mismatch, match="rows"):
        R.check_equal(got, want, KEY, "short")
    with pytest.raises(R.Mismatch):
        R.check_checksum(R.checksum(got, KEY), R.checksum(want, KEY),
                         "short")


def test_a_snapshot_that_lacks_a_key_is_refused():
    snaps = _snapshots(SEED, keys=50)
    snaps[2][KEY][0][0] = snaps[2][KEY][0][1]
    with pytest.raises(ValueError, match="every key once"):
        R.merged(snaps, KEY, GROUPS, UNGROUPED)
