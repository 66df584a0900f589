"""The copied generator and the numpy reference: deterministic in the
seed, and a comparison that misses neither a flipped bit nor a row."""

import numpy as np
import pytest

from chipbench import data, reference

SEED = 3_000_000_019            # the driver's seeds pass 2**31


def _runs(seed, key_seed=24):
    return data.gen_runs(seed, rows=20_000, runs=4, key_space=10_000,
                         key_seed=key_seed)


def test_generator_is_deterministic_in_the_seed_and_keeps_its_sizes():
    a, b, c = _runs(SEED), _runs(SEED), _runs(SEED + 1)
    assert len(a) == 4 and all(len(r["id"]) == 5_000 for r in a)
    for ra, rb in zip(a, b):
        for k in ra:
            assert np.array_equal(ra[k], rb[k])
    for k in ("v1", "v2", "v3"):
        assert not np.array_equal(a[0][k], c[0][k])
    # every seed writes other rows under the same keys: the same sizes
    for ra, rc in zip(a, c):
        assert np.array_equal(ra["id"], rc["id"])
    assert not np.array_equal(a[0]["id"], _runs(SEED, key_seed=25)[0]["id"])
    assert a[0]["v3"].dtype == np.int32 and a[0]["v2"].dtype == np.float64


@pytest.mark.parametrize("engine", ["deduplicate", "aggregation"])
def test_reference_against_a_python_loop(engine):
    cols = data.concat(_runs(SEED))
    want = {}
    for i, key in enumerate(cols["id"].tolist()):
        row = {k: cols[k][i] for k in ("v1", "v2", "v3")}
        if engine == "deduplicate" or key not in want:
            want[key] = row
        else:
            old = want[key]
            want[key] = {"v1": old["v1"] + row["v1"],
                         "v2": max(old["v2"], row["v2"]),
                         "v3": max(old["v3"], row["v3"])}
    got = reference.merged(cols, engine)
    assert got["id"].tolist() == sorted(want)
    for k in ("v1", "v2", "v3"):
        assert got[k].tolist() == [want[key][k] for key in sorted(want)]


def _shuffled(cols, seed=1):
    order = np.random.default_rng(seed).permutation(len(cols["id"]))
    return {k: v[order] for k, v in cols.items()}


def test_comparison_accepts_any_row_order():
    want = reference.merged(data.concat(_runs(SEED)), "aggregation")
    got = _shuffled(want)
    reference.check_equal(got, want, "shuffled")
    reference.check_checksum(reference.checksum(got),
                             reference.checksum(want), "shuffled")


def test_comparison_fails_on_one_flipped_low_bit_of_a_double():
    want = reference.merged(data.concat(_runs(SEED)), "aggregation")
    got = {k: v.copy() for k, v in _shuffled(want).items()}
    got["v2"].view(np.uint64)[17] ^= np.uint64(1)
    assert np.isclose(got["v2"], _shuffled(want)["v2"], rtol=0,
                      atol=1e-15).all()           # a tolerance would pass it
    with pytest.raises(reference.Mismatch, match="v2"):
        reference.check_equal(got, want, "flipped")
    with pytest.raises(reference.Mismatch, match="v2"):
        reference.check_checksum(reference.checksum(got),
                                 reference.checksum(want), "flipped")


def test_comparison_fails_on_one_missing_row():
    want = reference.merged(data.concat(_runs(SEED)), "deduplicate")
    got = {k: v[:-1] for k, v in want.items()}
    with pytest.raises(reference.Mismatch, match="rows"):
        reference.check_equal(got, want, "short")
    with pytest.raises(reference.Mismatch):
        reference.check_checksum(reference.checksum(got),
                                 reference.checksum(want), "short")


def test_table_checksum_equals_checksum_whatever_the_chunks():
    import pyarrow as pa
    want = reference.merged(data.concat(_runs(SEED)), "aggregation")
    halves = [pa.table({k: v[:100] for k, v in want.items()}),
              pa.table({k: v[100:] for k, v in want.items()})]
    assert reference.table_checksum(pa.concat_tables(halves)) == \
        reference.checksum(want)


def test_a_null_is_a_mismatch():
    import pyarrow as pa
    with pytest.raises(reference.Mismatch, match="nulls"):
        reference.columns_of(pa.table({"id": pa.array([1, None])}))
    with pytest.raises(reference.Mismatch, match="nulls"):
        reference.table_checksum(pa.table({"id": pa.array([1, None])}))
