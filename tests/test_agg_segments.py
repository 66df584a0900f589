"""Sorted-segment reductions of ops/agg.py: the four device wrappers
against numpy's `reduceat` over the layouts a window can have, the
sorted-and-dense contract they check, and the two facts of their
lowering that the benchmark's segment-reduce roofline rests on (no
scatter in the program, `_seg_` in the module's name)."""

import re
import zlib

import jax
import numpy as np
import pytest

from paimon_tpu.ops import agg

OPS = {
    "sum": (agg._seg_sum, np.add),
    "max": (agg._seg_max, np.maximum),
    "min": (agg._seg_min, np.minimum),
    "prod": (agg._seg_prod, np.multiply),
}
DTYPES = [np.int32, np.int64, np.float32]
ROWS = [1, 2, 1023, 1024, 1025, 5000]
LAYOUTS = ["singletons", "one", "random", "long_tail"]


def _lengths(layout, n, rng):
    if layout == "singletons":
        return np.ones(n, dtype=np.int64)
    if layout == "one":
        return np.array([n], dtype=np.int64)
    lens = []
    # long_tail: the last segment is the longest and ends on the last
    # real row, right before the padding
    room = n - min(n, 37) if layout == "long_tail" else n
    while room > 0:
        k = min(int(rng.integers(1, 13)), room)
        lens.append(k)
        room -= k
    if layout == "long_tail":
        lens.append(min(n, 37))
    return np.array(lens, dtype=np.int64)


def _ids(lens):
    return np.repeat(np.arange(len(lens), dtype=np.int64), lens)


def _starts(lens):
    return np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.intp)


def _values(op, dtype, n, rng):
    """Integers over their whole range (numpy and the device wrap alike);
    float32 sums and products over values whose every partial result is
    exact, so that the order of association cannot show."""
    if np.issubdtype(dtype, np.integer):
        info = np.iinfo(dtype)
        if op == "prod":
            v = rng.integers(-3, 4, size=n)
            v[rng.random(n) < 0.05] = info.max // 3
            return v.astype(dtype)
        return rng.integers(info.min, info.max, size=n, dtype=dtype,
                            endpoint=True)
    if op == "sum":
        return rng.integers(-1000, 1000, size=n).astype(dtype)
    if op == "prod":
        # powers of two, as many halvings as doublings: no rounding, and
        # 5000 of them in one segment stay far inside the exponent range
        return rng.choice([-2.0, -1.0, -0.5, 0.5, 1.0, 2.0],
                          size=n).astype(dtype)
    return (rng.standard_normal(n) * 1e6).astype(dtype)


def _reduceat(ufunc, vals, lens):
    # dtype pinned: numpy would widen an int32 sum or product to int64
    return ufunc.reduceat(vals, _starts(lens), dtype=vals.dtype)


def _same_bits(got, want):
    assert got.dtype == want.dtype
    assert got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("n", ROWS)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("op", list(OPS))
def test_matches_reduceat(op, dtype, n, layout):
    rng = np.random.default_rng(
        zlib.crc32(f"{op} {dtype.__name__} {n} {layout}".encode()))
    lens = _lengths(layout, n, rng)
    assert lens.sum() == n
    vals = _values(op, dtype, n, rng)
    fn, ufunc = OPS[op]
    got = fn(vals, _ids(lens), len(lens))
    _same_bits(got, _reduceat(ufunc, vals, lens))


@pytest.mark.parametrize("op", list(OPS))
def test_float64_reduces_on_the_host(op):
    rng = np.random.default_rng(5)
    lens = _lengths("random", 3000, rng)
    vals = rng.standard_normal(3000) * 1e300
    fn, ufunc = OPS[op]
    with np.errstate(over="ignore"):
        _same_bits(fn(vals, _ids(lens), len(lens)),
                   _reduceat(ufunc, vals, lens))


@pytest.mark.parametrize("n", [2, 1024, 1025, 5000])
def test_int64_sum_wraps_like_numpy(n):
    rng = np.random.default_rng(n)
    lens = _lengths("long_tail", n, rng)
    # one sign a segment, so every segment of two rows or more overflows
    vals = rng.choice(np.array([2**62, 2**62 + 12345, 2**63 - 1],
                               dtype=np.int64), size=n)
    vals *= rng.choice(np.array([-1, 1]), size=len(lens))[_ids(lens)]
    want = _reduceat(np.add, vals, lens)
    exact = [int(sum(map(int, vals[s:s + k])))
             for s, k in zip(_starts(lens), lens)]
    assert any(not -2**63 <= e < 2**63 for e in exact)    # it does wrap
    assert [e % 2**64 for e in exact] == [int(w) % 2**64 for w in want]
    _same_bits(agg._seg_sum(vals, _ids(lens), len(lens)), want)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("op", ["max", "min"])
def test_identity_masked_rows(op, dtype):
    """The aggregation masks rows that do not contribute with the
    reduction's identity; a segment with no contributing row reads the
    identity back."""
    rng = np.random.default_rng(11)
    n = 2500
    lens = _lengths("random", n, rng)
    ident = {"max": agg._np_min_ident,
             "min": agg._np_max_ident}[op](dtype)
    vals = _values(op, dtype, n, rng)
    keep = rng.random(n) < 0.5
    ids = _ids(lens)
    keep[ids == 3] = False                      # one all-masked segment
    masked = np.where(keep, vals, ident).astype(dtype)
    fn, ufunc = OPS[op]
    got = fn(masked, ids, len(lens))
    _same_bits(got, _reduceat(ufunc, masked, lens))
    assert got[3] == ident


@pytest.mark.parametrize("special", [np.nan, np.inf, -np.inf])
@pytest.mark.parametrize("op", ["max", "min"])
def test_float32_nan_and_inf(op, special):
    rng = np.random.default_rng(13)
    n = 1500
    lens = _lengths("random", n, rng)
    vals = rng.standard_normal(n).astype(np.float32)
    vals[rng.random(n) < 0.1] = special
    fn, ufunc = OPS[op]
    got = fn(vals, _ids(lens), len(lens))
    want = _reduceat(ufunc, vals, lens)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)    # NaN == NaN here
    if np.isnan(special):
        assert np.isnan(got).any()


BAD_IDS = {
    "unsorted": ([0, 1, 0, 1], 2),
    "descending": ([1, 1, 0, 0], 2),
    "relabelled": ([0, 2, 1, 3], 4),
    "gap": ([0, 0, 2, 2], 3),
    "gap_fewer_segments": ([0, 0, 2, 2], 2),
    "starts_at_one": ([1, 1, 2, 2], 2),
    "too_few_segments": ([0, 0, 1, 2], 2),
    "too_many_segments": ([0, 0, 1, 1], 3),
    "empty_with_segments": ([], 1),
}


@pytest.mark.parametrize("dtype", [np.int64, np.float64],
                         ids=["device", "host"])
@pytest.mark.parametrize("case", list(BAD_IDS))
def test_ids_not_sorted_and_dense_raise(case, dtype):
    ids, num_seg = BAD_IDS[case]
    ids = np.array(ids, dtype=np.int64)
    vals = np.arange(len(ids)).astype(dtype)
    for fn, _ in OPS.values():
        with pytest.raises(ValueError, match="ascending and dense"):
            fn(vals, ids, num_seg)


@pytest.mark.parametrize("dtype", DTYPES + [np.float64],
                         ids=lambda d: d.__name__)
def test_no_rows_no_segments(dtype):
    out = agg._seg_sum(np.zeros(0, dtype), np.zeros(0, np.int64), 0)
    assert out.dtype == dtype and out.shape == (0,)


@pytest.mark.parametrize("n", [1, 2, 1024, 1025, 5000])
def test_index_where_with_all_false_segments(n):
    rng = np.random.default_rng(n + 1)
    lens = _lengths("random", n, rng)
    ids = _ids(lens)
    mask = rng.random(n) < 0.4
    mask[ids % 3 == 0] = False                  # whole segments all-false
    last = agg._last_index_where(mask, ids, len(lens))
    first = agg._first_index_where(mask, ids, len(lens))
    want_last, want_first = [], []
    for s, k in zip(_starts(lens), lens):
        hit = s + np.flatnonzero(mask[s:s + k])
        want_last.append(hit[-1] if len(hit) else -1)
        want_first.append(hit[0] if len(hit) else -1)
    assert last.tolist() == want_last
    assert first.tolist() == want_first
    assert (last[::3] == -1).all() and (first[::3] == -1).all()


WINDOW_ROWS = 1 << 21      # what a streamed compaction's window pads to


@pytest.mark.parametrize("dtype", [np.int64, np.int32, np.float32],
                         ids=lambda d: d.__name__)
@pytest.mark.parametrize("name", ["_seg_sum_jit", "_seg_max_jit",
                                  "_seg_min_jit", "_seg_prod_jit"])
def test_lowering_has_no_scatter_and_keeps_its_name(name, dtype):
    """segreduce_kernel_roofline finds these modules by `_seg_` and this
    PR's gain is that no scatter (nor a gather) is left in them."""
    lowered = getattr(agg, name).lower(
        jax.ShapeDtypeStruct((WINDOW_ROWS,), dtype),
        jax.ShapeDtypeStruct((WINDOW_ROWS,), np.bool_))
    texts = [lowered.as_text(), lowered.compile().as_text()]
    for text in texts:      # the opcode, not a name in the metadata
        assert not re.search(r"(?:stablehlo\.|\s)(?:scatter|gather)\b", text)
    module = re.search(r"HloModule (\S+?)[,\s]", texts[1]).group(1)
    assert module == "jit_" + name and "_seg_" in module
    assert f"@jit_{name}" in texts[0]
