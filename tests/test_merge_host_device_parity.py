"""The cpu lexsort fallback and the XLA kernel must agree bit-for-bit.

The fallback (ops/merge.py _host_sorted_winners) answers every
device_sorted_winners call on cpu backends, so the kernel's padding +
validity logic would otherwise be test-dead off-accelerator:
PAIMON_FORCE_DEVICE_SORT=1 pins the kernel path and these tests compare
the two against each other on random workloads.
"""

import os

import numpy as np
import pytest

from paimon_tpu.ops.merge import device_sorted_winners


def _both_paths(lanes, seq, keep, order_lanes=None):
    os.environ.pop("PAIMON_FORCE_DEVICE_SORT", None)
    host = device_sorted_winners(lanes, seq, keep, order_lanes)
    os.environ["PAIMON_FORCE_DEVICE_SORT"] = "1"
    try:
        dev = device_sorted_winners(lanes, seq, keep, order_lanes)
    finally:
        os.environ.pop("PAIMON_FORCE_DEVICE_SORT", None)
    return host, dev


def _winners(perm, winner, n):
    perm = np.asarray(perm)
    winner = np.asarray(winner)
    real = perm < n
    return perm[np.asarray(winner, bool) & real]


@pytest.mark.parametrize("keep", ["last", "first"])
@pytest.mark.parametrize("seed", [0, 7, 31])
def test_host_matches_device_kernel(keep, seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 5000))
    lanes = rng.integers(0, 8, (n, 2), dtype=np.uint64) \
        .astype(np.uint32)                 # few distincts: big segments
    seq = rng.permutation(n).astype(np.int64)
    (hp, hw, hprev), (dp, dw, dprev) = _both_paths(lanes, seq, keep)
    h = _winners(hp, hw, n)
    d = _winners(dp, dw, n)
    assert np.array_equal(np.sort(h), np.sort(d))
    # winner per segment must be identical, not just same count
    assert set(h.tolist()) == set(d.tolist())

    # prev_in_segment feeds changelog derivation: winner -> predecessor
    # maps must agree too
    def prev_map(perm, winner, prev):
        perm, winner, prev = (np.asarray(perm), np.asarray(winner, bool),
                              np.asarray(prev))
        pos = np.flatnonzero(winner & (perm < n))
        return {int(perm[i]): int(prev[i]) for i in pos}

    assert prev_map(hp, hw, hprev) == prev_map(dp, dw, dprev)


def test_order_lanes_agree():
    rng = np.random.default_rng(3)
    n = 777
    lanes = rng.integers(0, 5, (n, 1), dtype=np.uint64).astype(np.uint32)
    order = rng.integers(0, 3, (n, 1), dtype=np.uint64).astype(np.uint32)
    seq = np.arange(n, dtype=np.int64)
    (hp, hw, _), (dp, dw, _) = _both_paths(lanes, seq, "last", order)
    assert set(_winners(hp, hw, n).tolist()) == \
        set(_winners(dp, dw, n).tolist())


def test_device_path_padding_still_covered():
    """Direct kernel run (forced): padded outputs, validity respected."""
    os.environ["PAIMON_FORCE_DEVICE_SORT"] = "1"
    try:
        lanes = np.zeros((3, 1), dtype=np.uint32)   # all-equal keys
        seq = np.array([5, 9, 1], dtype=np.int64)
        perm, winner, prev = device_sorted_winners(lanes, seq, "last")
        assert len(perm) >= 1024                     # padded
        win = perm[np.asarray(winner, bool) & (perm < 3)]
        assert win.tolist() == [1]                   # max-seq row wins
    finally:
        os.environ.pop("PAIMON_FORCE_DEVICE_SORT", None)


@pytest.mark.parametrize("keep", ["last", "first"])
@pytest.mark.parametrize("seed", [1, 9, 42])
def test_winners_only_fast_path_matches_full_sort(keep, seed):
    """The packed-key argsort + segmented-argmax fast path must pick
    byte-identical winners to the full (key, seq) sort."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(50, 8000))
    lanes = rng.integers(0, 9, (n, 2), dtype=np.uint64) \
        .astype(np.uint32)                 # heavy duplication
    # non-unique sequences so arrival-order tie-breaks matter
    seq = rng.integers(0, 12, n).astype(np.int64)

    fast = device_sorted_winners(lanes, seq, keep, winners_only=True)
    full = device_sorted_winners(lanes, seq, keep, winners_only=False)
    w_fast = set(_winners(fast[0], fast[1], n).tolist())
    w_full = set(_winners(full[0], full[1], n).tolist())
    assert w_fast == w_full


def _packed_key(lanes):
    return (lanes[:, 0].astype(np.uint64) << np.uint64(32)) \
        | lanes[:, 1].astype(np.uint64)


@pytest.mark.parametrize("keep", ["last", "first"])
@pytest.mark.parametrize("seed", [2, 11, 77])
def test_packed_route_matches_host(keep, seed, monkeypatch):
    """The device's one-word-a-row return (perm | winner << 31) must
    pick the SAME winners in the SAME key order as the host fast path."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(40, 9000))
    lanes = rng.integers(0, 50, (n, 2), dtype=np.uint64) \
        .astype(np.uint32)
    packed = _packed_key(lanes)
    seq = rng.integers(0, 15, n).astype(np.int64)

    host = device_sorted_winners(lanes, seq, keep, winners_only=True,
                                 packed=packed)
    monkeypatch.setenv("PAIMON_FORCE_DEVICE_SORT", "1")
    dev = device_sorted_winners(lanes, seq, keep, winners_only=True,
                                packed=packed)
    assert len(host[0]) == n and len(dev[0]) >= 1024    # padded
    h_idx = _winners(host[0], host[1], n)
    d_idx = _winners(dev[0], dev[1], n)
    # identical winners, identical (key-sorted) order
    assert np.array_equal(h_idx, d_idx)
    assert np.all(np.diff(packed[d_idx].astype(np.int64)) > 0)


def test_packed_route_with_order_lanes(monkeypatch):
    rng = np.random.default_rng(5)
    n = 3000
    lanes = rng.integers(0, 20, (n, 2), dtype=np.uint64) \
        .astype(np.uint32)
    packed = _packed_key(lanes)
    order = rng.integers(0, 4, (n, 1), dtype=np.uint64).astype(np.uint32)
    seq = np.arange(n, dtype=np.int64)
    host = device_sorted_winners(lanes, seq, "last", order_lanes=order,
                                 winners_only=True, packed=None)
    monkeypatch.setenv("PAIMON_FORCE_DEVICE_SORT", "1")
    dev = device_sorted_winners(lanes, seq, "last", order_lanes=order,
                                winners_only=True, packed=packed)
    h_idx = _winners(host[0], host[1], n)
    d_idx = _winners(dev[0], dev[1], n)
    assert set(h_idx.tolist()) == set(d_idx.tolist())
    # the packed return is key-ordered
    assert np.all(np.diff(packed[d_idx].astype(np.int64)) > 0)


def _winner_set(perm, winner, prev, n):
    """(winners, winner -> predecessor map) of one route's result."""
    perm, prev = np.asarray(perm), np.asarray(prev)
    pos = np.flatnonzero(np.asarray(winner, bool) & (perm < n))
    return (set(perm[pos].tolist()),
            {int(perm[i]): int(prev[i]) for i in pos})


@pytest.mark.parametrize("keep", ["last", "first"])
@pytest.mark.parametrize("seed", [0, 5, 11])
def test_full_route_matches_host_three_lanes(keep, seed):
    """The device's full return on three-lane keys: the winners and the
    winner -> predecessor map of the host route."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(100, 6000))
    lanes = rng.integers(0, 12, (n, 3), dtype=np.uint64) \
        .astype(np.uint32)
    seq = rng.permutation(n).astype(np.int64)
    host, dev = _both_paths(lanes, seq, keep)
    assert _winner_set(*host, n) == _winner_set(*dev, n)


def test_padding_never_joins_segments(monkeypatch):
    """All-zero real keys must not merge with the all-zero padding
    rows (validity is part of segment identity in the kernel too)."""
    monkeypatch.setenv("PAIMON_FORCE_DEVICE_SORT", "1")
    lanes = np.zeros((5, 2), dtype=np.uint32)
    seq = np.arange(5, dtype=np.int64)
    perm, winner, _ = device_sorted_winners(lanes, seq, "last")
    perm, winner = np.asarray(perm), np.asarray(winner, bool)
    win = perm[winner & (perm < 5)]
    assert win.tolist() == [4]       # one segment, max-seq row


@pytest.mark.parametrize("key", ["int64", "string"])
@pytest.mark.parametrize("pin", ["PAIMON_FORCE_DEVICE_SORT",
                                 "PAIMON_FORCE_HOST_SORT", None])
def test_one_route_decision_a_merge(pin, key, monkeypatch):
    """`merge_runs` decides its route ahead of the encode for a
    fixed-width key and after it for a key that can be cut: either way
    one decision, one ROUTE_LOG entry and one PATH_COUNTS tick a merge,
    with the inputs the router always logged."""
    import pyarrow as pa
    from paimon_tpu.ops import merge as M
    from paimon_tpu.ops.normkey import NormalizedKeyEncoder

    rng = np.random.default_rng(13)
    runs = []
    for r in range(3):
        k = np.sort(rng.integers(0, 500, 800))
        runs.append(pa.table({
            "k": pa.array(k) if key == "int64"
            else pa.array(["%04d" % v for v in k]),
            M.SEQ_COL: pa.array(np.arange(800 * r, 800 * (r + 1))),
            M.KIND_COL: pa.array(np.zeros(800, np.int8))}))
    if pin:
        monkeypatch.setenv(pin, "1")
    del M.ROUTE_LOG[:]
    counts = dict(M.PATH_COUNTS)
    res = M.merge_runs(runs, ["k"], key_encoder=NormalizedKeyEncoder(
        [runs[0].schema.field("k").type], nullable=[False]))
    ticks = {p: M.PATH_COUNTS[p] - counts[p] for p in counts}
    to_device = pin == "PAIMON_FORCE_DEVICE_SORT"
    assert M.ROUTE_LOG == [{
        "rows": 2400, "lanes": 2 if key == "int64" else 4,
        "winners_only": True, "host_fast": key == "int64",
        "pinned": pin is not None,
        "route": "device" if to_device else "host"}]
    assert sum(ticks.values()) == 1
    assert ticks["device"] == int(to_device)
    assert len(res.indices) == len(np.unique(
        np.concatenate([r.column("k").to_numpy(zero_copy_only=False)
                        for r in runs])))
