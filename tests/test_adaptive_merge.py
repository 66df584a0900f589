"""Link-adaptive merge path selection + packed winners-only output."""

import numpy as np
import pyarrow as pa
import pytest

from paimon_tpu.ops import merge as M
from paimon_tpu.ops.normkey import NormalizedKeyEncoder


def _mk(n, dupes=2, seed=0):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, max(n // dupes, 1), n).astype(np.uint32)
    lanes = np.stack([keys, np.zeros(n, np.uint32)], axis=1)
    seq = np.arange(n, dtype=np.int64)
    return lanes, seq


class TestCostModel:
    def test_wide_link_prefers_device(self, monkeypatch):
        monkeypatch.setattr(M, "_LINK_BW", (8e9, 8e9))   # PCIe-ish
        assert M._device_path_pays(4_000_000, 2, True, True)

    def test_narrow_link_prefers_host(self, monkeypatch):
        # 5M rows pad to 8M: the padded transfer over a slow d2h link
        # loses to the host fast path
        monkeypatch.setattr(M, "_LINK_BW", (900e6, 8e6))  # narrow d2h
        assert not M._device_path_pays(5_000_000, 2, True, True)
        # the full 9-byte/row output on that link loses even unpadded
        assert not M._device_path_pays(4_000_000, 2, False, True)

    def test_narrow_link_full_path_vs_slow_host_is_marginal_device(
            self, monkeypatch):
        # the 9-byte/row full path on the narrow link against the SLOW
        # general host sort: modeled device 4.7s vs host 5.7s at 4M
        # rows — device by a hair; pins the crossover direction
        monkeypatch.setattr(M, "_LINK_BW", (900e6, 8e6))
        assert M._device_path_pays(4_000_000, 6, False, False)


class TestLinkMeasurement:
    def test_first_caller_measures_the_rest_wait(self, monkeypatch):
        """Eight scan workers routing their first merge at once take
        ONE link reading between them (they used to take eight
        contended ones and keep whichever landed last)."""
        import threading
        import time

        calls = []

        def slow_timing():
            calls.append(threading.current_thread().name)
            time.sleep(0.05)          # long enough for all to pile up
            return (1e9, 2e9)

        monkeypatch.setattr(M, "_LINK_BW", None)
        monkeypatch.setattr(M, "_time_link", slow_timing)
        got = []
        start = threading.Barrier(8)

        def worker():
            start.wait(timeout=10)
            got.append(M._measure_link_bandwidth())

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
            assert not t.is_alive()
        assert len(calls) == 1
        assert got == [(1e9, 2e9)] * 8


    @pytest.mark.parametrize("backend,pin,taken", [
        ("tpu", None, True), ("cpu", None, False),
        ("tpu", "PAIMON_FORCE_HOST_SORT", False),
        ("tpu", "PAIMON_FORCE_DEVICE_SORT", False)])
    def test_a_reading_is_taken_ahead_only_where_the_router_wants_one(
            self, monkeypatch, backend, pin, taken):
        """`take_link_reading` (before `compact_table`'s tasks go side by
        side): an accelerator and no pin, as in the router itself."""
        calls = []
        monkeypatch.delenv("PAIMON_FORCE_HOST_SORT", raising=False)
        monkeypatch.delenv("PAIMON_FORCE_DEVICE_SORT", raising=False)
        if pin:
            monkeypatch.setenv(pin, "1")
        monkeypatch.setattr(M.jax, "default_backend", lambda: backend)
        monkeypatch.setattr(M, "_LINK_BW", None)
        monkeypatch.setattr(M, "_time_link",
                            lambda: calls.append(1) or (1e9, 2e9))
        M.take_link_reading()
        M.take_link_reading()                   # one reading a process
        assert len(calls) == (1 if taken else 0)
        assert M._LINK_BW == ((1e9, 2e9) if taken else None)


class TestPackedDevicePath:
    def test_packed_matches_host(self, monkeypatch):
        monkeypatch.setenv("PAIMON_FORCE_DEVICE_SORT", "1")
        lanes, seq = _mk(5000)
        perm_d, win_d, prev_d = M.device_sorted_winners(
            lanes, seq, "last", winners_only=True)
        monkeypatch.setenv("PAIMON_FORCE_HOST_SORT", "1")
        monkeypatch.delenv("PAIMON_FORCE_DEVICE_SORT")
        perm_h, win_h, _ = M.device_sorted_winners(
            lanes, seq, "last", winners_only=True)
        # same winner sets (device is padded, host unpadded)
        dw = set(perm_d[win_d[: len(perm_d)]].tolist())
        hw = set(perm_h[win_h].tolist())
        assert dw == hw
        assert (prev_d == -1).all()            # winners_only contract

    def test_packed_first_row(self, monkeypatch):
        monkeypatch.setenv("PAIMON_FORCE_DEVICE_SORT", "1")
        lanes, seq = _mk(3000, seed=3)
        perm, win, _ = M.device_sorted_winners(
            lanes, seq, "first", winners_only=True)
        winners = perm[win[: len(perm)]]
        keys = lanes[:, 0]
        # each winner is the FIRST arrival of its key
        for w in winners[:100]:
            k = keys[w]
            assert w == np.flatnonzero(keys == k).min()


class TestForceHost:
    def test_force_host_on_any_backend(self, monkeypatch):
        monkeypatch.setenv("PAIMON_FORCE_HOST_SORT", "1")
        lanes, seq = _mk(2000)
        perm, win, prev = M.device_sorted_winners(lanes, seq, "last")
        assert len(perm) == 2000               # unpadded => host path
        assert win.sum() == len(np.unique(lanes[:, 0]))


class TestKernelFailurePropagates:
    @pytest.mark.parametrize("winners_only,builder", [
        (True, "_merge_fn_packed"),
        (False, "_merge_fn"),
    ])
    def test_compile_refusal_fails_the_merge(self, monkeypatch,
                                             winners_only, builder):
        """A program the compiler refuses fails the call that needed
        it: no retry on a second program."""
        from jax.errors import JaxRuntimeError

        built = []

        def refusing_builder(*key):
            built.append(key)

            def fn(*args):
                raise JaxRuntimeError(
                    "INTERNAL: failed to compile the sort program")
            return fn

        monkeypatch.setenv("PAIMON_FORCE_DEVICE_SORT", "1")
        monkeypatch.setattr(M, builder, refusing_builder)
        lanes, seq = _mk(3000)
        packed = lanes[:, 0].astype(np.uint64) << np.uint64(32)
        with pytest.raises(JaxRuntimeError, match="failed to compile"):
            M.device_sorted_winners(lanes, seq, "last",
                                    winners_only=winners_only,
                                    packed=packed)
        assert len(built) == 1          # asked once


class TestRouteLog:
    @pytest.mark.parametrize("pin", [None, "PAIMON_FORCE_HOST_SORT",
                                     "PAIMON_FORCE_DEVICE_SORT"])
    def test_entries_name_two_routes_and_six_inputs(self, monkeypatch,
                                                    pin):
        """What the benchmark's meters and the chip smoke read: one
        entry a merge, `route` host or device, whichever engine merged
        and whatever pinned it."""
        from paimon_tpu.ops.agg import merge_runs_agg
        from paimon_tpu.options import CoreOptions
        from paimon_tpu.schema import Schema
        from paimon_tpu.schema.table_schema import TableSchema
        from paimon_tpu.types import BigIntType

        schema = (Schema.builder()
                  .column("id", BigIntType(False))
                  .column("v", BigIntType())
                  .primary_key("id")
                  .options({"bucket": "1", "merge-engine": "aggregation",
                            "fields.v.aggregate-function": "sum"})
                  .build())
        rng = np.random.default_rng(7)
        runs = []
        for r in range(3):
            ids = np.sort(rng.integers(0, 400, 1500))
            runs.append(pa.table({
                "_KEY_id": pa.array(ids, pa.int64()),
                "_SEQUENCE_NUMBER": pa.array(
                    np.arange(r * 1500, (r + 1) * 1500), pa.int64()),
                "_VALUE_KIND": pa.array(np.zeros(1500, np.int8), pa.int8()),
                "id": pa.array(ids, pa.int64()),
                "v": pa.array(rng.integers(0, 9, 1500), pa.int64())}))
        if pin:
            monkeypatch.setenv(pin, "1")
        monkeypatch.setattr(M, "ROUTE_LOG", [])
        enc = NormalizedKeyEncoder([pa.int64()], nullable=[False])
        M.merge_runs(runs, ["_KEY_id"], key_encoder=enc)
        M.merge_runs(runs, ["_KEY_id"], key_encoder=enc, with_prev=True)
        merge_runs_agg(runs, ["_KEY_id"], TableSchema.from_schema(0, schema),
                       CoreOptions(schema.options), key_encoder=enc)
        assert len(M.ROUTE_LOG) == 3
        for entry in M.ROUTE_LOG:
            assert set(entry) == {"rows", "lanes", "winners_only",
                                  "host_fast", "pinned", "route"}
            assert entry["rows"] == 4500 and entry["lanes"] == 2
            assert entry["pinned"] is (pin is not None)
            assert entry["route"] == (
                "device" if pin == "PAIMON_FORCE_DEVICE_SORT" else "host")
