"""Multi-host helpers on the virtual 8-device CPU mesh (the env's
stand-in for real multi-chip/host topology; conftest forces
xla_force_host_platform_device_count=8)."""

import numpy as np
import pyarrow as pa
import pytest

from paimon_tpu.parallel import multihost as MH


class TestBootstrap:
    def test_single_process_noop(self):
        idx, count = MH.initialize()
        assert (idx, count) == (0, 1)

    def test_env_defaults(self, monkeypatch):
        monkeypatch.setenv("NUM_PROCESSES", "1")
        monkeypatch.setenv("PROCESS_ID", "0")
        assert MH.initialize() == (0, 1)

    def test_peer_death_tolerance_unset(self, monkeypatch):
        monkeypatch.delenv("PAIMON_MULTIHOST_PEER_MISSED_HEARTBEATS",
                           raising=False)
        assert MH.peer_death_tolerance() == {}

    def test_peer_death_tolerance_explicit_and_env(self, monkeypatch):
        # N missed 10s heartbeats -> the runtime's total timeout
        assert MH.peer_death_tolerance(360) == {
            "heartbeat_timeout_seconds": 3600}
        monkeypatch.setenv("PAIMON_MULTIHOST_PEER_MISSED_HEARTBEATS",
                           "25")
        assert MH.peer_death_tolerance() == {
            "heartbeat_timeout_seconds": 250}
        # explicit argument wins over the env var
        assert MH.peer_death_tolerance(7) == {
            "heartbeat_timeout_seconds": 70}

    def test_initialize_forwards_tolerance_to_the_runtime(
            self, monkeypatch):
        """The one bring-up path: the public jax call, which on the
        installed jax takes the heartbeat budget itself."""
        import inspect

        import jax

        assert "heartbeat_timeout_seconds" in inspect.signature(
            jax.distributed.initialize).parameters
        inits = []
        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda **kw: inits.append(kw))
        MH.initialize("127.0.0.1:1", 2, 0, max_missing_heartbeats=360)
        assert inits == [{"coordinator_address": "127.0.0.1:1",
                          "num_processes": 2, "process_id": 0,
                          "heartbeat_timeout_seconds": 3600}]


class TestGlobalMesh:
    def test_one_axis_inferred(self):
        import jax
        mesh = MH.global_mesh(("data",))
        assert mesh.devices.size == len(jax.devices())
        assert mesh.axis_names == ("data",)

    def test_two_axis(self):
        mesh = MH.global_mesh(("data", "model"), shape=(4, 2))
        assert mesh.devices.shape == (4, 2)

    def test_bad_shape_rejected(self):
        with pytest.raises(ValueError, match="!= device count"):
            MH.global_mesh(("data",), shape=(3,))
        with pytest.raises(ValueError, match="shape is required"):
            MH.global_mesh(("a", "b"))


class TestProcessLocalBatch:
    def test_batch_shards_across_mesh(self):
        import jax
        mesh = MH.global_mesh(("data",))
        n = len(jax.devices()) * 4
        batch = MH.process_local_batch(
            mesh, {"x": np.arange(n, dtype=np.int32),
                   "y": np.arange(n, dtype=np.float32) * 2})
        assert batch["x"].shape == (n,)
        assert batch["x"].sharding.mesh.shape["data"] == \
            len(jax.devices())
        # a sharded computation over it works
        assert int(jax.numpy.sum(batch["x"])) == n * (n - 1) // 2

    def test_feeds_jax_data_loader_sharding(self, tmp_path):
        # jax_batches with a NamedSharding scatters device_puts
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        from paimon_tpu.integrations.jax_data import jax_batches
        from paimon_tpu.schema import Schema
        from paimon_tpu.table import FileStoreTable
        from paimon_tpu.types import BigIntType

        schema = (Schema.builder().column("id", BigIntType(False))
                  .options({"bucket": "-1"}).build())
        t = FileStoreTable.create(str(tmp_path / "t"), schema)
        wb = t.new_batch_write_builder()
        w = wb.new_write()
        w.write_arrow(pa.table({"id": pa.array(range(64), pa.int64())}))
        wb.new_commit().commit(w.prepare_commit())
        mesh = MH.global_mesh(("data",))
        sh = NamedSharding(mesh, PartitionSpec("data"))
        batches = list(jax_batches(t, 32, sharding=sh))
        assert len(batches) == 2
        assert batches[0]["id"].sharding == sh
        _ = jax.block_until_ready(batches[0]["id"])


class _FakeFile:
    def __init__(self, size):
        self.file_size = size


class _FakeSplit:
    def __init__(self, *sizes):
        self.data_files = [_FakeFile(s) for s in sizes]


class TestSplitAssignment:
    def test_partition_of_splits(self):
        # equal-weight splits degrade LPT to round-robin (ties break
        # on index), preserving the original ownership contract
        splits = list(range(10))
        owned = [MH.assign_splits(splits, p, 3) for p in range(3)]
        assert sorted(x for part in owned for x in part) == splits
        assert owned[0] == [0, 3, 6, 9]

    def test_default_single_process_owns_all(self):
        assert MH.assign_splits([1, 2, 3]) == [1, 2, 3]

    def test_byte_aware_lpt_balances_large_splits(self):
        # round-robin by index would give process 0 BOTH huge splits
        # (indices 0 and 2); byte-aware LPT spreads them
        splits = [_FakeSplit(1000), _FakeSplit(1), _FakeSplit(1000),
                  _FakeSplit(1)]
        owned = [MH.assign_splits(splits, p, 2) for p in range(2)]
        # disjoint cover
        ids = sorted(id(s) for part in owned for s in part)
        assert ids == sorted(id(s) for s in splits)
        loads = [sum(MH.split_weight(s) for s in part)
                 for part in owned]
        assert max(loads) <= 1001          # one big + one small each

    def test_lpt_deterministic_across_callers(self):
        import random
        sizes = [random.Random(7).randrange(1, 10_000)
                 for _ in range(50)]
        splits = [_FakeSplit(s) for s in sizes]
        for p in range(4):
            a = MH.assign_splits(splits, p, 4)
            b = MH.assign_splits(splits, p, 4)
            assert [id(s) for s in a] == [id(s) for s in b]
        # every process's plan agrees: union is a disjoint cover
        all_owned = [s for p in range(4)
                     for s in MH.assign_splits(splits, p, 4)]
        assert sorted(id(s) for s in all_owned) == \
            sorted(id(s) for s in splits)

    def test_split_weight_floor(self):
        assert MH.split_weight(object()) == 1
        assert MH.split_weight(_FakeSplit()) == 1
        assert MH.split_weight(_FakeSplit(0, 0)) == 1

    def test_commit_user(self):
        assert MH.distributed_write_commit_user("w") == "w-p0"
