"""Multi-host bootstrap and topology helpers.

The reference scales out through engine clusters whose workers talk
NCCL/MPI-style through Flink/Spark RPC (SURVEY §5 "distributed
communication backend").  The TPU-native counterpart is jax's
distributed runtime: every host runs the same program, devices of all
hosts form ONE global `Mesh`, and XLA inserts ICI/DCN collectives for
the shardings used — nothing in the table format itself needs a
message bus.  This module is the glue:

- `initialize(...)`: `jax.distributed.initialize` with env fallbacks
  (COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID — the same shape
  torchrun/mpirun environments provide).
- `global_mesh(...)`: a Mesh over every device of every host.
- `process_local_batch(...)`: turn each host's local Arrow/numpy batch
  into one globally-sharded jax.Array
  (`jax.make_array_from_process_local_data`) — the multi-host data
  ingestion path for jax_data loaders.
- `assign_splits(...)`: deterministic scan-split ownership per process
  (the analog of the reference's split enumerator handing splits to
  parallel source readers), byte-size-aware LPT like
  parallel/packing.py so one host never owns all the large splits.
- `barrier(...)` / `broadcast_value(...)` / `allgather_bytes(...)`:
  the small agreement primitives the distributed write plane
  (parallel/distributed.py) builds commit arbitration, pinned-snapshot
  scans and rescale handoffs on.

Everything degrades to single-process: `initialize` is a no-op when
num_processes==1, the mesh covers local devices, split assignment
returns everything, and the agreement primitives return their inputs
without touching a collective.
"""

import os
import time as _time
from typing import List, Optional, Sequence, Tuple

import numpy as np


# the coordination service counts a peer's silence in heartbeats of this
# many seconds; its own knob is the total timeout
_HEARTBEAT_INTERVAL_S = 10


def peer_death_tolerance(max_missing_heartbeats: Optional[int] = None
                         ) -> dict:
    """Heartbeat-tolerance kwargs for `jax.distributed.initialize`,
    from the explicit argument or the
    `PAIMON_MULTIHOST_PEER_MISSED_HEARTBEATS` env var.  Empty dict
    when neither is set (jax's default applies: after ~100s of silence
    the coordination service declares the quiet task crashed and
    FATALLY tears down every other task).

    That default contradicts this repo's fleet design: host death is
    an EXPECTED event the lease detector (parallel/maintenance_plane)
    observes and survives — survivors adopt the dead host's groups
    and keep serving.  A mesh that opts in here keeps the survivors'
    processes alive through a peer's death long enough for leases to
    govern, instead of having XLA abort them ~100s in."""
    if max_missing_heartbeats is None:
        env = os.environ.get("PAIMON_MULTIHOST_PEER_MISSED_HEARTBEATS")
        if env:
            max_missing_heartbeats = int(env)
    if max_missing_heartbeats is None:
        return {}
    return {"heartbeat_timeout_seconds":
            max_missing_heartbeats * _HEARTBEAT_INTERVAL_S}


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               max_missing_heartbeats: Optional[int] = None
               ) -> Tuple[int, int]:
    """Bring up jax's distributed runtime (multi-host). Arguments
    default from the standard env vars; single-process is a no-op.
    Returns (process_index, process_count).

    `max_missing_heartbeats` (or the
    `PAIMON_MULTIHOST_PEER_MISSED_HEARTBEATS` env var) widens how many
    10s heartbeats a peer may miss before the coordination service
    declares it crashed and aborts the WHOLE mesh — see
    `peer_death_tolerance` for why lease-governed fleets want this.

    CPU meshes ride jax's Gloo cross-process collectives, which the
    installed jax enables by default."""
    import jax

    coordinator_address = coordinator_address or \
        os.environ.get("COORDINATOR_ADDRESS")
    if num_processes is None:
        num_processes = int(os.environ.get("NUM_PROCESSES", "1"))
    if process_id is None:
        process_id = int(os.environ.get("PROCESS_ID", "0"))
    if num_processes > 1:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            **peer_death_tolerance(max_missing_heartbeats))
    return jax.process_index(), jax.process_count()


def global_mesh(axis_names: Sequence[str] = ("data",),
                shape: Optional[Sequence[int]] = None):
    """A Mesh over ALL devices (every process's chips). With one axis
    the shape is inferred; multi-axis shapes must multiply out to the
    global device count."""
    import jax
    from jax.sharding import Mesh

    devices = np.asarray(jax.devices())
    if shape is None:
        if len(axis_names) != 1:
            raise ValueError("shape is required for a multi-axis mesh")
        shape = (len(devices),)
    if int(np.prod(shape)) != len(devices):
        raise ValueError(f"mesh shape {tuple(shape)} != device count "
                         f"{len(devices)}")
    return Mesh(devices.reshape(shape), tuple(axis_names))


def process_local_batch(mesh, name_to_array, axis: str = "data"):
    """Assemble each process's host-local numpy columns into ONE
    globally sharded array per column: host batches concatenate along
    `axis` across processes without any host gathering the whole batch
    (reference: parallel source readers each feeding their workers).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = NamedSharding(mesh, PartitionSpec(axis))
    out = {}
    for name, arr in name_to_array.items():
        arr = np.asarray(arr)
        out[name] = jax.make_array_from_process_local_data(
            sharding, arr)
    return out


def split_weight(split) -> int:
    """A split's assignment weight: on-disk bytes from manifest stats
    (DataFileMeta.file_size sums — available before any file IO, same
    source as parallel/packing.bucket_row_counts).  Objects without
    data_files weigh 1 so plain sequences still round-robin."""
    files = getattr(split, "data_files", None)
    if not files:
        return 1
    return max(1, sum(int(f.file_size) for f in files))


def assign_splits(splits: Sequence, process_index: Optional[int] = None,
                  process_count: Optional[int] = None) -> List:
    """Deterministic byte-size-aware split ownership: splits pack onto
    processes with the same greedy LPT policy as parallel/packing.py,
    keyed on manifest byte sizes — round-robin by index ignored sizes,
    so one host could own every large split while its peers finished
    early and idled at the scan barrier.  Every process computes the
    SAME plan (sort + tie-breaks are total orders over (size, index)),
    reads only its own share, and no coordinator or shuffle is needed
    — the contract of the reference's split enumerator and the torch
    loader's (rank, worker) sharding, unchanged."""
    import jax

    if process_index is None:
        process_index = jax.process_index()
    if process_count is None:
        process_count = jax.process_count()
    if process_count <= 1:
        return list(splits)
    weights = [split_weight(s) for s in splits]
    order = sorted(range(len(splits)),
                   key=lambda i: (-weights[i], i))
    loads = [0] * process_count
    mine: List[int] = []
    for i in order:
        target = min(range(process_count), key=lambda p: (loads[p], p))
        loads[target] += weights[i]
        if target == process_index:
            mine.append(i)
    # preserve plan order within the owned share (stable for callers
    # that zip splits with prior state)
    return [splits[i] for i in sorted(mine)]


def distributed_write_commit_user(base: str = "writer") -> str:
    """Per-process commit user for multi-host writers: processes write
    independently and the snapshot CAS serializes their commits (the
    object-store conditional-PUT / rename-CAS is the only global
    agreement point — reference: committer operator singleton)."""
    import jax

    return f"{base}-p{jax.process_index()}"


# -- agreement primitives (parallel/distributed.py builds on these) ----------

def barrier(name: str = "barrier") -> float:
    """Block until every process reaches this point; returns the wait
    in milliseconds (also recorded in the multihost metric group —
    the direct cost of global agreement).  Single-process: 0ms.

    Deadline-aware like every other blocking wait in the repo
    (utils/deadline.py): a request whose budget is already spent must
    not ENTER a collective it may never leave — the tier-1 lint bans
    direct sync_global_devices / broadcast_one_to_all /
    process_allgather calls outside this module for exactly this
    reason (plus the wait metric)."""
    import jax

    if jax.process_count() == 1:
        return 0.0
    from jax.experimental import multihost_utils

    from paimon_tpu.metrics import (
        MULTIHOST_BARRIER_WAIT_MS, global_registry,
    )
    from paimon_tpu.utils.deadline import check_deadline
    check_deadline(f"multihost barrier {name!r}")
    t0 = _time.perf_counter()
    multihost_utils.sync_global_devices(name)
    waited = (_time.perf_counter() - t0) * 1000
    global_registry().multihost_metrics().histogram(
        MULTIHOST_BARRIER_WAIT_MS).update(waited)
    return waited


def broadcast_value(value: int, root: int = 0) -> int:
    """Agree on one int64 across all processes: `root`'s value wins
    (the "small broadcast" pinning one snapshot id for a
    snapshot-consistent cross-host scan).  Single-process: identity."""
    import jax

    if jax.process_count() == 1:
        return int(value)
    from jax.experimental import multihost_utils

    from paimon_tpu.utils.deadline import check_deadline
    check_deadline("multihost broadcast")
    out = multihost_utils.broadcast_one_to_all(
        np.asarray(int(value), dtype=np.int64),
        is_source=jax.process_index() == root)
    return int(np.asarray(out))


def allgather_bytes(payload: bytes) -> List[bytes]:
    """Every process contributes one bytes payload; every process
    receives ALL of them, indexed by process id.  Two-phase (length
    allgather -> padded uint8 allgather) so payload sizes may differ.
    This is the commit-message wire of coordinator arbitration and the
    row-exchange wire of 'exchange' routing.  Single-process:
    [payload]."""
    import jax

    if jax.process_count() == 1:
        return [bytes(payload)]
    from jax.experimental import multihost_utils

    from paimon_tpu.utils.deadline import check_deadline
    check_deadline("multihost allgather")
    arr = np.frombuffer(bytes(payload), dtype=np.uint8)
    lengths = np.asarray(multihost_utils.process_allgather(
        np.asarray([len(arr)], dtype=np.int64)))
    lengths = lengths.reshape(jax.process_count(), -1)[:, 0]
    max_len = max(1, int(lengths.max()))
    padded = np.zeros(max_len, dtype=np.uint8)
    padded[:len(arr)] = arr
    gathered = np.asarray(multihost_utils.process_allgather(padded))
    gathered = gathered.reshape(jax.process_count(), max_len)
    return [gathered[p, :int(lengths[p])].tobytes()
            for p in range(jax.process_count())]
