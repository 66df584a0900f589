"""Mesh-sharded multi-bucket merge.

Buckets are the unit of parallelism (reference shuffles rows to bucket
tasks via table/sink/ChannelComputer + FlinkStreamPartitioner; each task
merges one bucket with a loser tree). The TPU layout instead stacks all
buckets into [B, N, ...] arrays, shards the bucket axis over a
`jax.sharding.Mesh`, and runs the per-bucket segmented sort-merge
(ops/merge.py kernel) vmapped on every device, with commit statistics
(row counts) reduced across the mesh by `psum` over ICI.

Used by the multi-bucket compaction path and by the driver's multichip
dryrun; exercised on a virtual 8-device CPU mesh in tests.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["bucket_mesh", "pad_bucket_batches", "ShardedBucketMerge"]


def bucket_mesh(n_devices: Optional[int] = None, axis: str = "buckets"):
    """A 1-D device mesh over the bucket axis."""
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), axis_names=(axis,))


def pad_bucket_batches(
    lanes_list: Sequence[np.ndarray], seq_list: Sequence[np.ndarray]
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Stack per-bucket (lanes uint32[N_b, L], seq int64[N_b]) into padded
    [B, N, ...] arrays with an invalid mask (padding sorts last)."""
    from paimon_tpu.ops.merge import _pad_size

    b = len(lanes_list)
    num_lanes = lanes_list[0].shape[1] if b else 0
    # pad the row axis to a power of two so successive calls with nearby
    # bucket sizes reuse the compiled sharded program
    n = _pad_size(max((len(s) for s in seq_list), default=0))
    lanes = np.zeros((b, n, num_lanes), dtype=np.uint32)
    seq_hi = np.zeros((b, n), dtype=np.uint32)
    seq_lo = np.zeros((b, n), dtype=np.uint32)
    invalid = np.ones((b, n), dtype=np.uint32)
    for i, (la, sq) in enumerate(zip(lanes_list, seq_list)):
        k = len(sq)
        lanes[i, :k] = la
        u = sq.astype(np.int64).view(np.uint64)
        seq_hi[i, :k] = (u >> np.uint64(32)).astype(np.uint32)
        seq_lo[i, :k] = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        invalid[i, :k] = 0
    return lanes, seq_hi, seq_lo, invalid


class ShardedBucketMerge:
    """Compile-once sharded merge over a mesh.

    __call__(lanes[B,N,L], seq_hi[B,N], seq_lo[B,N], invalid[B,N]) ->
    (perm[B,N] int32, winner[B,N] bool, total_rows int64 replicated).
    B must be a multiple of the mesh axis size.
    """

    def __init__(self, mesh, num_lanes: int, keep: str = "last",
                 axis: str = "buckets"):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.mesh = mesh
        self.axis = axis
        self.num_lanes = num_lanes
        self.sharding = NamedSharding(mesh, P(axis))
        n_dev = mesh.shape[axis]

        from paimon_tpu.ops.merge import segmented_merge_body

        def per_bucket(lanes, seq_hi, seq_lo, invalid):
            perm, winner, _ = segmented_merge_body(
                [lanes[:, i] for i in range(num_lanes)],
                seq_hi, seq_lo, invalid, keep)
            return perm, winner

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(P(axis), P(axis), P(axis), P(axis)),
                 out_specs=(P(axis), P(axis), P()))
        def step(lanes, seq_hi, seq_lo, invalid):
            perm, winner = jax.vmap(per_bucket)(lanes, seq_hi, seq_lo,
                                                invalid)
            local_rows = jnp.sum(winner.astype(jnp.int64))
            total_rows = jax.lax.psum(local_rows, axis)
            return perm, winner, total_rows.reshape(1)

        self._fn = jax.jit(step)
        self._n_dev = n_dev

    def __call__(self, lanes: np.ndarray, seq_hi: np.ndarray,
                 seq_lo: np.ndarray, invalid: np.ndarray):
        import jax

        b = lanes.shape[0]
        if b % self._n_dev != 0:
            pad = self._n_dev - b % self._n_dev
            lanes = np.concatenate(
                [lanes, np.zeros((pad,) + lanes.shape[1:], lanes.dtype)])
            seq_hi = np.concatenate(
                [seq_hi, np.zeros((pad,) + seq_hi.shape[1:], seq_hi.dtype)])
            seq_lo = np.concatenate(
                [seq_lo, np.zeros((pad,) + seq_lo.shape[1:], seq_lo.dtype)])
            invalid = np.concatenate(
                [invalid, np.ones((pad,) + invalid.shape[1:], invalid.dtype)])
        args = [jax.device_put(a, self.sharding)
                for a in (lanes, seq_hi, seq_lo, invalid)]
        perm, winner, total = self._fn(*args)
        jax.block_until_ready((perm, winner, total))
        return (np.asarray(perm)[:b], np.asarray(winner)[:b],
                int(np.asarray(total)[0]))


_MERGER_CACHE: dict = {}


def _cached_merger(mesh, num_lanes: int, keep: str) -> "ShardedBucketMerge":
    key = (mesh, num_lanes, keep)
    m = _MERGER_CACHE.get(key)
    if m is None:
        m = _MERGER_CACHE[key] = ShardedBucketMerge(mesh, num_lanes,
                                                    keep=keep)
    return m


def merge_buckets_sharded(
    lanes_list: Sequence[np.ndarray], seq_list: Sequence[np.ndarray],
    mesh=None, keep: str = "last"
) -> Tuple[List[np.ndarray], int]:
    """Merge many buckets at once over a mesh.

    Each bucket b has key lanes uint32[N_b, L] and sequence int64[N_b]
    (rows in arrival order, runs already concatenated oldest-first).
    Returns per-bucket winner indices (into the bucket's input order,
    sorted by key) and the psum'd total output row count.
    """
    if not lanes_list:
        return [], 0
    if mesh is None:
        mesh = bucket_mesh()
    lanes, seq_hi, seq_lo, invalid = pad_bucket_batches(lanes_list, seq_list)
    merger = _cached_merger(mesh, lanes.shape[2], keep)
    perm, winner, total = merger(lanes, seq_hi, seq_lo, invalid)
    out = []
    for i in range(len(lanes_list)):
        win_pos = np.flatnonzero(winner[i])
        out.append(perm[i][win_pos].astype(np.int64))
    return out, total
