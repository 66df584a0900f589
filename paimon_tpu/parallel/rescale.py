"""Bucket rescale via an all_to_all collective repartition.

reference: changing a table's bucket count requires a full shuffle —
each row re-hashes to `Math.abs(hash % newBuckets)` and moves to its
new owner task (table/sink/ChannelComputer.java routing, executed as a
flink network shuffle by dedicated rescale jobs).

TPU shape: the shuffle IS the collective.  Each device receives an
equal slice of the table's row-hash vector; on device it computes every
row's new bucket (Java truncated `abs(h % B)` via lax.rem, bit-compat
with core/bucket.py), packs row REFERENCES into per-target-device slot
blocks, and one `jax.lax.all_to_all` over the mesh delivers each
device exactly the references it will own (ownership: new_bucket %
n_devices, round-robin).  Variable-length row bytes never cross the
device — the host moves Arrow rows per the mesh-computed routing
table, writes the new bucket files, and commits an overwrite.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional

import numpy as np

__all__ = ["rescale_dispatch_sharded", "rescale_table_buckets",
           "rescale_routing", "rescale_write_messages",
           "rescale_commit"]

_INVALID = np.uint32(0xFFFFFFFF)


def _dispatch_kernel(mesh, axis: str, n_per_dev: int, cap: int,
                     new_buckets: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    n_dev = mesh.shape[axis]

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(axis), P(axis), P(axis)),
             out_specs=(P(axis), P(axis), P(axis)))
    def step(hashes, valid, row_gid):
        h, v, gid = hashes[0], valid[0], row_gid[0]
        # Java `Math.abs(h % n)` with truncated division == abs(lax.rem)
        signed = h.astype(jnp.int32)
        new_bucket = jnp.abs(
            jax.lax.rem(signed, jnp.int32(new_buckets))).astype(jnp.uint32)
        target = (new_bucket % jnp.uint32(n_dev)).astype(jnp.uint32)
        target = jnp.where(v, target, jnp.uint32(n_dev))   # padding rows
        # contiguous per-target runs via one stable sort
        order = jnp.argsort(target, stable=True)
        s_target = target[order]
        s_gid = gid[order]
        s_bucket = new_bucket[order]
        starts = jnp.searchsorted(
            s_target, jnp.arange(n_dev, dtype=jnp.uint32))
        idx_in_run = jnp.arange(n_per_dev, dtype=jnp.int32) - starts[
            jnp.minimum(s_target, n_dev - 1).astype(jnp.int32)]
        ok = (s_target < n_dev) & (idx_in_run < cap)
        slot_gid = jnp.full((n_dev, cap), _INVALID, dtype=jnp.uint32)
        slot_bkt = jnp.full((n_dev, cap), _INVALID, dtype=jnp.uint32)
        # route not-ok rows to an out-of-range slot and let mode="drop"
        # discard them — an in-range dummy index would race the genuine
        # row scattered there (scatter order is unspecified)
        rows = jnp.where(ok, s_target.astype(jnp.int32), n_dev)
        cols = jnp.where(ok, idx_in_run, 0)
        slot_gid = slot_gid.at[rows, cols].set(s_gid, mode="drop")
        slot_bkt = slot_bkt.at[rows, cols].set(s_bucket, mode="drop")
        dropped = jnp.sum((s_target < n_dev) & ~(idx_in_run < cap))
        # THE collective: slot block d travels to device d
        recv_gid = jax.lax.all_to_all(slot_gid, axis, 0, 0)
        recv_bkt = jax.lax.all_to_all(slot_bkt, axis, 0, 0)
        total_dropped = jax.lax.psum(dropped, axis)
        return (recv_gid[None], recv_bkt[None],
                total_dropped.reshape(1, 1))

    return jax.jit(step)


def rescale_dispatch_sharded(hashes: np.ndarray, new_buckets: int,
                             mesh=None, axis: str = "buckets",
                             slack: float = 2.0
                             ) -> Dict[int, np.ndarray]:
    """Route every row to its new bucket with one all_to_all.

    hashes: uint32[total_rows] reference-compatible bucket hashes in
    global row order (core/bucket.KeyHasher.hashes low 32 bits).
    Returns {new_bucket: sorted global row indices} covering every row.
    Slot capacity doubles-and-retries on hash skew overflow."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paimon_tpu.parallel.sharded_merge import bucket_mesh

    if mesh is None:
        mesh = bucket_mesh(axis=axis)
    n_dev = mesh.shape[axis]
    total = len(hashes)
    n_per_dev = max(1, -(-total // n_dev))
    # balanced load per (source, target) block is n_per_dev/n_dev;
    # worst case (every local row to one target) is n_per_dev
    cap = min(n_per_dev, max(16, int(n_per_dev / n_dev * slack)))

    padded = n_per_dev * n_dev
    h = np.zeros(padded, dtype=np.uint32)
    h[:total] = hashes.astype(np.uint32)
    valid = np.zeros(padded, dtype=bool)
    valid[:total] = True
    gid = np.arange(padded, dtype=np.uint32)

    fn = _dispatch_kernel(mesh, axis, n_per_dev, cap, new_buckets)
    sharding = NamedSharding(mesh, P(axis))
    args = [jax.device_put(a.reshape(n_dev, n_per_dev), sharding)
            for a in (h, valid, gid)]
    recv_gid, recv_bkt, dropped = fn(*args)
    jax.block_until_ready((recv_gid, recv_bkt, dropped))
    if int(np.asarray(dropped).sum()) > 0:
        if cap >= n_per_dev:
            raise RuntimeError("rescale slot capacity overflow")
        return rescale_dispatch_sharded(hashes, new_buckets, mesh, axis,
                                        slack * 4)

    gids = np.asarray(recv_gid).reshape(-1)   # [n_dev * n_dev * cap]
    bkts = np.asarray(recv_bkt).reshape(-1)
    ok = gids != _INVALID
    gids, bkts = gids[ok], bkts[ok]
    result: Dict[int, np.ndarray] = {}
    order = np.argsort(bkts, kind="stable")
    bkts_s, gids_s = bkts[order], gids[order]
    uniq, starts = np.unique(bkts_s, return_index=True)
    bounds = np.append(starts, len(bkts_s))
    for i, b in enumerate(uniq):
        result[int(b)] = np.sort(
            gids_s[bounds[i]:bounds[i + 1]]).astype(np.int64)
    routed = sum(len(v) for v in result.values())
    assert routed == total, (routed, total)
    return result


def _validate_rescale(table, new_buckets: int):
    if not table.primary_keys or table.options.bucket < 1:
        raise ValueError("rescale targets fixed-bucket pk tables")
    if table.partition_keys:
        raise NotImplementedError("rescale of partitioned tables: loop "
                                  "partitions")
    if new_buckets < 1:
        raise ValueError("new_buckets must be >= 1")


def rescale_routing(table, values, new_buckets: int,
                    mesh=None) -> Dict[int, np.ndarray]:
    """{new_bucket: global row indices into `values`} via the mesh
    all_to_all dispatch, bit-compat-checked against the host bucket
    formula.  Bucket membership is a pure function of the row keys, so
    every host of a multi-host plane computes an EQUIVALENT routing
    from the same pinned snapshot regardless of its local mesh shape —
    which is what lets the distributed rescale shard the rewrite by
    target-bucket ownership with no routing exchange."""
    from paimon_tpu.core.bucket import KeyHasher, _bucket_from_hash

    bucket_keys = table.schema.bucket_keys() or \
        table.schema.trimmed_primary_keys()
    rt = table.schema.logical_row_type()
    hasher = KeyHasher(bucket_keys,
                       [rt.get_field(k).type for k in bucket_keys])
    hashes = (hasher.hashes(values)
              & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    routing = rescale_dispatch_sharded(hashes, new_buckets, mesh)
    # bit-compat guard against the host formula
    host_buckets = _bucket_from_hash(hashes, new_buckets)
    for b, gids in routing.items():
        assert (host_buckets[gids] == b).all(), \
            "device routing diverged from reference bucket formula"
    return routing


def rescale_write_messages(table, values, routing, new_buckets: int,
                           buckets: Optional[List[int]] = None):
    """Write the rescaled bucket files for `buckets` (default: every
    routed bucket) and return their CommitMessages.  A multi-host
    plane passes each host the subset it will OWN under the bumped
    ownership map, so the rewrite IO shards across hosts and the
    elected committer only publishes."""
    import pyarrow as pa

    from paimon_tpu.core.kv_file import KeyValueFileWriter
    from paimon_tpu.core.read import MergeFileSplitRead
    from paimon_tpu.core.write import CommitMessage, build_kv_table
    from paimon_tpu.ops.merge import sort_table
    from paimon_tpu.options import CoreOptions

    reader = MergeFileSplitRead(table.file_io, table.path, table.schema,
                                table.options)
    writer = KeyValueFileWriter(
        table.file_io, reader.path_factory, table.schema,
        file_format=table.options.file_format,
        compression=table.options.file_compression,
        target_file_size=table.options.target_file_size,
        index_spec=table.options.file_index_spec,
        bloom_fpp=table.options.get(CoreOptions.FILE_INDEX_BLOOM_FPP),
        format_per_level=table.options.file_format_per_level,
        format_options=table.options.format_options,
        **table.options.kv_writer_kwargs())
    max_level = table.options.max_level

    wanted = None if buckets is None else {int(b) for b in buckets}
    messages: List[CommitMessage] = []
    for b, gids in sorted(routing.items()):
        if wanted is not None and int(b) not in wanted:
            continue
        rows = values.take(pa.array(gids))
        kv = build_kv_table(rows, table.schema,
                            np.arange(rows.num_rows, dtype=np.int64),
                            np.zeros(rows.num_rows, dtype=np.int8))
        order = sort_table(kv, reader.key_cols,
                          key_encoder=reader.key_encoder)
        kv = kv.take(pa.array(order))
        metas = writer.write((), int(b), kv, level=max_level)
        messages.append(CommitMessage((), int(b), new_buckets,
                                      new_files=metas))
    return messages


def rescale_commit(table, new_buckets: int, messages,
                   properties: Optional[Dict[str, str]] = None
                   ) -> Optional[int]:
    """Publish a rescale: ALTER the bucket option first, then INSERT
    OVERWRITE the reorganized data (reference procedure order; writers
    must be paused for the whole rescale, like the reference's offline
    rescale job).  If the overwrite fails, roll the option back so the
    pre-rescale layout stays consistent with the schema."""
    from paimon_tpu.core.commit import FileStoreCommit
    from paimon_tpu.schema import SchemaChange, SchemaManager

    sm = SchemaManager(table.file_io, table.path, table.branch)
    sm.commit_changes(SchemaChange.set_option("bucket", str(new_buckets)))
    try:
        commit = FileStoreCommit(table.file_io, table.path, table.schema,
                                 table.options, branch=table.branch)
        sid = commit.overwrite(messages, properties=properties)
    except BaseException:
        sm.commit_changes(SchemaChange.set_option(
            "bucket", str(table.options.bucket)))
        raise
    return sid


def rescale_table_buckets(table, new_buckets: int, mesh=None,
                          properties: Optional[Dict[str, str]] = None
                          ) -> Optional[int]:
    """Rewrite a fixed-bucket primary-key table to `new_buckets`: the
    mesh computes the routing (abs(hash % B) + all_to_all), the host
    moves rows, writes the new bucket files and commits an overwrite
    (stamped with `properties`, e.g. the distributed write plane's
    ownership-map generation), then records the new bucket count in
    the schema."""
    _validate_rescale(table, new_buckets)
    values = table.to_arrow()      # merged current state, value columns
    if values.num_rows == 0:
        return None
    routing = rescale_routing(table, values, new_buckets, mesh)
    messages = rescale_write_messages(table, values, routing,
                                      new_buckets)
    return rescale_commit(table, new_buckets, messages,
                          properties=properties)
