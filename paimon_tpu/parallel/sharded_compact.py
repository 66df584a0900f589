"""End-to-end sharded bucket compaction over a device mesh.

reference: compaction parallelism is one JVM task per bucket
(mergetree/compact/MergeTreeCompactTask.java:83 scheduled by
flink sink topologies via table/sink/ChannelComputer.java).  The TPU
layout runs EVERY bucket's compaction in one mesh program instead:

  host:   decode each bucket's sorted runs (Arrow, variable-length data
          stays on host) and encode fixed-width key lanes
  device: [B, N] bucket-stacked lanes sharded over the mesh axis; each
          device sort-merges its buckets (vmapped segmented kernel) and
          computes the COMMIT STATISTICS on device: per-bucket output
          row counts, live-row counts (delete kinds excluded) and the
          psum'd totals that the commit message needs
  host:   takes winner indices per bucket, encodes output files, and
          commits compact_before/compact_after in one snapshot

So the merge AND the bookkeeping reductions ride the mesh; only
file IO and Arrow assembly stay on host.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import numpy as np

__all__ = ["ShardedCompactStats", "compact_table_sharded"]


class _ShardedCompactKernel:
    """shard_map(vmap(segmented merge)) + device-side stats reductions.

    __call__(lanes[B,N,L], seq_hi, seq_lo, invalid, kinds[B,N]) ->
    (perm[B,N], winner[B,N], live[B,N],
     per_bucket_out[B], total_out, total_live) — totals psum'd over the
    mesh and replicated."""

    def __init__(self, mesh, num_lanes: int, axis: str = "buckets"):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from paimon_tpu.ops.merge import segmented_merge_body

        self.mesh = mesh
        self.axis = axis
        self.sharding = NamedSharding(mesh, P(axis))
        self.replicated = NamedSharding(mesh, P())
        self._n_dev = mesh.shape[axis]

        def per_bucket(lanes, seq_hi, seq_lo, invalid, kinds):
            perm, winner, _ = segmented_merge_body(
                [lanes[:, i] for i in range(num_lanes)],
                seq_hi, seq_lo, invalid, "last")
            # kinds travel in input order; gather to sorted order so the
            # winner mask lines up (0=+I, 2=+U survive full compaction)
            s_kinds = kinds[perm]
            live = winner & ((s_kinds == 0) | (s_kinds == 2))
            return perm, winner, live

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
                 out_specs=(P(axis), P(axis), P(axis), P(axis), P(), P()))
        def step(lanes, seq_hi, seq_lo, invalid, kinds):
            perm, winner, live = jax.vmap(per_bucket)(
                lanes, seq_hi, seq_lo, invalid, kinds)
            per_bucket_out = jnp.sum(live, axis=1, dtype=jnp.int64)
            total_out = jax.lax.psum(jnp.sum(winner, dtype=jnp.int64),
                                     self.axis)
            total_live = jax.lax.psum(jnp.sum(per_bucket_out), self.axis)
            return (perm, winner, live, per_bucket_out,
                    total_out.reshape(1), total_live.reshape(1))

        self._fn = jax.jit(step)

    def __call__(self, lanes, seq_hi, seq_lo, invalid, kinds):
        import jax

        b = lanes.shape[0]
        pad = (-b) % self._n_dev
        if pad:
            def ext(a, fill=0):
                shape = (pad,) + a.shape[1:]
                return np.concatenate(
                    [a, np.full(shape, fill, a.dtype)])
            lanes, seq_hi, seq_lo = ext(lanes), ext(seq_hi), ext(seq_lo)
            invalid = ext(invalid, 1)
            kinds = ext(kinds)
        args = [jax.device_put(a, self.sharding)
                for a in (lanes, seq_hi, seq_lo, invalid, kinds)]
        out = self._fn(*args)
        jax.block_until_ready(out)
        perm, winner, live, per_bucket, total, total_live = out
        return (np.asarray(perm)[:b], np.asarray(live)[:b],
                np.asarray(per_bucket)[:b], int(np.asarray(total)[0]),
                int(np.asarray(total_live)[0]))


class ShardedCompactStats:
    def __init__(self, buckets: int, input_rows: int, output_rows: int,
                 total_winners: int, snapshot_id: Optional[int]):
        self.buckets = buckets
        self.input_rows = input_rows
        self.output_rows = output_rows
        self.total_winners = total_winners
        self.snapshot_id = snapshot_id


_KERNEL_CACHE: dict = {}


def compact_table_sharded(table, mesh=None,
                          axis: str = "buckets") -> ShardedCompactStats:
    """Full compaction of every bucket of a primary-key table in one
    mesh program: read -> sharded merge + device stats -> encode ->
    COMPACT commit.  The deduplicate winner select runs vmapped per
    bucket with bucket-axis sharding; commit row counts come from the
    device psum, not host recounting."""
    import pyarrow as pa

    from paimon_tpu.core.kv_file import KeyValueFileWriter, read_kv_file
    from paimon_tpu.core.read import MergeFileSplitRead, assemble_runs
    from paimon_tpu.core.write import CommitMessage
    from paimon_tpu.core.commit import FileStoreCommit
    from paimon_tpu.ops.merge import KIND_COL, SEQ_COL
    from paimon_tpu.parallel.sharded_merge import (
        bucket_mesh, pad_bucket_batches,
    )
    from paimon_tpu.options import CoreOptions

    # this legacy path hard-codes the deduplicate winner select; any
    # other engine must fail loudly instead of silently deduping while
    # callers migrate to parallel/mesh_engine.compact_table_mesh
    from paimon_tpu.parallel.mesh_engine import UnsupportedMergeEngineError
    from paimon_tpu.options import MergeEngine
    engine = table.options.merge_engine
    if engine != MergeEngine.DEDUPLICATE:
        raise UnsupportedMergeEngineError(
            f"compact_table_sharded only implements merge-engine "
            f"'deduplicate', got {engine!r}; use "
            f"parallel.mesh_engine.compact_table_mesh, which dispatches "
            f"on the merge engine")
    if not table.primary_keys:
        raise ValueError("sharded compaction targets primary-key tables")
    if mesh is None:
        mesh = bucket_mesh(axis=axis)
    plan = table.new_read_builder().new_scan().plan()
    splits = [s for s in plan.splits if len(s.data_files) > 0]
    if not splits:
        return ShardedCompactStats(0, 0, 0, 0, None)

    reader = MergeFileSplitRead(table.file_io, table.path, table.schema,
                                table.options)
    encoder = reader.key_encoder
    lanes_list, seq_list, kinds_list, tables = [], [], [], []
    n_input = 0
    for s in splits:
        runs_meta = assemble_runs(s.data_files)
        runs = []
        for run_files in runs_meta:
            for f in run_files:
                runs.append(read_kv_file(
                    reader.file_io, reader.path_factory, s.partition,
                    s.bucket, f, None, None, schema=table.schema,
                    schema_manager=table.schema_manager))
        t = pa.concat_tables(runs, promote_options="none")
        lanes, _ = encoder.encode_table(t, reader.key_cols)
        seq = np.asarray(t.column(SEQ_COL).combine_chunks()
                         .cast(pa.int64()))
        kinds = np.asarray(t.column(KIND_COL).combine_chunks()
                           .cast(pa.int8()))
        lanes_list.append(lanes)
        seq_list.append(seq)
        kinds_list.append(kinds)
        tables.append(t)
        n_input += t.num_rows

    lanes, seq_hi, seq_lo, invalid = pad_bucket_batches(lanes_list,
                                                        seq_list)
    n_pad = lanes.shape[1]
    kinds = np.zeros((lanes.shape[0], n_pad), dtype=np.int8)
    for i, k in enumerate(kinds_list):
        kinds[i, :len(k)] = k

    key = (mesh, lanes.shape[2], axis)
    kernel = _KERNEL_CACHE.get(key)
    if kernel is None:
        kernel = _KERNEL_CACHE[key] = _ShardedCompactKernel(
            mesh, lanes.shape[2], axis)
    perm, live, per_bucket, total_win, total_live = kernel(
        lanes, seq_hi, seq_lo, invalid, kinds)

    # host: take winners per bucket, roll output files, build the commit
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
    messages = []
    out_rows = 0
    for i, s in enumerate(splits):
        win_pos = np.flatnonzero(live[i])
        indices = perm[i][win_pos].astype(np.int64)
        merged = tables[i].take(pa.array(indices))
        out_rows += merged.num_rows
        after = writer.write(s.partition, s.bucket, merged,
                             level=max_level) if merged.num_rows else []
        messages.append(CommitMessage(
            s.partition, s.bucket, s.total_buckets,
            compact_before=list(s.data_files), compact_after=after))
    assert out_rows == total_live, (out_rows, total_live)

    commit = FileStoreCommit(table.file_io, table.path, table.schema,
                             table.options, branch=table.branch)
    sid = commit.commit(messages)
    return ShardedCompactStats(len(splits), n_input, out_rows,
                               total_win, sid)
