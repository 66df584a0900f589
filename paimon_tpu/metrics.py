"""Engine-agnostic metrics registry.

reference: paimon-core/.../metrics/ (MetricRegistry, Counter, Gauge,
Histogram) with groups CommitMetrics / ScanMetrics / CompactionMetrics
(operation/metrics/). System tables remain the queryable surface; this
registry is the programmatic one.
"""

from __future__ import annotations

import bisect
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

__all__ = ["Counter", "Gauge", "Histogram", "MetricGroup",
           "MetricRegistry", "global_registry",
           "COMPACTION_BUCKET_RETRIES", "COMPACTION_BUCKET_FALLBACKS",
           "COMPACTION_BUCKET_FAILURES", "FSCK_VIOLATIONS",
           "SCAN_FILE_CACHE_HITS", "SCAN_FILE_CACHE_MISSES",
           "SCAN_FOOTER_CACHE_HITS", "SCAN_FOOTER_CACHE_MISSES",
           "SCAN_RANGE_CACHE_HITS", "SCAN_RANGE_CACHE_MISSES",
           "SCAN_RANGE_CACHE_HIT_BYTES", "SCAN_PIPELINE_SPLITS",
           "SCAN_PIPELINE_BYTES", "SCAN_READ_RETRIES",
           "SCAN_DEVICE_DECODE_FILES", "SCAN_DEVICE_DECODE_FALLBACKS",
           "WRITE_FLUSHES", "WRITE_FLUSHED_BYTES", "WRITE_FLUSH_WAIT_MS",
           "WRITE_INFLIGHT_BYTES", "WRITE_RETRIES",
           "SCAN_SPLIT_MS", "SCAN_MERGE_MS",
           "WRITE_SORT_MS", "WRITE_FLUSH_TASK_MS",
           "IO_READ_MS", "IO_DECODE_MS", "IO_ENCODE_MS", "IO_UPLOAD_MS",
           "IO_ENCODE_ROWS", "IO_ENCODE_SPLIT_ROWS",
           "COMPACTION_WINDOW_MS", "COMPACTION_FALLBACK_MS",
           "COMMIT_CAS_MS", "COMMIT_MANIFEST_ENCODE_MS",
           "COMMIT_DURATION_MS", "COMPACTION_DURATION_MS",
           "COMPACTION_TABLE_MS", "COMPACTION_CONCURRENT_TASKS_PEAK",
           "COMPACTION_MESH_STEPS", "COMPACTION_MESH_PADDED_ROWS",
           "WRITE_ROUTE_MS", "WRITE_ROUTE_NOCOPY_ROWS", "WRITE_ROUTE_ROWS",
           "WRITE_BUILD_MS", "IO_STATS_MS",
           "MERGE_PREP_MS", "MERGE_DEVICE_MS", "MERGE_AGG_MS",
           "MERGE_SELECT_MS", "MERGE_GATHER_MS", "MERGE_GATHER_BYTES",
           "MERGE_RETURN_BYTES", "MERGE_PREP_PLANAR_ROWS", "SCAN_AGG_MS",
           "MERGE_WINNERS_MS", "MERGE_HOST_MS", "MERGE_MASK_MS",
           "MERGE_DEVICE_TRIPS", "MERGE_DEVICE_INFLIGHT_SUM",
           "MERGE_DEVICE_ROWS", "MERGE_TIEBREAK_MS", "MERGE_TIEBREAK_ROWS",
           "MERGE_TIEBREAK_RESORTED_ROWS",
           "WRITE_HASH_MS", "WRITE_HASH_ROWS", "WRITE_HASH_VECTOR_ROWS",
           "WRITE_TAKE_MS", "WRITE_DEFERRED_GATHER_ROWS",
           "SCAN_AGG_BELOW_ROWS",
           "SCAN_ROWS_IN", "SCAN_RAW_ROWS",
           "STREAM_EVENTS_INGESTED", "STREAM_CHECKPOINTS",
           "STREAM_CHECKPOINT_MS", "STREAM_LOOP_RESTARTS",
           "STREAM_FRESHNESS_MS", "STREAM_CHANGELOG_ROWS",
           "STREAM_COMPACTIONS", "STREAM_COMPACTIONS_PAUSED",
           "STREAM_SOURCE_BACKLOG",
           "SERVICE_REQUESTS", "SERVICE_REJECTED",
           "SERVICE_QUEUE_DEPTH", "SERVICE_INFLIGHT_BYTES",
           "SERVICE_TENANT_BYTES", "SERVICE_ADMISSION_WAIT_MS",
           "SERVICE_LOOKUP_MS", "SERVICE_SCAN_MS",
           "SERVICE_CHANGELOG_MS", "SERVICE_LOOKUP_KEYS",
           "SERVICE_LOOKUP_CPU_MS",
           "SERVICE_LOOP_LAG_MS", "SERVICE_CONNECTIONS",
           "SERVICE_DELTA_ROWS", "SERVICE_DELTA_BYTES",
           "SERVICE_DELTA_OVERFLOWS", "SERVICE_ROUTER_FORWARDED",
           "SERVICE_ROUTER_UPSTREAM_ERRORS",
           "SERVICE_ROUTER_RING_CHANGES",
           "SERVICE_SCAN_CACHE_HITS", "SERVICE_SCAN_CACHE_MISSES",
           "LOOKUP_BLOCK_CACHE_HITS", "LOOKUP_BLOCK_CACHE_MISSES",
           "LOOKUP_READER_BUILDS", "LOOKUP_READER_REUSES",
           "LOOKUP_FILES_PRUNED", "LOOKUP_SNAPSHOT_REFRESHES",
           "LOOKUP_DELTA_HITS", "LOOKUP_NATIVE_PROBES",
           "LOOKUP_NATIVE_FALLBACKS",
           "LOOKUP_INDEX_MS", "LOOKUP_PROBE_MS", "LOOKUP_GATHER_MS",
           "LOOKUP_CHANGELOG_MS", "LOOKUP_PROBE_KEYS", "LOOKUP_PROBE_HITS",
           "LOOKUP_INDEX_ROWS", "LOOKUP_INDEX_DEVICE_BYTES",
           "LOOKUP_LEVEL_ROWS_DECODED",
           "CACHE_DISK_HITS", "CACHE_DISK_MISSES",
           "CACHE_DISK_PROMOTIONS", "CACHE_DISK_DEMOTIONS",
           "CACHE_DISK_EVICTIONS", "CACHE_DISK_BYTES",
           "CACHE_DISK_STAGED_UPLOADS", "CACHE_DISK_STAGE_MS",
           "RESILIENCE_HEDGES_ISSUED", "RESILIENCE_HEDGES_WON",
           "RESILIENCE_HEDGES_ABANDONED", "RESILIENCE_BREAKER_STATE",
           "RESILIENCE_BREAKER_FAST_FAILS",
           "RESILIENCE_DEADLINE_EXCEEDED", "RESILIENCE_BROWNOUT_SHEDS",
           "RESILIENCE_BROWNOUT_LEVEL", "RESILIENCE_HEDGE_WAIT_MS",
           "MULTIHOST_COMMIT_CONFLICTS", "MULTIHOST_COMMIT_RETRIES",
           "MULTIHOST_OWNERSHIP_HANDOFFS", "MULTIHOST_BARRIER_WAIT_MS",
           "MULTIHOST_FOREIGN_ROWS",
           "MULTIHOST_OWNED_BUCKETS", "MULTIHOST_MAINTENANCE_TAKEOVERS",
           "MULTIHOST_LEASE_RENEWALS", "MULTIHOST_LEASE_EXPIRED",
           "PLAN_PLANS", "PLAN_MS", "PLAN_DELTA_APPLIES",
           "PLAN_MANIFESTS_READ", "PLAN_MANIFESTS_PRUNED",
           "PLAN_ENTRIES_DECODED", "PLAN_MANIFEST_COMPACTIONS",
           "FLEET_REJOINS", "FLEET_GENERATIONS",
           "FLEET_FSCK_INCREMENTAL_RUNS", "FLEET_FSCK_OBJECTS_CHECKED",
           "FLEET_FSCK_WATERMARK_AGE_MS",
           "SLO_AVAILABILITY_BURN_FAST", "SLO_AVAILABILITY_BURN_SLOW",
           "SLO_LATENCY_BURN_FAST", "SLO_LATENCY_BURN_SLOW",
           "SLO_ALERT", "SLO_GOOD_EVENTS", "SLO_BAD_EVENTS"]

# fault-tolerance counter names (one definition; producers in
# parallel/fault.py + mesh_engine.py, consumers in tests/dashboards):
#   bucket_retries   — transient per-bucket failures that were retried
#   bucket_fallbacks — buckets degraded to the single-chip path
#   bucket_failures  — buckets that exhausted the whole ladder (raised)
COMPACTION_BUCKET_RETRIES = "bucket_retries"
COMPACTION_BUCKET_FALLBACKS = "bucket_fallbacks"
COMPACTION_BUCKET_FAILURES = "bucket_failures"
FSCK_VIOLATIONS = "fsck_violations"

# read-side cache + pipeline counter names (scan metric group;
# producers in fs/caching.py + parallel/scan_pipeline.py + core read
# paths, consumers in scan_bench.py / tests / dashboards)
SCAN_FILE_CACHE_HITS = "file_cache_hits"
SCAN_FILE_CACHE_MISSES = "file_cache_misses"
SCAN_FOOTER_CACHE_HITS = "footer_cache_hits"
SCAN_FOOTER_CACHE_MISSES = "footer_cache_misses"
SCAN_RANGE_CACHE_HITS = "range_cache_hits"
SCAN_RANGE_CACHE_MISSES = "range_cache_misses"
SCAN_RANGE_CACHE_HIT_BYTES = "range_cache_hit_bytes"
SCAN_PIPELINE_SPLITS = "pipeline_splits"          # splits prefetched
SCAN_PIPELINE_BYTES = "pipeline_bytes"            # est. bytes admitted
SCAN_READ_RETRIES = "read_retries"                # transient IO retries
SCAN_DEVICE_DECODE_FILES = "device_decode_files"  # raw-page device reads
SCAN_DEVICE_DECODE_FALLBACKS = "device_decode_fallbacks"  # host fallbacks

# write-pipeline counter names (write metric group; producers in
# parallel/write_pipeline.py, consumers in write_bench.py / tests /
# dashboards)
WRITE_FLUSHES = "flushes"                   # flush tasks admitted
WRITE_FLUSHED_BYTES = "flushed_bytes"       # est. buffered bytes flushed
WRITE_FLUSH_WAIT_MS = "flush_wait_ms"       # producer ms blocked on the
                                            # in-flight byte budget
WRITE_INFLIGHT_BYTES = "inflight_bytes"     # gauge: bytes in flight now
WRITE_RETRIES = "write_retries"             # transient flush retries

# per-stage latency HISTOGRAM names (obs plane: every obs.trace span
# that names a (group, metric) lands its duration here, so the trace
# timeline and the registry snapshot can never disagree; producers are
# the span call sites in parallel/{scan,write}_pipeline.py,
# core/{read,write,commit}.py, parallel/mesh_engine.py, format/format.py)
SCAN_SPLIT_MS = "split_ms"                  # scan: whole read_split
SCAN_MERGE_MS = "merge_ms"                  # scan: merge kernel
SCAN_AGG_MS = "agg_ms"                      # scan: pushed aggregate, host side
SCAN_AGG_BELOW_ROWS = "agg_below_rows"      # counter: rows aggregated below
#                                             the merge (ops/scan_agg.py)
SCAN_ROWS_IN = "rows_in"                    # counter: rows the split reads
#                                             of pk tables decoded
SCAN_RAW_ROWS = "raw_rows"                  # counter: those of them read
#                                             with no merge (a split of
#                                             one sorted run, `_read_raw`)
WRITE_SORT_MS = "sort_ms"                   # write: buffer sort/dedup
WRITE_FLUSH_TASK_MS = "flush_task_ms"       # write: whole flush task
IO_READ_MS = "read_ms"                      # io: store -> bytes
IO_DECODE_MS = "decode_ms"                  # io: bytes -> Arrow
IO_ENCODE_MS = "encode_ms"                  # io: Arrow -> bytes
IO_UPLOAD_MS = "upload_ms"                  # io: bytes -> store
IO_ENCODE_ROWS = "encode_rows"              # counter: rows of the Parquet
                                            # files `write` encoded
IO_ENCODE_SPLIT_ROWS = "encode_split_rows"  # counter: those whose file was
                                            # encoded in several pieces
IO_STATS_MS = "stats_ms"                    # io: a rolled file's column
                                            # statistics, first / last key
COMPACTION_WINDOW_MS = "window_ms"          # compaction: device window
COMPACTION_FALLBACK_MS = "fallback_ms"      # compaction: 1-chip rescue
COMMIT_CAS_MS = "cas_ms"                    # commit: one CAS publish
COMMIT_MANIFEST_ENCODE_MS = "manifest_encode_ms"
COMMIT_DURATION_MS = "duration_ms"          # commit: one whole commit,
                                            # published or given up
COMPACTION_DURATION_MS = "duration_ms"      # compaction: one whole task
COMPACTION_TABLE_MS = "table_ms"            # compaction: compact_table's
                                            # group phase, wall (tasks
                                            # side by side inside it)
COMPACTION_CONCURRENT_TASKS_PEAK = "concurrent_tasks_peak"  # gauge: most
                                            # tasks in flight, last call
COMPACTION_MESH_STEPS = "mesh_steps"        # counter: mesh engine steps run
COMPACTION_MESH_PADDED_ROWS = "mesh_padded_rows"  # counter: lanes x n_pad
                                            # of those steps: every row slot
                                            # the chips sorted, padding and
                                            # drained lanes included
WRITE_ROUTE_MS = "route_ms"                 # write: bucket hash, group-by,
                                            # hand-over to the bucket writers
WRITE_ROUTE_NOCOPY_ROWS = "route_nocopy_rows"   # counter: rows of batches
                                            # that were one group, handed on
                                            # without a take
WRITE_ROUTE_ROWS = "route_rows"             # counter: rows the route handled
WRITE_BUILD_MS = "build_ms"                 # write: a flush's KV-shaped table
WRITE_HASH_MS = "hash_ms"                   # write: the route's bucket hash
WRITE_HASH_ROWS = "hash_rows"               # counter: rows the route hashed
WRITE_HASH_VECTOR_ROWS = "hash_vector_rows"  # counter: those hashed by the
                                            # vectorised path, not a row
                                            # at a time
WRITE_TAKE_MS = "take_ms"                   # write: the route's takes of the
                                            # key columns and kinds, and a
                                            # selection taken before its flush
WRITE_DEFERRED_GATHER_ROWS = "deferred_gather_rows"  # counter: rows a
                                            # flush gathered straight from
                                            # the caller's batch
# merge metric group: the stages of one sorted-run merge, whoever
# called it (scan split, flush sort, compaction window) — producers
# in ops/merge.py, ops/agg.py and compact/manager.py
MERGE_PREP_MS = "prep_ms"                   # concat, lane encode, pad
MERGE_DEVICE_MS = "device_ms"               # first upload -> result on host
MERGE_AGG_MS = "agg_ms"                     # aggregation epilogue, whole
MERGE_SELECT_MS = "select_ms"               # its per-segment row selections
MERGE_GATHER_MS = "gather_ms"               # Arrow take in merge order
MERGE_GATHER_BYTES = "gather_bytes"         # counter: buffer bytes taken
MERGE_RETURN_BYTES = "return_bytes"         # counter: bytes a merge handed back
MERGE_PREP_PLANAR_ROWS = "prep_planar_rows"  # counter: rows whose device
                                            # operands went straight from
                                            # the Arrow chunks to planes
MERGE_WINNERS_MS = "winners_ms"             # (perm, winner) words -> row
                                            # indices, on every route
MERGE_HOST_MS = "host_ms"                   # a merge sorted on the host
MERGE_MASK_MS = "mask_ms"                   # aggregation epilogue's own numpy
MERGE_DEVICE_TRIPS = "device_trips"         # counter: round trips to the chip
MERGE_DEVICE_INFLIGHT_SUM = "device_inflight_sum"   # counter: round trips
                                            # open as each one opened, itself
                                            # included; / device_trips = how
                                            # many shared the link
MERGE_DEVICE_ROWS = "device_rows"           # counter: real rows of the merge
                                            # round trips (`merge.device`)
MERGE_TIEBREAK_MS = "tiebreak_ms"           # keys cut to their lane prefix
                                            # put in exact order
MERGE_TIEBREAK_ROWS = "tiebreak_rows"       # counter: rows compared by
                                            # their full key bytes
MERGE_TIEBREAK_RESORTED_ROWS = "tiebreak_resorted_rows"  # counter: rows of
                                            # prefix groups holding several
                                            # keys, sorted again

# streaming-daemon counter/gauge/histogram names (stream metric group;
# producer is service/stream_daemon.py, consumers tests/soak_harness.py
# + dashboards).  freshness_ms is END-TO-END: event pulled from the CDC
# source -> its checkpoint's rows visible to a changelog scan.
STREAM_EVENTS_INGESTED = "events_ingested"    # CDC events written
STREAM_CHECKPOINTS = "checkpoints"            # offset commits that landed
STREAM_CHECKPOINT_MS = "checkpoint_ms"        # one checkpoint commit
STREAM_LOOP_RESTARTS = "loop_restarts"        # supervised loop restarts
STREAM_FRESHNESS_MS = "freshness_ms"          # event -> changelog-visible
STREAM_CHANGELOG_ROWS = "changelog_rows_served"
STREAM_COMPACTIONS = "compactions"            # triggered compaction runs
STREAM_COMPACTIONS_PAUSED = "compactions_paused"  # skipped: ingest pressure
STREAM_SOURCE_BACKLOG = "source_backlog"      # gauge: unpulled events

# query-serving-plane counter/gauge/histogram names (service metric
# group; producers are service/admission.py + service/query_service.py,
# consumers benchmarks/serve_bench.py + tests + dashboards).  Per-tenant
# in-flight bytes render as one gauge per tenant keyed like a table:
# group("service", tenant) -> prometheus label table="<tenant>".
SERVICE_REQUESTS = "requests"                 # admitted requests
SERVICE_REJECTED = "rejected"                 # 429s: queue full/timeout
SERVICE_QUEUE_DEPTH = "queue_depth"           # gauge: waiters right now
SERVICE_INFLIGHT_BYTES = "inflight_bytes"     # gauge: admitted bytes now
SERVICE_TENANT_BYTES = "tenant_inflight_bytes"    # gauge, per tenant
SERVICE_ADMISSION_WAIT_MS = "admission_wait_ms"   # queued -> admitted
SERVICE_LOOKUP_MS = "lookup_ms"               # whole /lookup request
SERVICE_SCAN_MS = "scan_ms"                   # whole /scan request
SERVICE_CHANGELOG_MS = "changelog_ms"         # whole /changelog poll
SERVICE_LOOKUP_KEYS = "lookup_keys"           # point-get keys served
# per-key handler CPU (thread_time around the /lookup body, divided
# by the batch's key count): the bench-honesty meter behind the
# "handler CPU per lookup" headline — wall latency can hide in IO,
# this cannot
SERVICE_LOOKUP_CPU_MS = "lookup_cpu_per_key_ms"

# event-loop serving engine + hot delta tier + replica router names
# (same service metric group; producers are service/async_server.py,
# service/delta.py and service/router.py).  loop_lag_ms is THE health
# canary of the event-loop engine: how long a finished response waited
# before the loop picked it up — a starved loop is late at accepting,
# reading and writing all at once.  delta_rows/delta_bytes gauge the
# in-memory delta tier (unflushed serving-writer rows merged into
# point lookups); delta_overflow counts writes that pushed the tier
# past service.delta.max-bytes (the "commit now" signal).
SERVICE_LOOP_LAG_MS = "loop_lag_ms"           # response ready -> flushed
SERVICE_CONNECTIONS = "connections"           # gauge: open sockets now
SERVICE_DELTA_ROWS = "delta_rows"             # gauge: delta-tier rows
SERVICE_DELTA_BYTES = "delta_bytes"           # gauge: delta-tier bytes
SERVICE_DELTA_OVERFLOWS = "delta_overflow"    # writes past max-bytes
SERVICE_ROUTER_FORWARDED = "router_forwarded"     # proxied requests
SERVICE_ROUTER_UPSTREAM_ERRORS = "router_upstream_errors"
SERVICE_ROUTER_RING_CHANGES = "router_ring_changes"   # join/leave/
# suspend/re-admit events — a churning ring is a churning SST cache
SERVICE_SCAN_CACHE_HITS = "scan_cache_hits"       # snapshot-keyed
SERVICE_SCAN_CACHE_MISSES = "scan_cache_misses"   # result cache

# point-lookup-plane counter names (lookup metric group; producers in
# lookup/sst.py + lookup/local_query.py).  block_cache_* watch the
# pinned SST block cache; files_pruned counts data files skipped by
# manifest key-range + bloom stats BEFORE any IO.
LOOKUP_BLOCK_CACHE_HITS = "block_cache_hits"
LOOKUP_BLOCK_CACHE_MISSES = "block_cache_misses"
LOOKUP_READER_BUILDS = "reader_builds"        # SSTs built (file reads)
LOOKUP_READER_REUSES = "reader_reuses"        # SSTs served warm
LOOKUP_FILES_PRUNED = "files_pruned"          # skipped by stats, no IO
LOOKUP_SNAPSHOT_REFRESHES = "snapshot_refreshes"  # plan reloads
LOOKUP_DELTA_HITS = "delta_hits"              # keys answered by delta
# native_probes counts SST probe batches resolved by the C path
# (native/probe.c sst_probe_batch); native_fallbacks counts batches
# that WANTED the native path but degraded to numpy (no compiler,
# PAIMON_DISABLE_NATIVE, or a stale .so predating the probe symbols —
# a nonzero steady-state value is the "serving the slow path" alarm)
LOOKUP_NATIVE_PROBES = "native_probes"
LOOKUP_NATIVE_FALLBACKS = "native_fallbacks"
# the lookup changelog producer's levels index (lookup/levels_index.py,
# compact/manager.py): build and update of the resident index, the
# device probe, the before-images' gather, the changelog file's encode
# and upload; probe_keys / probe_hits count probed keys and those a run
# above level 0 holds; index_rows / index_device_bytes count the rows
# and lane bytes put on the device; level_rows_decoded counts rows of
# level files decoded for the index (0 once it is built)
LOOKUP_INDEX_MS = "index_ms"
LOOKUP_PROBE_MS = "probe_ms"
LOOKUP_GATHER_MS = "gather_ms"
LOOKUP_CHANGELOG_MS = "changelog_ms"
LOOKUP_PROBE_KEYS = "probe_keys"
LOOKUP_PROBE_HITS = "probe_hits"
LOOKUP_INDEX_ROWS = "index_rows"
LOOKUP_INDEX_DEVICE_BYTES = "index_device_bytes"
LOOKUP_LEVEL_ROWS_DECODED = "level_rows_decoded"

# tiered host-SSD storage counter/gauge/histogram names (cache_disk
# metric group; producers in fs/caching.py DiskCacheTier + the
# UploadStager in parallel/write_pipeline.py, consumers
# benchmarks/tier_bench.py + tests + dashboards).  promotions are
# memory->disk writes earned by repeated hits, demotions are entries
# pushed to disk by memory-LRU pressure (or too large for memory),
# evictions are disk entries dropped by the max-bytes bound OR failed
# validation (wipe/truncate/bit-flip degrades to the object store).
CACHE_DISK_HITS = "hits"                      # served from SSD
CACHE_DISK_MISSES = "misses"                  # disk tier consulted, absent
CACHE_DISK_PROMOTIONS = "promotions"          # hit-earned mem->disk writes
CACHE_DISK_DEMOTIONS = "demotions"            # pressure-driven mem->disk
CACHE_DISK_EVICTIONS = "evictions"            # bound/validation drops
CACHE_DISK_BYTES = "bytes"                    # gauge: on-disk bytes now
CACHE_DISK_STAGED_UPLOADS = "staged_uploads"  # uploads acked from stage
CACHE_DISK_STAGE_MS = "stage_ms"              # one encode->staged-fsync

# tail-tolerance counter/gauge/histogram names (resilience metric
# group; producers in fs/resilience.py + utils/deadline.py +
# service/brownout.py + service/admission.py, consumers
# benchmarks/chaos_bench.py + tests + dashboards).  The breaker state
# gauge renders one series per backend: group("resilience", backend
# name) -> prometheus label table="<backend>"; 0=closed, 1=half-open,
# 2=open.  brownout_level is the serving plane's degradation rung
# (0 normal, 1 degrade hedging/prefetch, 2 shed low priority).
RESILIENCE_HEDGES_ISSUED = "hedges_issued"      # hedge requests sent
RESILIENCE_HEDGES_WON = "hedges_won"            # hedge beat the primary
RESILIENCE_HEDGES_ABANDONED = "hedges_abandoned"  # loser left running
RESILIENCE_BREAKER_STATE = "breaker_state"      # gauge, per backend
RESILIENCE_BREAKER_FAST_FAILS = "breaker_fast_fails"  # open-circuit rejects
RESILIENCE_DEADLINE_EXCEEDED = "deadline_exceeded"    # tripped scopes
RESILIENCE_BROWNOUT_SHEDS = "brownout_sheds"    # requests shed browned-out
RESILIENCE_BROWNOUT_LEVEL = "brownout_level"    # gauge: current rung
RESILIENCE_HEDGE_WAIT_MS = "hedge_wait_ms"      # delay before the hedge

# multi-host write-plane counter/histogram names (multihost metric
# group; producers in parallel/multihost.py + parallel/distributed.py,
# consumers benchmarks/multihost_bench.py + tests + dashboards).
# commit_conflicts counts snapshot-CAS losses observed by distributed
# commits (each is one peer's concurrent publish); commit_retries
# counts distributed commits that needed >1 CAS attempt before
# winning; ownership_handoffs counts (partition,bucket) owners that
# moved between ownership-map versions (bucket rescale); barrier_wait_ms
# is the per-process wall time spent inside cross-host barriers
# (sync_global_devices) — the direct cost of global agreement.
MULTIHOST_COMMIT_CONFLICTS = "commit_conflicts"
MULTIHOST_COMMIT_RETRIES = "commit_retries"
MULTIHOST_OWNERSHIP_HANDOFFS = "ownership_handoffs"
MULTIHOST_BARRIER_WAIT_MS = "barrier_wait_ms"
MULTIHOST_FOREIGN_ROWS = "foreign_rows_routed"  # rows exchanged to owners

# multi-host MAINTENANCE-plane names (same multihost group; producer is
# parallel/maintenance_plane.py, consumers the multi-host soak tests +
# dashboards).  owned_buckets is a per-process gauge of the
# (partition,bucket) groups this process currently owns (it JUMPS on a
# takeover — the visible re-lease of a dead peer's buckets);
# lease_renewals counts this process's successful lease stamps
# (commit-carried or heartbeat); lease_expired counts peers this
# process's failure detector declared dead; maintenance_takeovers
# counts completed adoptions (ownership version bumped with the dead
# set recorded — the acceptance signal of host-death tolerance).
MULTIHOST_OWNED_BUCKETS = "owned_buckets"
MULTIHOST_MAINTENANCE_TAKEOVERS = "maintenance_takeovers"
MULTIHOST_LEASE_RENEWALS = "lease_renewals"
MULTIHOST_LEASE_EXPIRED = "lease_expired"

# incremental-metadata-plane counter/histogram names (plan metric
# group; producers in core/scan.py + maintenance/manifest_compact.py,
# consumers benchmarks/plan_bench.py + tests + dashboards).
# plan_delta_applies counts plans served by advancing a cached plan
# with only the new snapshots' delta manifests (the steady-state
# streaming re-plan path); manifests_pruned counts whole manifest
# files skipped by the columnar stats sidecar BEFORE any fetch, and
# entries_decoded is the proof meter — it must not move for pruned
# manifests.  The whole group is pre-allocated at FileStoreScan
# construction so the Prometheus endpoint always renders the series.
PLAN_PLANS = "plans"                          # scan plans produced
PLAN_MS = "plan_ms"                           # one whole plan() call
PLAN_DELTA_APPLIES = "plan_delta_applies"     # cache-advanced plans
PLAN_MANIFESTS_READ = "manifests_read"        # manifest files fetched
PLAN_MANIFESTS_PRUNED = "manifests_pruned"    # skipped before fetch
PLAN_ENTRIES_DECODED = "entries_decoded"      # manifest entries decoded
PLAN_MANIFEST_COMPACTIONS = "manifest_compactions"  # full rewrites

# self-healing fleet-plane counter/gauge names (fleet metric group;
# producers in parallel/maintenance_plane.py + maintenance/fsck.py +
# maintenance/orphan.py, consumers the kill-two-then-rejoin soak tests
# + dashboards).  rejoins counts hosts READMITTED into the ownership
# map by the elected granter (the acceptance signal of operator-free
# healing: two victims rejoining render rejoins 2); generations is a
# gauge of the current ownership-map version (every takeover, rejoin
# and rescale advances it); fsck_incremental_runs counts fsck/orphan
# sweeps that rode the watermark delta walk instead of the full chain;
# fsck_objects_checked counts objects (snapshots, manifest lists,
# manifests, data files) a sweep actually verified — the O(delta)
# proof meter, mirroring plan entries_decoded; fsck_watermark_age_ms
# is a gauge of how stale the last clean-sweep watermark is (an alert
# on this catches a fleet whose verification plane silently stopped).
FLEET_REJOINS = "rejoins"
FLEET_GENERATIONS = "generations"
FLEET_FSCK_INCREMENTAL_RUNS = "fsck_incremental_runs"
FLEET_FSCK_OBJECTS_CHECKED = "fsck_objects_checked"
FLEET_FSCK_WATERMARK_AGE_MS = "fsck_watermark_age_ms"

# SLO burn-rate plane gauge/counter names (slo metric group; producer
# obs/slo.py's SloEvaluator — evaluated per replica over the serving
# histogram windows, consumers GET /slo, the router fleet aggregate,
# `paimon fleet status` and the Prometheus `paimon_slo_*` series).
# burn = (observed bad-event rate) / (error budget); >1 means the
# budget is being spent faster than the objective allows, and the
# alert gauge goes 1 only when BOTH the fast and slow windows burn hot
# (Google SRE multi-window multi-burn-rate alerting: the slow window
# kills flapping, the fast window kills slow detection).
SLO_AVAILABILITY_BURN_FAST = "availability_burn_fast"
SLO_AVAILABILITY_BURN_SLOW = "availability_burn_slow"
SLO_LATENCY_BURN_FAST = "latency_burn_fast"
SLO_LATENCY_BURN_SLOW = "latency_burn_slow"
SLO_ALERT = "alert"
SLO_GOOD_EVENTS = "good_events"
SLO_BAD_EVENTS = "bad_events"

# Fixed cumulative-bucket bounds (milliseconds) for the Prometheus
# `le`-bucket exposition of every latency histogram.  FIXED ON PURPOSE:
# external Prometheus can only aggregate `_bucket` series across
# replicas (histogram_quantile over a sum()) when every replica exports
# the identical bound set.
HISTOGRAM_BUCKET_BOUNDS_MS = (
    1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
    1000.0, 2500.0, 5000.0, 10000.0)


class Counter:
    def __init__(self):
        self._v = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1):
        with self._lock:
            self._v += n

    @property
    def count(self) -> int:
        with self._lock:
            return self._v


class Gauge:
    def __init__(self, fn: Optional[Callable[[], float]] = None):
        self._fn = fn
        self._v = 0.0

    def set(self, v: float):
        self._v = v

    @property
    def value(self) -> float:
        return self._fn() if self._fn is not None else self._v


class Histogram:
    """Sliding-window histogram (reference DescriptiveStatisticsHistogram
    with window size 100).

    Thread-safe on BOTH sides: the window is a deque(maxlen=window), so
    `update` is O(1) (the old list.pop(0) was O(n)), and every read
    takes the lock — `sum()`/`max()` over a deque that another thread
    is appending to raises "deque mutated during iteration", and even
    the old list version could return torn means.

    Besides the window, a cumulative `total_count`/`total_sum` pair is
    tracked: Prometheus summary `_count`/`_sum` must be MONOTONIC for
    rate()/increase() to work — window-derived values would cap at the
    window size and fluctuate as samples rotate out.
    """

    def __init__(self, window: int = 100):
        self.window = window
        self._values: deque = deque(maxlen=max(1, int(window)))
        self._total_count = 0
        self._total_sum = 0.0
        # cumulative per-bound counts over the FIXED shared bound set
        # (HISTOGRAM_BUCKET_BOUNDS_MS) — the +Inf bucket is
        # total_count.  Stored non-cumulative per slot; bucket_counts()
        # emits the running `le` form Prometheus wants.
        self._bucket_slots = [0] * len(HISTOGRAM_BUCKET_BOUNDS_MS)
        self._lock = threading.Lock()

    def update(self, v: float):
        i = bisect.bisect_left(HISTOGRAM_BUCKET_BOUNDS_MS, v)
        with self._lock:
            self._values.append(v)
            self._total_count += 1
            self._total_sum += v
            if i < len(self._bucket_slots):
                self._bucket_slots[i] += 1

    def bucket_counts(self) -> List[tuple]:
        """Cumulative ``(le_bound_ms, count)`` pairs, monotonic in both
        coordinates, ending with ``(inf, total_count)``."""
        with self._lock:
            slots = list(self._bucket_slots)
            total = self._total_count
        out, run = [], 0
        for bound, n in zip(HISTOGRAM_BUCKET_BOUNDS_MS, slots):
            run += n
            out.append((bound, run))
        out.append((float("inf"), total))
        return out

    @property
    def total_count(self) -> int:
        """Cumulative updates ever (monotonic; window-independent)."""
        with self._lock:
            return self._total_count

    @property
    def total_sum(self) -> float:
        """Cumulative sum of every update ever (monotonic)."""
        with self._lock:
            return self._total_sum

    @property
    def count(self) -> int:
        with self._lock:
            return len(self._values)

    def percentile(self, p: float) -> float:
        with self._lock:
            if not self._values:
                return 0.0
            vals = sorted(self._values)
            i = min(len(vals) - 1, int(p / 100 * len(vals)))
            return vals[i]

    def window_values(self) -> List[float]:
        """The trailing sample window as a list (fleet aggregation:
        pooling several instances' windows gives a TRUE pooled
        percentile, which no combination of per-instance percentiles
        can)."""
        with self._lock:
            return list(self._values)

    @property
    def mean(self) -> float:
        with self._lock:
            if not self._values:
                return 0.0
            return sum(self._values) / len(self._values)

    @property
    def max(self) -> float:
        with self._lock:
            return max(self._values) if self._values else 0.0


class MetricGroup:
    def __init__(self, name: str):
        self.name = name
        self.metrics: Dict[str, object] = {}
        self._lock = threading.Lock()

    def _get(self, name: str, kind: type, factory: Callable):
        """Lazy allocation (the old `setdefault(name, Kind())` built a
        throwaway metric on every hot-path call once the name existed)
        + kind safety (reusing a name across kinds used to silently
        return the wrong type; now it raises)."""
        with self._lock:
            m = self.metrics.get(name)
            if m is None:
                m = factory()
                self.metrics[name] = m
            elif not isinstance(m, kind):
                raise TypeError(
                    f"metric {name!r} in group {self.name!r} is a "
                    f"{type(m).__name__}, not a {kind.__name__}")
            return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter, Counter)

    def gauge(self, name: str,
              fn: Optional[Callable[[], float]] = None) -> Gauge:
        return self._get(name, Gauge, lambda: Gauge(fn))

    def histogram(self, name: str, window: int = 100) -> Histogram:
        return self._get(name, Histogram, lambda: Histogram(window))

    def timer(self, histogram_name: str):
        """Context manager recording elapsed millis into a histogram."""
        h = self.histogram(histogram_name)

        class _Timer:
            def __enter__(self_t):
                self_t.t0 = time.perf_counter()
                return self_t

            def __exit__(self_t, *exc):
                h.update((time.perf_counter() - self_t.t0) * 1000)
                return False

        return _Timer()


class MetricRegistry:
    """reference metrics/MetricRegistry.java: groups keyed by
    (group_type, table)."""

    def __init__(self):
        self._groups: Dict[str, MetricGroup] = {}
        self._lock = threading.Lock()

    def group(self, group_type: str, table: str = "") -> MetricGroup:
        key = f"{group_type}:{table}" if table else group_type
        with self._lock:
            return self._groups.setdefault(key, MetricGroup(key))

    def commit_metrics(self, table: str = "") -> MetricGroup:
        return self.group("commit", table)

    def scan_metrics(self, table: str = "") -> MetricGroup:
        return self.group("scan", table)

    def compaction_metrics(self, table: str = "") -> MetricGroup:
        return self.group("compaction", table)

    def write_metrics(self, table: str = "") -> MetricGroup:
        """Pipelined write/ingest plane (ours)."""
        return self.group("write", table)

    def maintenance_metrics(self, table: str = "") -> MetricGroup:
        """Expire / orphan-clean / fsck plane (ours)."""
        return self.group("maintenance", table)

    def stream_metrics(self, table: str = "") -> MetricGroup:
        """Streaming-daemon plane (ours; service/stream_daemon.py)."""
        return self.group("stream", table)

    def service_metrics(self, table: str = "") -> MetricGroup:
        """Query-serving plane (ours; service/query_service.py +
        service/admission.py).  `table` doubles as the tenant id for
        per-tenant gauges."""
        return self.group("service", table)

    def lookup_metrics(self, table: str = "") -> MetricGroup:
        """Point-lookup plane (ours; lookup/)."""
        return self.group("lookup", table)

    def cache_disk_metrics(self, table: str = "") -> MetricGroup:
        """Tiered host-SSD storage plane (ours; fs/caching.py disk
        tier + the write path's staged uploads)."""
        return self.group("cache_disk", table)

    def resilience_metrics(self, table: str = "") -> MetricGroup:
        """Tail-tolerance plane (ours; fs/resilience.py hedges +
        breakers, utils/deadline.py, service/brownout.py).  `table`
        doubles as the backend name for per-backend breaker gauges."""
        return self.group("resilience", table)

    def plan_metrics(self, table: str = "") -> MetricGroup:
        """Incremental metadata plane (ours; core/scan.py delta-apply
        plan cache + vectorized manifest pruning +
        maintenance/manifest_compact.py)."""
        return self.group("plan", table)

    def multihost_metrics(self, table: str = "") -> MetricGroup:
        """Multi-host write plane (ours; parallel/multihost.py
        barriers + parallel/distributed.py sharded-ownership writers
        and commit arbitration)."""
        return self.group("multihost", table)

    def fleet_metrics(self, table: str = "") -> MetricGroup:
        """Self-healing fleet plane (ours; coordinated rejoin in
        parallel/maintenance_plane.py + incremental fsck/orphan
        sweeps in maintenance/)."""
        return self.group("fleet", table)

    def slo_metrics(self, table: str = "") -> MetricGroup:
        """SLO burn-rate plane (ours; obs/slo.py SloEvaluator —
        pre-allocated so the `paimon_slo_*` series exist from the
        first scrape, before any request has been judged)."""
        return self.group("slo", table)

    def snapshot_rows(self) -> List[Dict[str, object]]:
        """Flat typed rows — THE single serialization point behind
        every observability surface (`$metrics` system table,
        Prometheus exposition, bench `metrics_snapshot` blocks, the
        CLI, and `snapshot()` itself):

            {"group", "table", "metric", "kind", "value",
             + for histograms: "count", "mean", "p95", "max"}

        `value` is the counter count, the gauge value, or the
        histogram mean.
        """
        with self._lock:
            groups = list(self._groups.items())
        rows: List[Dict[str, object]] = []
        for gkey, group in groups:
            gtype, _, gtable = gkey.partition(":")
            with group._lock:
                metrics = list(group.metrics.items())
            for mname, m in metrics:
                base = {"group": gtype, "table": gtable, "metric": mname}
                if isinstance(m, Counter):
                    rows.append({**base, "kind": "counter",
                                 "value": m.count})
                elif isinstance(m, Gauge):
                    rows.append({**base, "kind": "gauge",
                                 "value": m.value})
                elif isinstance(m, Histogram):
                    mean = m.mean
                    rows.append({**base, "kind": "histogram",
                                 "value": mean, "count": m.count,
                                 "mean": mean,
                                 "p95": m.percentile(95), "max": m.max,
                                 "total_count": m.total_count,
                                 "total_sum": m.total_sum,
                                 "buckets": m.bucket_counts()})
        return rows

    def snapshot(self) -> Dict[str, Dict[str, object]]:
        """{group: {metric: value}} for reporting (histograms render as
        {count, mean, p95, max} dicts).  Built from snapshot_rows so
        every surface serializes identically."""
        out: Dict[str, Dict[str, object]] = {}
        for r in self.snapshot_rows():
            gkey = f"{r['group']}:{r['table']}" if r["table"] \
                else r["group"]
            d = out.setdefault(gkey, {})
            if r["kind"] == "histogram":
                d[r["metric"]] = {"count": r["count"], "mean": r["mean"],
                                  "p95": r["p95"], "max": r["max"]}
            else:
                d[r["metric"]] = r["value"]
        return out


_GLOBAL = MetricRegistry()


def global_registry() -> MetricRegistry:
    return _GLOBAL


class CompactTimer:
    """Sliding-window busy-time tracker: how many milliseconds of the
    last `window_ms` were spent compacting (reference
    compact/CompactTimer.java — O(1) amortized interval bookkeeping;
    the numbers feed write-stall decisions and busy gauges)."""

    def __init__(self, window_ms: int = 60_000, clock=None):
        import threading as _threading
        import time as _time
        self.window_ms = window_ms
        self._clock = clock or (lambda: int(_time.time() * 1000))
        self._intervals: list = []      # [start, end or None]
        self._depth = 0                 # overlapping tasks share one
        self._lock = _threading.Lock()  # interval (thread-safe like
                                        # the reference @ThreadSafe)

    @property
    def _active(self) -> bool:
        return self._depth > 0

    def start(self, now: Optional[int] = None):
        now = self._clock() if now is None else now
        with self._lock:
            self._trim(now)
            if self._depth == 0:
                self._intervals.append([now, None])
            self._depth += 1

    def stop(self, now: Optional[int] = None):
        now = self._clock() if now is None else now
        with self._lock:
            if self._depth > 0:
                self._depth -= 1
                if self._depth == 0:
                    self._intervals[-1][1] = now

    def _trim(self, now: int):
        horizon = now - self.window_ms
        self._intervals = [
            iv for iv in self._intervals
            if iv[1] is None or iv[1] > horizon]

    def busy_millis(self, now: Optional[int] = None) -> int:
        """Compaction-busy milliseconds within the trailing window."""
        now = self._clock() if now is None else now
        horizon = now - self.window_ms
        with self._lock:
            self._trim(now)
            total = 0
            for start, end in self._intervals:
                e = now if end is None else min(end, now)
                s = max(start, horizon)
                if e > s:
                    total += e - s
            return total

    def busy_ratio(self, now: Optional[int] = None) -> float:
        return self.busy_millis(now) / self.window_ms
