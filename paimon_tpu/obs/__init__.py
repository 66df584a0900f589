"""Unified observability plane.

One span-tracing layer (`obs/trace.py`) instruments every concurrent
plane — scan pipeline, write pipeline, mesh compaction, fault ladders,
commit — and one serialization point (`MetricRegistry.snapshot_rows`)
feeds every surface.  A span has two listeners: the bounded ring
(`enable_tracing()`) and any open JAX profiler session, in which every
span is also the host annotation `paimon.<span name>` — `paimon.scan.split`,
`paimon.merge.device`, `paimon.compact.window`, `paimon.write.route`,
`paimon.wait`... — on the device trace's clock (`--trace 1` of the
benchmark, or `jax.profiler.start_trace` around a table operation).
The surfaces:

* Chrome trace-event JSON export (`obs/export.py`, opens in Perfetto);
* fleet-wide merged traces (`obs/merge.py`): per-process spools under
  `trace.export.dir` stitched into one Perfetto file with flow arrows
  across every serving hop and store-carried context boundary;
* the black-box flight recorder (`obs/flight.py`): an always-on ring
  of operational events dumped on crash/SIGTERM and on demand;
* the SLO burn-rate plane (`obs/slo.py`): declarative availability +
  latency objectives served at /slo and aggregated on the router;
* `$metrics` / `$traces` system tables (`table/system.py`);
* Prometheus text exposition (`GET /metrics` on the query service);
* CLI: `paimon table metrics`, `paimon table debug-bundle`,
  `paimon fleet trace --merge`, and `--trace out.json`.
"""

from paimon_tpu.obs.trace import (  # noqa: F401
    Span, TraceCollector, carry, collector, current_context_token,
    current_trace_id, disable_tracing, enable_tracing, inject_headers,
    metrics_enabled, new_trace_id, process_tag, profiler_listening,
    server_span,
    set_export_dir, set_metrics_enabled, set_replica_id, span,
    spool_flush, sync_from_options, take_spans, tracing_enabled,
)
from paimon_tpu.obs.export import (  # noqa: F401
    export_chrome_trace, render_prometheus, to_chrome_trace,
)
from paimon_tpu.obs.merge import (  # noqa: F401
    export_merged, merge_spools, read_spools,
)

__all__ = [
    "Span", "TraceCollector", "carry", "collector",
    "current_context_token",
    "current_trace_id", "disable_tracing", "enable_tracing",
    "export_chrome_trace", "export_merged", "inject_headers",
    "merge_spools", "metrics_enabled", "new_trace_id", "process_tag",
    "profiler_listening",
    "read_spools", "render_prometheus", "server_span",
    "set_export_dir", "set_metrics_enabled", "set_replica_id", "span",
    "spool_flush", "sync_from_options", "take_spans",
    "to_chrome_trace", "tracing_enabled",
]
