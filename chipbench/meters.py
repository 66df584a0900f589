"""What the benchmark reads from the program: compiles (jax.monitoring),
the metric registry's histograms and counters, the merge router's path
counts and first routings, its link reading.  All as deltas over the
window."""

from __future__ import annotations

from dataclasses import dataclass


class CompileMeter:
    """Backend compiles (count, seconds) and persistent-cache hits and
    misses (copied from chip_smoke.py `_CompileMeter`)."""

    def __init__(self, jax):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return (self.compiles, self.seconds, self.cache_hits,
                self.cache_misses)

    def since(self, snap):
        now = self.snapshot()
        return {"backend_compiles": now[0] - snap[0],
                "backend_compile_s": now[1] - snap[1],
                "persistent_cache_hits": now[2] - snap[2],
                "persistent_cache_misses": now[3] - snap[3]}


def _registry_totals():
    """{(group, metric): total} summed over tables: a histogram's sum of
    all values ever recorded, a counter's count."""
    from paimon_tpu.metrics import global_registry
    out = {}
    for r in global_registry().snapshot_rows():
        if r["kind"] == "histogram":
            value = r["total_sum"]
        elif r["kind"] == "counter":
            value = r["value"]
        else:
            continue
        key = (r["group"], r["metric"])
        out[key] = out.get(key, 0) + value
    return out


@dataclass
class Delta:
    registry: dict          # {(group, metric): delta over the window}
    paths: dict             # merges per route: host, device, ovc
    routes: list            # distinct (route, rows, lanes) of the first 64
    link: object            # the router's h2d / d2h reading, bytes/s
    compiles: dict


class Window:
    """Opened as the window opens; `close()` gives the deltas."""

    def __init__(self, meter: CompileMeter):
        from paimon_tpu.ops import merge as M
        self._M = M
        self._meter = meter
        self._compiles = meter.snapshot()
        self._registry = _registry_totals()
        self._paths = dict(M.PATH_COUNTS)
        del M.ROUTE_LOG[:]

    def close(self) -> Delta:
        M = self._M
        now = _registry_totals()
        routes = sorted({(r["route"], r["rows"], r["lanes"])
                         for r in M.ROUTE_LOG})
        return Delta(
            registry={k: v - self._registry.get(k, 0)
                      for k, v in now.items()},
            paths={k: M.PATH_COUNTS[k] - self._paths.get(k, 0)
                   for k in M.PATH_COUNTS},
            routes=routes[:16], link=M._LINK_BW,
            compiles=self._meter.since(self._compiles))
