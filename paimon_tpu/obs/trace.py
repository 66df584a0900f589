"""Low-overhead structured span tracing.

Design constraints, in priority order:

1. **No-op when disabled.**  ``span(...)`` is called on every pipeline
   stage of every hot path; with no listener it must cost two flag
   checks (the module's own, and the profiler's static
   ``TraceAnnotation.is_enabled()``, ~80 ns).  The disabled call
   returns a shared singleton context manager (no allocation beyond
   the caller's kwargs dict, which is built per *stage* — per split /
   per flush / per window — never per row).  `benchmarks/micro.py`
   ``obs`` measures the disabled path at <2% of scan wall time vs an
   uninstrumented baseline, asserted by a tier-1 test
   (tests/test_obs.py).
2. **Thread-safe bounded collection.**  Spans land in a ring
   (`collections.deque(maxlen=trace.buffer.spans)`) under one lock;
   an unbounded trace can never OOM a long-running service.
3. **Nestable, across pools too.**  A `contextvars.ContextVar` tracks
   the current span, so children record their parent id without any
   caller plumbing.  Work handed to a pool or a thread goes through
   `carry(fn)`, which re-installs the submitter's span (and trace id)
   in the worker: a split, a window or a flush task records the span
   that caused it, and every span of one operation walks back to its
   root (`scan.to_arrow`, `compact.task`, `write.batch` /
   `write.prepare` / `write.commit`).  Each pool thread is still its
   own track in the Chrome trace.
4. **One timing, two sinks.**  A span that names a ``group``/``metric``
   also lands its duration in that metric group's latency histogram
   (`metrics.py`), so the registry snapshot and the trace timeline can
   never disagree about what was measured.
5. **Two listeners, one call site.**  Besides the ring
   (`enable_tracing()`), an open JAX profiler session is a listener:
   while one is open — whether or not tracing was enabled — every span
   also enters ``jax.profiler.TraceAnnotation("paimon." + name)`` with
   its scalar attrs, so the program's stages land on their thread's
   line of the trace's ``/host:CPU`` plane, on the same clock as the
   device planes (`paimon.scan.split`, `paimon.merge.device`,
   `paimon.compact.window`, `paimon.write.route`, `paimon.wait` ...;
   `chipbench/span_reduce.py` reads them back).  No option switches
   this on: start a profiler session around a table operation.

Enabling the ring is process-global (the planes share thread pools, so
per-table tracing would tear one timeline into halves): call
`enable_tracing()` / `disable_tracing()` directly (CLI `--trace`,
tests), or set the `trace.enabled` / `metrics.enabled` table options —
every pipeline entry point calls `sync_from_options`, where an
explicitly-set key wins and an absent key leaves the current state
untouched (so an explicit `enable_tracing()` is not silently reverted
by the next untraced table's scan).
"""

from __future__ import annotations

import contextvars
import itertools
import json
import os
import platform
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

__all__ = ["Span", "TraceCollector", "span", "enable_tracing",
           "disable_tracing", "tracing_enabled", "set_metrics_enabled",
           "metrics_enabled", "collector", "take_spans",
           "sync_from_options", "export_path", "export_dir",
           "set_export_dir", "process_tag", "set_replica_id",
           "new_trace_id", "current_trace_id", "current_context_token",
           "carry", "profiler_listening", "ANNOTATION_PREFIX",
           "inject_headers", "server_span", "spool_flush",
           "reset_spool"]

DEFAULT_BUFFER_SPANS = 8192

# Span stage names introduced by the fleet plane.  Producers use
# these BY NAME (the analysis-plane obs-drift rule checks that every
# STAGE_* constant has a producer in the package), so a renamed
# stage that loses its producer fails analysis instead of silently
# vanishing from the merged timeline.
STAGE_SERVE_REQUEST = "serve.request"
STAGE_CLIENT_REQUEST = "client.request"
STAGE_PLAN_LINK = "plan.link"
STAGE_LEASE_FOLD = "lease.fold"

# Every span of this module is named ``ANNOTATION_PREFIX + name`` on the
# profiler's host plane (chipbench/span_reduce.py matches on it).
ANNOTATION_PREFIX = "paimon."

# Header names of the W3C-style context carried on every serving hop.
HDR_TRACE_ID = "X-Trace-Id"
HDR_PARENT_SPAN = "X-Parent-Span"


class Span:
    """One completed timed region. `start_us` is microseconds on the
    process-wide perf_counter timeline (Chrome trace ts unit)."""

    __slots__ = ("span_id", "parent_id", "name", "cat", "start_us",
                 "dur_us", "tid", "thread", "attrs")

    def __init__(self, span_id: int, parent_id: Optional[int], name: str,
                 cat: str, start_us: float, dur_us: float, tid: int,
                 thread: str, attrs: Dict):
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.cat = cat
        self.start_us = start_us
        self.dur_us = dur_us
        self.tid = tid
        self.thread = thread
        self.attrs = attrs

    def overlaps(self, other: "Span") -> bool:
        """Wall-clock interval intersection (tests/benchmarks)."""
        return self.start_us < other.start_us + other.dur_us and \
            other.start_us < self.start_us + self.dur_us

    def __repr__(self):
        return (f"Span({self.name!r}, {self.dur_us / 1000.0:.3f}ms, "
                f"thread={self.thread!r}, attrs={self.attrs})")


class TraceCollector:
    """Thread-safe bounded span ring; oldest spans evict first."""

    def __init__(self, max_spans: int = DEFAULT_BUFFER_SPANS):
        self.max_spans = max(1, int(max_spans))
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=self.max_spans)
        self.dropped = 0          # evicted by the ring bound

    def add(self, s: Span):
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(s)

    def snapshot(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def clear(self):
        with self._lock:
            self._spans.clear()
            self.dropped = 0

    def resize(self, max_spans: int):
        max_spans = max(1, int(max_spans))
        with self._lock:
            if max_spans != self.max_spans:
                self.max_spans = max_spans
                self._spans = deque(self._spans, maxlen=max_spans)

    def __len__(self):
        with self._lock:
            return len(self._spans)


# -- process-global state ---------------------------------------------------

_enabled = False
_metrics_on = True
_collector = TraceCollector()
_export_path: Optional[str] = None
_export_dir: Optional[str] = None
_ids = itertools.count(1)
_current: contextvars.ContextVar = contextvars.ContextVar(
    "paimon_current_span", default=None)
_trace_id: contextvars.ContextVar = contextvars.ContextVar(
    "paimon_trace_id", default=None)

# Process identity for cross-process span references.  The OS reuses
# pids, so a random salt keeps tokens unique across a fleet's whole
# lifetime (a crashed worker's pid can be handed to its replacement).
_PROC = "%s-%d-%s" % (platform.node(), os.getpid(),
                      os.urandom(3).hex())
_replica_id: Optional[str] = None

# Spool bookkeeping: the per-process .jsonl under `trace.export.dir`
# is append-only; `_spooled_through` is the highest span id already on
# disk so repeated flushes never duplicate lines.
_spool_lock = threading.Lock()
_spooled_through = 0
_spool_header_done = False


_TraceAnnotation = None       # jax.profiler's, bound on first use


def profiler_listening() -> bool:
    """True exactly while a JAX profiler session is open.  Costs one
    static call (~80 ns); never imports jax itself — a process that
    has not imported it has no session to listen to."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        if "jax" not in sys.modules:
            return False
        from jax.profiler import TraceAnnotation
        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation.is_enabled()


def _annotation(name: str, attrs: Dict):
    """The span as the profiler sees it: `paimon.<name>` with the
    scalar attrs (rows, bytes, bucket, route...); anything else — a
    partition tuple, a path list — stays in the ring only."""
    return _TraceAnnotation(
        ANNOTATION_PREFIX + name,
        **{k: v for k, v in attrs.items()
           if isinstance(v, (bool, int, float, str))})


class _NoopSpan:
    """Shared do-nothing context manager for the disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NOOP = _NoopSpan()


class _MetricSpan:
    """Tracing disabled, metrics enabled: time the region into its
    latency histogram only — no ring append, no contextvar."""

    __slots__ = ("group", "metric", "t0")

    def __init__(self, group: str, metric: str):
        self.group = group
        self.metric = metric

    def set(self, **attrs):
        return self

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        from paimon_tpu.metrics import global_registry
        global_registry().group(self.group).histogram(self.metric) \
            .update((time.perf_counter() - self.t0) * 1000.0)
        return False


class _ProfiledSpan(_MetricSpan):
    """A profiler session is open and the ring is off: the span is an
    annotation on the profiler's clock, plus its histogram."""

    __slots__ = ("_ann",)

    def __init__(self, name: str, group: Optional[str],
                 metric: Optional[str], attrs: Dict):
        self.group = group if _metrics_on else None
        self.metric = metric
        self._ann = _annotation(name, attrs)

    def __enter__(self):
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.group is not None:
            _MetricSpan.__exit__(self)
        self._ann.__exit__(*exc)
        return False


class _LiveSpan:
    """Tracing enabled: full span with nesting + ring + histogram (and
    the profiler's annotation while a session is open)."""

    __slots__ = ("name", "cat", "group", "metric", "attrs", "t0",
                 "span_id", "_token", "_ann")

    def __init__(self, name: str, cat: str, group: Optional[str],
                 metric: Optional[str], attrs: Dict):
        self.name = name
        self.cat = cat
        self.group = group
        self.metric = metric
        self.attrs = attrs

    def set(self, **attrs):
        """Attach attrs mid-span (e.g. a result size known at the end)."""
        self.attrs.update(attrs)
        return self

    def __enter__(self):
        self.span_id = next(_ids)
        self._token = _current.set(self.span_id)
        self._ann = None
        if profiler_listening():
            self._ann = _annotation(self.name, self.attrs)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        _current.reset(self._token)
        parent = _current.get()
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        t = threading.current_thread()
        _collector.add(Span(
            self.span_id, parent, self.name, self.cat,
            self.t0 * 1e6, (t1 - self.t0) * 1e6,
            t.ident or 0, t.name, self.attrs))
        if self.group is not None and _metrics_on:
            from paimon_tpu.metrics import global_registry
            global_registry().group(self.group).histogram(self.metric) \
                .update((t1 - self.t0) * 1000.0)
        return False


def span(name: str, *, cat: str = "", group: Optional[str] = None,
         metric: Optional[str] = None, **attrs):
    """Context manager timing one stage.

    `cat` buckets spans for the Chrome trace; `group`+`metric` also
    land the duration in `global_registry().group(group)`'s
    `histogram(metric)` (use the *_MS constants from metrics.py so the
    name-drift test sees the producer).  Extra kwargs become span
    attributes (table/partition/bucket/snapshot/attempt...) — pass raw
    values, stringification happens at export time.

    Listeners: the ring (`enable_tracing()`) and an open JAX profiler
    session, which sees the span as the annotation `paimon.<name>`
    with the scalar attrs.  With neither, a grouped span only times
    its histogram and an ungrouped one is the shared no-op.
    """
    if _enabled:
        return _LiveSpan(name, cat, group, metric or name, attrs)
    if profiler_listening():
        return _ProfiledSpan(name, group, metric or name, attrs)
    if group is not None and _metrics_on:
        return _MetricSpan(group, metric or name)
    return _NOOP


def carry(fn):
    """`fn`, bound to the submitting thread's current span and trace
    id: called on a pool worker (or a spawned thread) it re-installs
    both, so the spans it opens there record the span that caused the
    work as their parent.  Returns `fn` itself when the ring is off —
    the profiler's trace nests by time on each thread's own line and
    has no parent ids to carry."""
    if not _enabled:
        return fn
    sid, tid = _current.get(), _trace_id.get()

    def carried(*args, **kwargs):
        t_span, t_trace = _current.set(sid), _trace_id.set(tid)
        try:
            return fn(*args, **kwargs)
        finally:
            _trace_id.reset(t_trace)
            _current.reset(t_span)
    return carried


# -- cross-process trace context --------------------------------------------

def process_tag() -> str:
    """Stable identity of this process inside a fleet trace:
    ``<host>-<pid>-<salt>``.  Span references across process
    boundaries are ``<process_tag>:<span_id>`` tokens."""
    return _PROC


def set_replica_id(replica_id: Optional[str]) -> None:
    """Tag this process's spool with a serving replica id so merged
    traces name tracks by replica, not just host-pid."""
    global _replica_id
    _replica_id = replica_id


def new_trace_id() -> str:
    """Fresh 128-bit trace id (32 hex chars, W3C trace-id shaped)."""
    return os.urandom(16).hex()


def current_trace_id() -> Optional[str]:
    return _trace_id.get()


def current_context_token() -> Optional[str]:
    """``<process_tag>:<span_id>`` of the current span, or None when
    no span is open (or tracing is off).  This is what gets stamped
    into snapshot commit properties and the X-Parent-Span header."""
    if not _enabled:
        return None
    sid = _current.get()
    if sid is None:
        return None
    return f"{_PROC}:{sid}"


def inject_headers(headers: Dict[str, str]) -> Dict[str, str]:
    """Add the W3C-style context headers to an outbound request.  A
    no-op unless tracing is on and a span is current; allocates a
    trace id lazily so the first hop of a request mints it."""
    if not _enabled:
        return headers
    sid = _current.get()
    if sid is None:
        return headers
    tid = _trace_id.get()
    if tid is None:
        tid = new_trace_id()
        _trace_id.set(tid)
    headers[HDR_TRACE_ID] = tid
    headers[HDR_PARENT_SPAN] = f"{_PROC}:{sid}"
    return headers


class _AdoptedSpan:
    """Server-side request span that adopts the remote caller's
    context: the trace id rides the contextvar for the handler's
    duration, and the remote parent token lands in the span attrs
    (``remote_parent``), where the fleet merge tool turns it into a
    flow arrow between the two processes' tracks."""

    __slots__ = ("_headers", "_attrs", "_inner", "_tid_token")

    def __init__(self, headers: Dict[str, str], attrs: Dict):
        self._headers = headers
        self._attrs = attrs

    def __enter__(self):
        tid = self._headers.get("x-trace-id")
        parent = self._headers.get("x-parent-span")
        self._tid_token = _trace_id.set(tid) if tid else None
        if tid:
            self._attrs["trace_id"] = tid
        if parent:
            self._attrs["remote_parent"] = parent
        self._inner = _LiveSpan(STAGE_SERVE_REQUEST, "serve", None,
                                None, self._attrs)
        self._inner.__enter__()
        return self._inner

    def __exit__(self, exc_type, exc, tb):
        r = self._inner.__exit__(exc_type, exc, tb)
        if self._tid_token is not None:
            _trace_id.reset(self._tid_token)
        return r


def server_span(headers: Optional[Dict[str, str]], **attrs):
    """Context manager wrapping one inbound request's handler; the
    shared no-op when tracing is off (one flag check on the serving
    hot path).  `headers` are the request's lower-cased headers."""
    if not _enabled:
        return _NOOP
    return _AdoptedSpan(headers or {}, attrs)


# -- per-process spool under trace.export.dir -------------------------------

def _jsonable(v):
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    return str(v)


def spool_flush() -> Optional[str]:
    """Append spans newer than the last flush to this process's
    ``<trace.export.dir>/<process_tag>.jsonl``; returns the spool path
    or None when no dir is configured.  The first line is a process
    header carrying identity plus a (wall clock, perf_counter) anchor
    pair — span timestamps are on the process-local perf_counter
    timeline, and the merge tool uses the anchor to re-base every
    process onto one shared wall-clock timeline.

    Like `maybe_export`, a spool failure warns instead of raising: the
    recorder must never fail the data path it observes."""
    global _spooled_through, _spool_header_done
    if _export_dir is None:
        return None
    spans = _collector.snapshot()
    path = os.path.join(_export_dir, _PROC + ".jsonl")
    with _spool_lock:
        fresh = [s for s in spans if s.span_id > _spooled_through]
        try:
            os.makedirs(_export_dir, exist_ok=True)
            with open(path, "a") as f:
                if not _spool_header_done:
                    f.write(json.dumps({
                        "proc": _PROC, "pid": os.getpid(),
                        "host": platform.node(),
                        "replica": _replica_id,
                        "wall_s": time.time(),
                        "perf_s": time.perf_counter(),
                    }) + "\n")
                    _spool_header_done = True
                for s in fresh:
                    f.write(json.dumps({
                        "sid": s.span_id, "parent": s.parent_id,
                        "name": s.name, "cat": s.cat,
                        "ts": round(s.start_us, 3),
                        "dur": round(s.dur_us, 3),
                        "tid": s.tid, "thread": s.thread,
                        "attrs": {k: _jsonable(v)
                                  for k, v in s.attrs.items()},
                    }) + "\n")
        except OSError as e:
            import warnings
            warnings.warn(f"trace spool to {path!r} failed: {e}",
                          RuntimeWarning)
            return None
        if fresh:
            _spooled_through = max(_spooled_through,
                                   max(s.span_id for s in fresh))
    return path


def reset_spool() -> None:
    """Forget spool state (tests): the next flush rewrites the header
    and re-spools the whole ring to a fresh file."""
    global _spooled_through, _spool_header_done
    with _spool_lock:
        _spooled_through = 0
        _spool_header_done = False


def set_export_dir(d: Optional[str]) -> None:
    global _export_dir
    if d != _export_dir:
        _export_dir = d
        reset_spool()


def export_dir() -> Optional[str]:
    return _export_dir


# -- switches ----------------------------------------------------------------

def enable_tracing(max_spans: Optional[int] = None):
    global _enabled
    if max_spans is not None:
        _collector.resize(max_spans)
    _enabled = True


def disable_tracing():
    global _enabled
    _enabled = False


def tracing_enabled() -> bool:
    return _enabled


def set_metrics_enabled(flag: bool):
    global _metrics_on
    _metrics_on = bool(flag)


def metrics_enabled() -> bool:
    return _metrics_on


def collector() -> TraceCollector:
    return _collector


def take_spans(clear: bool = False) -> List[Span]:
    out = _collector.snapshot()
    if clear:
        _collector.clear()
    return out


def export_path() -> Optional[str]:
    return _export_path


def sync_from_options(options) -> None:
    """Sync the process-global switches from a table's options at a
    pipeline entry point.  Explicitly-set keys win; absent keys leave
    the current state untouched.  `options` is a CoreOptions (or
    anything exposing `.options` with contains/get), or None."""
    global _export_path
    if options is None:
        return
    raw = getattr(options, "options", None)
    if raw is None or not hasattr(raw, "contains"):
        return
    from paimon_tpu.options import CoreOptions
    if raw.contains(CoreOptions.TRACE_ENABLED):
        if raw.get(CoreOptions.TRACE_ENABLED):
            # only resize when the key is explicitly set — the option
            # DEFAULT must not shrink a ring a caller enlarged via
            # enable_tracing(max_spans=...) (resizing drops spans)
            enable_tracing(
                raw.get(CoreOptions.TRACE_BUFFER_SPANS)
                if raw.contains(CoreOptions.TRACE_BUFFER_SPANS)
                else None)
        else:
            disable_tracing()
    if raw.contains(CoreOptions.METRICS_ENABLED):
        set_metrics_enabled(bool(raw.get(CoreOptions.METRICS_ENABLED)))
    if raw.contains(CoreOptions.TRACE_EXPORT_PATH):
        _export_path = raw.get(CoreOptions.TRACE_EXPORT_PATH)
    if raw.contains(CoreOptions.TRACE_EXPORT_DIR):
        set_export_dir(raw.get(CoreOptions.TRACE_EXPORT_DIR))


def maybe_export() -> Optional[str]:
    """Flush the ring to `trace.export.path` if configured (called at
    pipeline completion points); returns the path written, or None.

    An export failure (unwritable path) must never fail — or, from a
    `finally`, MASK the error of — the data path it observes: it
    warns and returns None instead."""
    if not _enabled:
        return None
    if _export_dir is not None:
        spool_flush()
    if _export_path is None:
        return None
    from paimon_tpu.obs.export import export_chrome_trace
    try:
        export_chrome_trace(_export_path)
    except OSError as e:
        import warnings
        warnings.warn(f"trace export to {_export_path!r} failed: {e}",
                      RuntimeWarning)
        return None
    return _export_path
