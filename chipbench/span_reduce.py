"""From a profiler trace to what the host did while the device idled.

`trace_reduce.py` says how long the device idled; this says under which
of the program's own spans.  The program (`paimon_tpu/obs/trace.py`)
enters a `TraceAnnotation` named `paimon.<span>` for every stage span
while a profiler session is open, so its stages lie on their threads'
lines of the host plane, on the device planes' clock.

The rules, the same for every PR:

* device busy and the window are `trace_reduce`'s; with several chips
  the device idles when no chip runs an op.
* **idle** is the device's gaps inside the harness's per-operation
  annotations (`chipbench.<traffic>`); the gaps between operations are
  the harness's own work and are reported apart, charged to no span.
* on every host line the `paimon.*` events nest by containment.  A
  **leaf** is an event with no `paimon.*` event inside it on its own
  line.  Only leaves attribute: the self time of an event that has
  children (an envelope such as `scan.split` or `compact.task`) is
  charged to nobody, so it shows as uncovered, and a new envelope
  around old code cannot hide a gap.
* a leaf named `paimon.wait` says who waited, not what ran: it is
  listed but never counts as cover.
* idle seconds by leaf name = (union over threads of that name's leaf
  intervals) ∩ idle.  Threads overlap, so the names may sum to more
  than the idle time; what no covering leaf of any thread overlaps is
  **uncovered**.

Imports nothing of `paimon_tpu`.

    python3 -m chipbench.span_reduce <trace dir | file.xplane.pb>

prints the `[chipbench] spans {...}` line of a trace (any trace with
`paimon.*` annotations in it, a CPU one too: without a device plane the
whole of every operation counts as idle).
"""

from __future__ import annotations

import json
import sys
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Tuple

from chipbench import trace_reduce as T
from chipbench.trace_reduce import Interval, clip, gaps, total, union

SPAN_PREFIX = "paimon."
WAIT = SPAN_PREFIX + "wait"
_EPS = 1e-6         # seconds; the trace's clock is in nanoseconds


@dataclass
class HostSpan:
    name: str
    start: float
    end: float
    self_s: float       # its duration less its direct children's
    leaf: bool          # no paimon.* event inside it on its own line


@dataclass
class SpanReduction:
    window: Interval
    has_device: bool
    spans: List[HostSpan] = field(default_factory=list)
    device_idle: List[Interval] = field(default_factory=list)  # in window
    idle: List[Interval] = field(default_factory=list)  # inside operations
    between_s: float = 0.0      # device idle between the operations
    idle_by_leaf: Dict[str, float] = field(default_factory=dict)
    uncovered_s: float = 0.0
    self_s: Dict[str, float] = field(default_factory=dict)

    @property
    def idle_s(self) -> float:
        return total(self.idle)

    def uncovered_share(self) -> float:
        """100 x idle time no covering leaf overlaps / idle time."""
        return 100.0 * self.uncovered_s / self.idle_s if self.idle_s else 0.0

    def idle_share_within(self, names: Iterable[str]) -> float:
        """100 x (time at least one span of `names` is open and the
        device idles) / (time at least one is open): what a round trip
        spends on link, dispatch and Python, not in a device op."""
        names = set(names)
        opened = union(clip([(s.start, s.end) for s in self.spans
                             if s.name in names], self.window))
        if not opened:
            return 0.0
        return 100.0 * total(intersect(opened, self.device_idle)) \
            / total(opened)

    def line(self, top: int = 12) -> str:
        ranked = sorted(self.idle_by_leaf.items(), key=lambda kv: -kv[1])
        selfs = sorted(self.self_s.items(), key=lambda kv: -kv[1])
        return "[chipbench] spans " + json.dumps({
            "device": self.has_device, "spans": len(self.spans),
            "window_s": self.window[1] - self.window[0],
            "idle_s": self.idle_s, "between_operations_s": self.between_s,
            "uncovered_s": self.uncovered_s,
            "idle_s_by_leaf": {n[len(SPAN_PREFIX):]: s
                               for n, s in ranked[:top]},
            "self_ms": {n[len(SPAN_PREFIX):]: round(s * 1e3, 3)
                        for n, s in selfs[:2 * top]}})


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Of two sorted lists of disjoint intervals, what both hold."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def nest(events: List[Tuple[str, float, float]]) -> List[HostSpan]:
    """One host line's `paimon.*` events, nested by containment."""
    out: List[HostSpan] = []
    stack: List[HostSpan] = []
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1].end <= a + _EPS:
            stack.pop()
        span = HostSpan(name, a, b, b - a, True)
        if stack:
            stack[-1].leaf = False
            stack[-1].self_s -= b - a
        stack.append(span)
        out.append(span)
    return out


def reduce_planes(planes) -> SpanReduction:
    operations, lines, device_busy = [], [], []
    window = None
    for pname, plane_lines in planes:
        if pname.startswith(T.DEVICE_PLANE):
            by_line = dict(plane_lines)
            events = by_line.get(T.MODULES_LINE, []) \
                + by_line.get(T.OPS_LINE, [])
            device_busy += [(a, b) for _, a, b in events]
        elif pname.startswith("/host:"):
            for _, events in plane_lines:
                lines.append([e for e in events
                              if e[0].startswith(SPAN_PREFIX)])
                for n, a, b in events:
                    if n == T.WINDOW:
                        window = (a, b)
                    elif n.startswith(T.ANNOTATION_PREFIX):
                        operations.append((a, b))
    if window is None:          # a trace taken outside the harness
        extent = device_busy + [(a, b) for ln in lines for _, a, b in ln]
        window = (min(a for a, _ in extent), max(b for _, b in extent)) \
            if extent else (0.0, 0.0)
    operations = union(clip(operations, window)) or [window]

    red = SpanReduction(window=window, has_device=bool(device_busy))
    busy = union(clip(device_busy, window))
    red.device_idle = gaps(busy, window)
    red.idle = intersect(red.device_idle, operations)
    red.between_s = total(red.device_idle) - red.idle_s

    by_name: Dict[str, List[Interval]] = {}
    for events in lines:
        for s in nest(events):
            if s.end <= window[0] or s.start >= window[1]:
                continue
            red.spans.append(s)
            red.self_s[s.name] = red.self_s.get(s.name, 0.0) + s.self_s
            if s.leaf:
                by_name.setdefault(s.name, []).append((s.start, s.end))
    cover: List[Interval] = []
    for name, ivs in by_name.items():
        red.idle_by_leaf[name] = total(intersect(union(ivs), red.idle))
        if name != WAIT:
            cover += ivs
    red.uncovered_s = red.idle_s - total(intersect(union(cover), red.idle))
    return red


def reduce_file(path: str) -> SpanReduction:
    return reduce_planes(T.read_planes(path))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        sys.stderr.write(__doc__.split("\n\n")[-2] + "\n")
        return 2
    path = argv[0] if argv[0].endswith(".xplane.pb") \
        else T.find_xplane(argv[0])
    if not path:
        sys.stderr.write(f"span_reduce: no .xplane.pb under {argv[0]!r}\n")
        return 1
    print(reduce_file(path).line(), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
