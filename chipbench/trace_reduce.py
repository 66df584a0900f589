"""From a profiler trace (`*.xplane.pb`) to device numbers.

The reduction is the benchmark's own, so that every PR computes the same
number in the same way: the union of the intervals in which an operation
ran on each chip (busy), time per device op and per XLA module, the
longest idle gaps named by the host annotation that covers most of each, and the
least bytes a kernel has to move, read from the shapes in the op's own
HLO text.

A plane is a device if its name starts with `/device:TPU:`; its lines
`XLA Ops` and `XLA Modules` hold the events.  The host's
`TraceAnnotation`s named `chipbench.*` are found on any host line;
`chipbench.window` bounds the measured window.
"""

from __future__ import annotations

import bisect
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "chipbench.window"
ANNOTATION_PREFIX = "chipbench."

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
_OPERAND = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\](?:\{[^}]*\})?\s+"
                      r"%([\w.\-]+)")

Interval = Tuple[float, float]


@dataclass
class Event:
    name: str            # short op or module name
    module: str          # the enclosing module's name ("" for a module)
    start: float         # seconds on the trace's clock
    end: float
    text: str            # the HLO text the profiler gives, or the name
    ops: List["Event"] = field(default_factory=list)    # a module's ops

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclass
class Reduction:
    window: Interval
    chips: int
    busy_s: float                       # mean over the chips
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)
    op_seconds: Dict[str, float] = field(default_factory=dict)
    module_seconds: Dict[str, float] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        ranked = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[n, s] for n, s in ranked[:top]],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:top]]}


# -- intervals ---------------------------------------------------------------

def union(intervals: List[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint intervals: overlapping events count once."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: List[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def total(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: List[Interval], window: Interval) -> List[Interval]:
    """What `busy` (disjoint, sorted) leaves of the window."""
    out, at = [], window[0]
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if window[1] > at:
        out.append((at, window[1]))
    return out


# -- HLO text ----------------------------------------------------------------

def short_name(text: str) -> str:
    """`%sort.31 = (...) sort(...)` -> `sort.31`; a plain name stays."""
    head = text.split(" = ", 1)[0].strip()
    return head.lstrip("%")


def module_base(name: str) -> str:
    """`jit__seg_sum_jit(1686...)` -> `jit__seg_sum_jit`."""
    return name.split("(", 1)[0]


def _nbytes(dtype: str, dims: str) -> int:
    n = 1
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def result_bytes(text: str) -> Optional[int]:
    """Bytes of the result (every element of a tuple) of one HLO
    instruction, from its text; None if the text carries no shapes."""
    if " = " not in text:
        return None
    rest = text.split(" = ", 1)[1]
    shapes = _SHAPE.findall(rest[:_result_end(rest)])
    return sum(_nbytes(t, d) for t, d in shapes) if shapes else None


def operands(text: str) -> List[Tuple[str, int]]:
    """(name, bytes) of each array operand of one HLO instruction."""
    if " = " not in text:
        return []
    rest = text.split(" = ", 1)[1]
    rest = rest[_result_end(rest):]
    cut = re.search(r"[\w\-]+\(", rest)
    if not cut:
        return []
    args = rest[cut.end():]
    return [(name, _nbytes(t, d)) for t, d, name in _OPERAND.findall(args)]


def _result_end(rest: str) -> int:
    """Index just past the result type (a shape or a tuple of shapes)."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return i + 1
    m = re.match(r"\S+\s", rest)
    return m.end() if m else 0


def sort_min_bytes(op: Event) -> Optional[int]:
    """A sort reads every operand once and writes every result once; its
    results have the operands' shapes.  Any sort algorithm moves at
    least that."""
    out = result_bytes(op.text)
    return None if out is None else 2 * out


def module_min_bytes(ops: List[Event]) -> Optional[int]:
    """One execution of a module reads each of its inputs once and writes
    its result once.  Inputs are the operands no op of the module
    produced (tuple elements and constants are not inputs); the result
    is the last op's."""
    if not ops:
        return None
    made = {o.name for o in ops}
    seen, total_b = set(), 0
    for o in ops:
        for name, nbytes in operands(o.text):
            if name in made or name in seen \
                    or name.startswith(("get-tuple-element", "constant")):
                continue
            seen.add(name)
            total_b += nbytes
    out = result_bytes(ops[-1].text)
    if out is None or not seen:
        return None
    return total_b + out


# -- reading the trace -------------------------------------------------------

def find_xplane(trace_dir: str) -> Optional[str]:
    found = []
    for d, _, files in os.walk(trace_dir):
        found += [os.path.join(d, f) for f in files
                  if f.endswith(".xplane.pb")]
    return max(found, key=os.path.getmtime) if found else None


def read_planes(path: str):
    """[(plane name, [(line name, [(name, start_s, end_s)])])] with
    nothing but JAX."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name,
                          [(e.name, e.start_ns / 1e9,
                            (e.start_ns + e.duration_ns) / 1e9)
                           for e in line.events]))
        planes.append((plane.name, lines))
    return planes


def reduce_planes(planes) -> Optional[Reduction]:
    """None when no operation ran on any device."""
    annotations = []
    devices = []
    for pname, lines in planes:
        if pname.startswith(DEVICE_PLANE):
            by_line = dict(lines)
            devices.append((by_line.get(MODULES_LINE, []),
                            by_line.get(OPS_LINE, [])))
        elif pname.startswith("/host:"):
            for _, events in lines:
                annotations += [e for e in events
                                if e[0].startswith(ANNOTATION_PREFIX)]
    devices = [d for d in devices if d[0] or d[1]]
    if not devices:
        return None

    spans = [(a, b) for mods, ops in devices for _, a, b in mods + ops]
    window = next(((a, b) for n, a, b in annotations if n == WINDOW),
                  (min(a for a, _ in spans), max(b for _, b in spans)))

    all_ops, all_modules, busy_per_chip = [], [], []
    for mods, ops in devices:
        mod_events = sorted((Event(module_base(n), "", a, b, n)
                             for n, a, b in mods), key=lambda e: e.start)
        starts = [m.start for m in mod_events]
        op_events = []
        for text, a, b in sorted(ops, key=lambda e: e[1]):
            # modules of one chip do not overlap: the last one that
            # started before the op either holds it or none does
            i = bisect.bisect_right(starts, a) - 1
            home = mod_events[i] if i >= 0 \
                and b <= mod_events[i].end + 1e-9 else None
            op = Event(short_name(text), home.name if home else "", a, b,
                       text)
            if home:
                home.ops.append(op)
            op_events.append(op)
        busy = union(clip([(e.start, e.end)
                           for e in mod_events + op_events], window))
        busy_per_chip.append(busy)
        all_modules += [m for m in mod_events
                        if m.end > window[0] and m.start < window[1]]
        all_ops += [o for o in op_events
                    if o.end > window[0] and o.start < window[1]]

    red = Reduction(window=window, chips=len(devices),
                    busy_s=sum(map(total, busy_per_chip)) / len(devices),
                    ops=all_ops, modules=all_modules)
    for o in all_ops:
        key = f"{o.module}/{o.name}" if o.module else o.name
        red.op_seconds[key] = red.op_seconds.get(key, 0.0) + o.seconds
    for m in all_modules:
        red.module_seconds[m.name] = \
            red.module_seconds.get(m.name, 0.0) + m.seconds

    # idle gaps of the first chip, named by the annotation that covers
    # most of the gap, if it covers half of it
    spans = [a for a in annotations if a[0] != WINDOW]
    for a, b in gaps(busy_per_chip[0], window):
        over, cover = max(((min(b, e) - max(a, s), n) for n, s, e in spans),
                          default=(0.0, ""))
        name = cover[len(ANNOTATION_PREFIX):] if over >= (b - a) / 2 \
            else "between_operations"
        red.idle_gaps.append((name, b - a))
    red.idle_gaps.sort(key=lambda g: -g[1])
    return red


def reduce_file(path: str) -> Optional[Reduction]:
    return reduce_planes(read_planes(path))


def peak_bytes_per_s(device_kind: str) -> float:
    """HBM bandwidth of the device, from the table beside this file.  A
    device that is not in the table is an error, not a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json")
    return float(peaks[device_kind]["hbm_bytes_per_s"])
