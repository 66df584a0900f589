"""Host time inside the operations that no leaf span names: the self
time of the program's envelopes, from the run's profiler trace, per
million rows of the completed operations.  No params.

An envelope is a `paimon.*` span with a `paimon.*` span inside it on its
own thread; its self time is its duration less its direct children's
(`span_reduce.nest`'s rule).  Summed over every envelope, on every
thread, that begins inside one of the harness's per-operation
annotations (`chipbench.<traffic>`); a span that begins between two
operations is the harness's own work and counts nothing.  A leaf counts
nothing either, `paimon.wait` among them: a thread that only waits adds
no time here.

The complement of `idle_unattributed_share.*` (`span_idle`,
`uncovered`): that asks whether any thread had a covering leaf open
while the device idled, so it reads near 0 wherever several workers
overlap; this asks how much host time lies under no leaf at all, and is
summed over the threads.

Nothing to read (`None`) without a trace, or without rows.  The spans
are `span_idle`'s reduction of the trace, made once a run; the
operations' intervals are read from the host planes, once a run."""

import bisect
import json
import os

from chipbench import span_reduce, trace_reduce
from chipbench.readers import span_idle

_operations = {}        # trace directory -> [(start, end)] | None


def operations(planes):
    """The harness's per-operation annotations of a plane list, as
    sorted disjoint intervals."""
    found = []
    for pname, lines in planes:
        if pname.startswith("/host:"):
            for _, events in lines:
                found += [(a, b) for n, a, b in events
                          if n.startswith(trace_reduce.ANNOTATION_PREFIX)
                          and n != trace_reduce.WINDOW]
    return trace_reduce.union(found)


def self_seconds(spans, ops):
    """({envelope name: self seconds}, the covering leaves' seconds)
    over those of `spans` (`span_reduce.HostSpan`) that begin inside one
    of `ops`; a `paimon.wait` leaf is in neither."""
    starts = [a for a, _ in ops]
    own, leaves = {}, 0.0
    for s in spans:
        i = bisect.bisect_right(starts, s.start) - 1
        if i < 0 or s.start >= ops[i][1]:
            continue
        if not s.leaf:
            own[s.name] = own.get(s.name, 0.0) + s.self_s
        elif s.name != span_reduce.WAIT:
            leaves += s.end - s.start
    return own, leaves


def _operations_of(run):
    trace_dir = os.path.join(
        span_idle._ROOT, "chiprun_out", "chipbench", "traces",
        f"{run.cell['name']}.seed{run.args.seed}")
    if trace_dir not in _operations:
        path = trace_reduce.find_xplane(trace_dir)
        _operations[trace_dir] = \
            operations(trace_reduce.read_planes(path)) if path else None
    return _operations[trace_dir]


def read(run, params):
    red = span_idle._reduction(run)
    if red is None or not run.rows:
        return None
    ops = _operations_of(run)
    if not ops:
        return None
    own, leaves = self_seconds(red.spans, ops)
    ranked = sorted(own.items(), key=lambda kv: -kv[1])
    print("[chipbench] span_self " + json.dumps({
        "operations": len(ops), "leaves_ms": round(leaves * 1e3, 3),
        "envelopes_self_ms": round(sum(own.values()) * 1e3, 3),
        "self_ms": {n[len(span_reduce.SPAN_PREFIX):]: round(s * 1e3, 3)
                    for n, s in ranked[:12]}}), flush=True)
    return 1e3 * sum(own.values()) / (run.rows / 1e6)
