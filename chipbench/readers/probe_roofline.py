"""Least time over measured device time of the levels-index probe, in
the window.

The least bytes of one probe call come from the problem, not from the
compiled program (`least_bytes`): the probe keys read and their rows
written, plus the index keys a search has to read at the least — the
whole run once, or one key a probe for each step of a binary search,
whichever is fewer.  So any probe (binary search, merge path, hash) is
read against the same work, and a resident index is not counted as read
whole by every call.  The problem of each call is the attributes of the
program's span around it (params `span`: `probes`, `index_rows`,
`key_bytes`), read from the run's trace; the device time is that of the
XLA modules whose name contains params `module_contains`, over the
device's HBM bandwidth (`peaks.json`).

Nothing to read (`None`) without a trace, or where the window holds no
such module or no such span (a program without the probe)."""

import math
import os

from chipbench import trace_reduce as T
from chipbench.readers import span_idle

_calls = {}         # trace directory -> [(probes, index_rows, key_bytes)]


def least_bytes(probes: int, index_rows: int, key_bytes: int) -> int:
    """Bytes one call must move at the least."""
    steps = math.ceil(math.log2(index_rows)) if index_rows > 1 else 0
    return probes * (key_bytes + 4) + min(index_rows * key_bytes,
                                          probes * steps * key_bytes)


def _spans(path, name, window):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name != name or not \
                        window[0] <= e.start_ns / 1e9 < window[1]:
                    continue
                stats = dict(e.stats)
                out.append((int(stats.get("probes", 0)),
                            int(stats.get("index_rows", 0)),
                            int(stats.get("key_bytes", 0))))
    return out


def read(run, params):
    red = run.trace
    if red is None:
        return None
    modules = [m for m in red.modules
               if params["module_contains"] in m.name]
    seconds = sum(m.seconds for m in modules)
    if not modules or seconds <= 0:
        return None
    trace_dir = os.path.join(
        span_idle._ROOT, "chiprun_out", "chipbench", "traces",
        f"{run.cell['name']}.seed{run.args.seed}")
    if trace_dir not in _calls:
        path = T.find_xplane(trace_dir)
        _calls[trace_dir] = _spans(path, params["span"], red.window) \
            if path else []
    calls = _calls[trace_dir]
    if not calls:
        return None
    least = sum(least_bytes(*c) for c in calls) \
        / T.peak_bytes_per_s(run.device["kind"])
    return 100.0 * least / seconds
