"""Least time over measured device time of one kernel, in the window.

Bound by bytes: the least time is the bytes the kernel must move once
(computed by `trace_reduce` from the shapes in the trace's own HLO text)
over the device's HBM bandwidth (`peaks.json`).  params: either
`op_prefix` (device ops whose short name starts with it: each is a
sort-like op that reads and writes its operands once) or
`module_contains` (XLA modules whose name contains it: each execution
reads its inputs and writes its result once)."""

from chipbench import trace_reduce as T


def read(run, params):
    red = run.trace
    if red is None:
        return None
    if "op_prefix" in params:
        events = [o for o in red.ops
                  if o.name.startswith(params["op_prefix"])]
        sizes = [T.sort_min_bytes(o) for o in events]
    else:
        events = [m for m in red.modules
                  if params["module_contains"] in m.name]
        sizes = [T.module_min_bytes(m.ops) for m in events]
    seconds = sum(e.seconds for e in events)
    if not events or None in sizes or seconds <= 0:
        return None
    least = sum(sizes) / T.peak_bytes_per_s(run.device["kind"])
    return 100.0 * least / seconds
