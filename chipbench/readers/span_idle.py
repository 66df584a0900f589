"""The device's idle time against the program's own spans
(`chipbench/span_reduce.py`), from the run's profiler trace.  params,
one of:

* `{"uncovered": true}`: 100 x idle time inside the operations that no
  leaf span of any thread covers / idle time inside the operations.
* `{"within": [names]}`: 100 x (time at least one such span is open and
  the device idles) / (time at least one is open).  0.0 when the trace
  holds no such span (the program opened none in this run).

Nothing to read (`None`) without a trace or without a device plane in
it.  The trace is found by `run.py`'s own rule, from the cell's name
and `--seed`, and reduced once per run; the first read prints the
`[chipbench] spans {...}` line, ahead of the info line."""

import os

from chipbench import span_reduce, trace_reduce

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_reductions = {}        # trace directory -> SpanReduction | None


def _reduction(run):
    trace_dir = os.path.join(
        _ROOT, "chiprun_out", "chipbench", "traces",
        f"{run.cell['name']}.seed{run.args.seed}")
    if trace_dir not in _reductions:
        path = trace_reduce.find_xplane(trace_dir)
        red = span_reduce.reduce_file(path) if path else None
        if red is not None:
            print(red.line(), flush=True)
        _reductions[trace_dir] = red
    return _reductions[trace_dir]


def read(run, params):
    if run.trace is None:               # no trace, or no device in it
        return None
    red = _reduction(run)
    if red is None or not red.has_device:
        return None
    if params.get("uncovered"):
        return red.uncovered_share()
    return red.idle_share_within(params["within"])
