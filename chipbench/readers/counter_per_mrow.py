"""A counter of the program's metric registry, as its delta over the
window, per million rows of the completed operations.  params: group,
metric.

Nothing to read (`None`) where the program has no such counter or no
operation completed; a counter that did not move reads 0."""


def read(run, params):
    key = (params["group"], params["metric"])
    if key not in run.counters.registry or not run.rows:
        return None
    return run.counters.registry[key] / (run.rows / 1e6)
