"""A histogram of the program's metric registry (milliseconds, timed
inside the program), summed over the window, per million rows of the
completed operations.  params: group, metric."""


def read(run, params):
    ms = run.counters.registry.get((params["group"], params["metric"]))
    if not ms or not run.rows:
        return None
    return ms / (run.rows / 1e6)
