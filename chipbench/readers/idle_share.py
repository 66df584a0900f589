"""1 - (union of the device-op intervals / window), from the profiler's
trace, averaged over the chips."""


def read(run, params):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
