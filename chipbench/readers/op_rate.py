"""Rows of the completed operations over the sum of their durations, by
the benchmark's own clock."""


def read(run, params):
    if not run.ops:
        return None
    return run.rows / run.op_seconds
