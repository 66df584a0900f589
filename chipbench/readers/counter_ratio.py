"""One counter of the program's metric registry over another, both as
deltas over the window, times `scale` (100: a share in percent).
params: numerator [group, metric], denominator [group, metric], scale.

Nothing to read (`None`) where the program has no denominator counter
or it did not move; a numerator the program never touched reads 0."""


def read(run, params):
    registry = run.counters.registry
    below = registry.get(tuple(params["denominator"]))
    if not below:
        return None
    above = registry.get(tuple(params["numerator"]), 0)
    return params.get("scale", 1.0) * above / below
