"""Share of the window's merges that the router sent down one route
(`ops/merge.py` PATH_COUNTS).  params: route."""


def read(run, params):
    paths = run.counters.paths
    merges = sum(paths.values())
    if not merges:
        return None
    return 100.0 * paths[params["route"]] / merges
