"""Seeded upsert stream for the lookup-changelog-upsert configuration.

One commit is one checkpoint of `commit_rows` upserts (+I rows, no
deletes) into `mor50m-dedup`'s table (`data.gen_runs`' row shape).  The
keys are uniform over [0, key_space), drawn from a stream of the
configuration's `key_seed` apart from the one that built the table, so
every run's commits touch the same keys and fill every bucket, run and
padded program to the same sizes; the values come from `--seed`, from
streams apart from the build's, so a commit's values are never the ones
the table holds.  Imports nothing of `paimon_tpu`."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

# the entropy word that sets the stream's draws apart from the build's
_STREAM = 1


def gen_upserts(seed: int, commit_rows: int, pool_commits: int,
                key_space: int, key_seed: int):
    """`pool_commits` commits of `commit_rows` rows: id uniform in
    [0, key_space), v1 BIGINT < 2**40, v2 DOUBLE in [0, 1), v3 INT in
    [0, 100)."""
    n = commit_rows * pool_commits
    draws = {"id": lambda g: g.integers(0, max(key_space, 1), n),
             "v1": lambda g: g.integers(0, 1 << 40, n),
             "v2": lambda g: g.random(n),
             "v3": lambda g: g.integers(0, 100, n, dtype=np.int32)}
    streams = [np.random.SeedSequence([key_seed, _STREAM])] \
        + np.random.SeedSequence([seed, _STREAM]).spawn(len(draws) - 1)
    with ThreadPoolExecutor(max_workers=len(draws)) as pool:
        futures = {k: pool.submit(draw, np.random.default_rng(s))
                   for (k, draw), s in zip(draws.items(), streams)}
        cols = {k: f.result() for k, f in futures.items()}
    return [{k: v[i * commit_rows:(i + 1) * commit_rows]
             for k, v in cols.items()} for i in range(pool_commits)]
