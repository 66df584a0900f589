"""Seeded data and tables for one configuration.

Copied from chip_smoke.py (`_gen_runs`, `_create_table`, `_write_runs`)
so that the yardstick does not import what later PRs edit.  Everything
is drawn from `--seed`; the sizes never depend on it.
"""

from __future__ import annotations

import contextlib
import os
import shutil
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_TYPES = {"BIGINT NOT NULL": ("BigIntType", (False,)),
          "BIGINT": ("BigIntType", ()),
          "DOUBLE": ("DoubleType", ()),
          "INT": ("IntType", ())}


def gen_runs(seed: int, rows: int, runs: int, key_space: int,
             key_seed: int):
    """`runs` commits of rows // runs rows: id in [0, key_space),
    v1 BIGINT < 2**40, v2 DOUBLE in [0, 1), v3 INT in [0, 100).

    The keys are drawn uniformly from `key_seed`, which belongs to the
    configuration, and the values from `seed`: every seed writes other
    rows under the same keys.  So the rows the files hold, the merge
    windows and the padded sizes of every device program are the same
    in every run, and set-up finds its programs in the cache whatever
    the seed.  One vectorised draw per column, each from its own stream
    and on its own thread (numpy draws without the interpreter lock),
    then cut into the commits."""
    per_run = rows // runs
    n = per_run * runs
    draws = {"id": lambda g: g.integers(0, max(key_space, 1), n),
             "v1": lambda g: g.integers(0, 1 << 40, n),
             "v2": lambda g: g.random(n),
             "v3": lambda g: g.integers(0, 100, n, dtype=np.int32)}
    streams = [np.random.SeedSequence(key_seed)] \
        + np.random.SeedSequence(seed).spawn(len(draws) - 1)
    with ThreadPoolExecutor(max_workers=len(draws)) as pool:
        futures = {k: pool.submit(draw, np.random.default_rng(s))
                   for (k, draw), s in zip(draws.items(), streams)}
        cols = {k: f.result() for k, f in futures.items()}
    return [{k: v[i * per_run:(i + 1) * per_run] for k, v in cols.items()}
            for i in range(runs)]


def concat(run_list):
    return {k: np.concatenate([r[k] for r in run_list])
            for k in run_list[0]}


def create_table(path: str, table_cfg: dict):
    from paimon_tpu import types as T
    from paimon_tpu.schema import Schema
    from paimon_tpu.table import FileStoreTable

    builder = Schema.builder()
    for name, sql in table_cfg["columns"]:
        cls, args = _TYPES[sql]
        builder = builder.column(name, getattr(T, cls)(*args))
    options = {"bucket": str(table_cfg["buckets"]), **table_cfg["options"]}
    schema = builder.primary_key(*table_cfg["primary_key"]) \
        .options(options).build()
    return FileStoreTable.create(path, schema)


def to_arrow(run):
    import pyarrow as pa
    return pa.table({"id": pa.array(run["id"], pa.int64()),
                     "v1": pa.array(run["v1"], pa.int64()),
                     "v2": pa.array(run["v2"], pa.float64()),
                     "v3": pa.array(run["v3"], pa.int32())})


def write_commit(table, arrow_table, batches: int = 1):
    """One acknowledged batch commit: write (in `batches` calls), prepare,
    commit."""
    step = -(-arrow_table.num_rows // batches)
    wb = table.new_batch_write_builder()
    with wb.new_write() as w:
        for at in range(0, arrow_table.num_rows, step):
            w.write_arrow(arrow_table.slice(at, step))
        wb.new_commit().commit(w.prepare_commit())


@contextlib.contextmanager
def host_pinned_build():
    """The build is not under test: keep its flush sorts off the device
    (the files are the same bit for bit), for this block only."""
    os.environ["PAIMON_FORCE_HOST_SORT"] = "1"
    try:
        yield
    finally:
        del os.environ["PAIMON_FORCE_HOST_SORT"]


def forced_routes():
    """`PAIMON_FORCE_*` variables that are set: none may be when a
    window opens."""
    return sorted(k for k in os.environ if k.startswith("PAIMON_FORCE_"))


def _link_data_files(src, dst):
    # data files are immutable; metadata (snapshot hints) may be
    # rewritten in place, so only files under bucket-* share an inode
    if os.path.basename(os.path.dirname(src)).startswith("bucket-"):
        os.link(src, dst)
    else:
        shutil.copy2(src, dst)


def clone_table(src: str, dst: str):
    """A copy of a built table whose data files are hard links: no bytes
    are rewritten, so no writeback runs under the next timed span."""
    shutil.copytree(src, dst, copy_function=_link_data_files)
