"""YCSB workload-A updates committed into the usertable: one commit of
the configuration's pool per operation, the commits taken in turn, into
a write-only partial-update table created empty in set-up (upstream's
dedicated-compaction layout: the writer reads nothing of the table).

Timed from `write_arrow` through `prepare_commit` to the acknowledged
`commit`.  Rows are the updates handed to the writer.  After the window
the whole table is scanned and held to `chipbench/reference_ycsb.py`,
which folds the committed commits beside the scan."""

from __future__ import annotations

import contextlib
import importlib
import os
import time
from concurrent.futures import ThreadPoolExecutor

from chipbench import data


def _modules(run):
    d = run.config["data"]
    return (importlib.import_module("chipbench." + d["generator"]),
            importlib.import_module("chipbench." + d["reference"]))


def prepare(run):
    from paimon_tpu.ops import merge
    if not hasattr(merge, "tiebreak_cut_keys"):
        # such a program orders keys past the lane prefix a row at a
        # time: a commit takes ~17 s and the check minutes, far past the
        # cell's run, so it fails here, at once, and measures nothing
        raise RuntimeError("the program has no vectorised tie-break of "
                           "string keys past the lane prefix "
                           "(ops/merge.py tiebreak_cut_keys)")
    gen, ref = _modules(run)
    workload = run.config["workload"]
    t = time.perf_counter()
    commits = gen.gen_commits(run.args.seed,
                              {**run.config["data"], **run.data}, workload)
    batches = [gen.to_arrow(c, workload) for c in commits]
    run.setup["generate_s"] = time.perf_counter() - t
    run.state.update(
        gen=gen, ref=ref, commits=commits, batches=batches, committed=[],
        fields=gen.field_names(workload),
        table=gen.create_table(os.path.join(run.tmp, "usertable"),
                               run.config["table"]))


def before(run, i):
    return i % len(run.state["batches"])


def operation(run, j):
    data.write_commit(run.state["table"], run.state["batches"][j])
    return j


def after(run, i, j):
    run.state["committed"].append(j)
    return run.state["batches"][j].num_rows


def warm(run):
    """The pool's first commit into a table of its own.  Every commit of
    the pool fills each bucket's flush to a size that pads the sort to
    the same two programs (the keys, and so the buckets' row counts,
    come from `data.key_seed`): the first compiles both."""
    table = run.state["gen"].create_table(os.path.join(run.tmp, "warm"),
                                          run.config["table"])
    data.write_commit(table, run.state["batches"][0])


@contextlib.contextmanager
def _host_cpu_device():
    """JAX's default device is the host's CPU in this block, for every
    thread (the global setting, not a thread's own)."""
    import jax
    before = jax.config.jax_default_device
    jax.config.update("jax_default_device", jax.devices("cpu")[0])
    try:
        yield
    finally:
        jax.config.update("jax_default_device", before)


def verify(run):
    """The whole table after the last commit equals the reference's
    fold of the committed commits, row for row and column for column.
    The fold runs beside the scan (numpy and Arrow work without the
    interpreter lock).  The scan is not under test: its merges stay on
    the host and its segment reductions run on the host's CPU device,
    so that a bucket size the window's commit count makes new compiles
    no program for the chip after the window (that took the check from
    ~15 s to ~310 s on a chip with no compile cache; the rows are the
    same on either route)."""
    from paimon_tpu.table import FileStoreTable
    ref = run.state["ref"]
    with ThreadPoolExecutor(max_workers=1) as pool:
        want = pool.submit(ref.fold, run.state["commits"],
                           run.state["committed"], run.state["fields"])
        t = time.perf_counter()
        with data.host_pinned_build(), _host_cpu_device():
            got = FileStoreTable.load(
                os.path.join(run.tmp, "usertable")).to_arrow()
        run.state["verify_scan_s"] = time.perf_counter() - t
        ref.check(got, want.result(), "scan after the last commit")
