"""Batch ingest into an empty table: one commit of one run of the
configuration's data per operation, the runs taken in turn.

Timed from `write_arrow` through `prepare_commit` to the acknowledged
`commit`.  Rows are those handed to the writer."""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

from chipbench import data, reference
from chipbench.operations import _table


def prepare(run):
    runs = _table.generate(run)
    run.state.update(
        runs=runs, batches=[data.to_arrow(r) for r in runs], committed=[],
        table=data.create_table(os.path.join(run.tmp, "ingest"),
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
    """One commit of the same size into a table of its own."""
    table = data.create_table(os.path.join(run.tmp, "warm"),
                              run.config["table"])
    data.write_commit(table, run.state["batches"][0])


def verify(run):
    """A scan after the last commit equals the reference over all the
    rows committed, in commit order.  The reference's sort runs beside
    the scan (numpy sorts without the interpreter lock)."""
    from paimon_tpu.table import FileStoreTable
    runs = run.state["runs"]
    cols = data.concat([runs[j] for j in run.state["committed"]])
    with ThreadPoolExecutor(max_workers=1) as pool:
        want = pool.submit(reference.merged, cols,
                           run.config["table"]["engine"])
        path = os.path.join(run.tmp, "ingest")
        got = reference.columns_of(FileStoreTable.load(path).to_arrow())
        reference.check_equal(got, want.result(),
                              "scan after the last commit")
