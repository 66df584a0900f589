"""Streaming upserts under `changelog-producer=lookup`: one writer, one
commit of the configuration's pool per operation, into the fully
compacted `mor50m-dedup` table.

Set-up builds the table from `data.gen_runs` (write-only, no changelog:
the build is not under test), compacts it fully, opens one stream writer
on it (upstream's sink: `write_arrow`, `prepare_commit`, `commit`,
checkpoint after checkpoint).  The writer's levels index is built by
its first commit, which `warm` makes.  A cycle is the pool's commits in
turn; before the next cycle the finished one is kept aside as a
hard-link clone and the table is rolled back to the set-up snapshot.
The writer lives on: its index drops the runs the rollback removed and
keeps the set-up's, so every cycle sees the same level shapes and no
level file is decoded in the window.  Each operation is timed from
`write_arrow` through `prepare_commit` (the flush, the forced level-0
compaction, the probe of the levels index, the changelog) to the
acknowledged commit; its rows are the upserts.

After the window every committed commit's changelog, and the whole table
after the last commit of each cycle, are held to
`chipbench/reference_lookup.py`."""

from __future__ import annotations

import importlib
import importlib.util
import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench import data
from chipbench.operations import _table


def _ref(run):
    return importlib.import_module(
        "chipbench." + run.config["data"]["reference"])


def prepare(run):
    if importlib.util.find_spec("paimon_tpu.lookup.levels_index") is None:
        # such a program writes no changelog at commit and replays the
        # whole table to write one at a compaction: it fails here, at
        # once, and measures nothing
        raise RuntimeError("the program has no lookup compaction at "
                           "commit (paimon_tpu.lookup.levels_index)")
    from chipbench import data_lookup
    ref = _ref(run)
    d = {**run.config["data"], **run.data}
    runs = _table.generate(run)
    t = time.perf_counter()
    pool_commits = data_lookup.gen_upserts(
        run.args.seed, d["commit_rows"], d["pool_commits"],
        d["key_space"], d["key_seed"])
    run.setup["generate_stream_s"] = time.perf_counter() - t
    base = os.path.join(run.tmp, "base")
    table = data.create_table(base, run.config["table"])
    build = table.copy(run.config["build_options"])
    with ThreadPoolExecutor(max_workers=1) as pool:
        # numpy sorts without the interpreter lock: the reference's
        # state is folded beside the build
        state = pool.submit(ref.base_state, runs, d["key_space"])
        t = time.perf_counter()
        with data.host_pinned_build():
            for r in runs:
                data.write_commit(build, data.to_arrow(r),
                                  _table.BUILD_BATCHES)
            run.setup["build_s"] = time.perf_counter() - t
            t = time.perf_counter()
            if build.compact(full=True) is None:
                raise RuntimeError("the set-up compaction committed "
                                   "nothing")
        run.setup["compact_s"] = time.perf_counter() - t
        t = time.perf_counter()
        base_state = state.result()
        run.setup["reference_wait_s"] = time.perf_counter() - t
    from paimon_tpu.table import FileStoreTable
    table = FileStoreTable.load(base)
    wb = table.new_stream_write_builder()
    writer, committer = wb.new_write(), wb.new_commit()
    run.state.update(
        ref=ref, table=table, writer=writer, committer=committer,
        base_id=table.snapshot_manager.latest_snapshot_id(),
        base_state=base_state, commits=pool_commits,
        batches=[data.to_arrow(c) for c in pool_commits],
        cycles=[], open=None, kept=0, identifier=0,
        index_rows=int(base_state.present.sum()))


def _close_cycle(run, keep: bool):
    """Keep the open cycle's table aside (hard links), and roll the
    table back to the set-up snapshot."""
    cycle = run.state["open"]
    if cycle is None:
        return
    if keep and cycle["commits"]:
        run.state["kept"] += 1
        cycle["path"] = os.path.join(run.tmp, f"cycle_{run.state['kept']}")
        data.clone_table(run.state["table"].path, cycle["path"])
        run.state["cycles"].append(cycle)
    run.state["table"].rollback_to(run.state["base_id"])
    run.state["open"] = None


def before(run, i):
    pool = len(run.state["batches"])
    if i % pool == 0:
        _close_cycle(run, keep=True)
    if run.state["open"] is None:
        run.state["open"] = {"commits": []}
    return i % pool


def operation(run, j):
    w, c = run.state["writer"], run.state["committer"]
    w.write_arrow(run.state["batches"][j])
    run.state["identifier"] += 1
    c.commit(w.prepare_commit(), commit_identifier=run.state["identifier"])
    return j


def after(run, i, j):
    run.state["open"]["commits"].append(j)
    return run.state["batches"][j].num_rows


def warm(run):
    """One whole cycle, then the rollback: every level shape the window
    meets, so every program it runs, is compiled here."""
    for i in range(len(run.state["batches"])):
        operation(run, before(run, i))
    run.state["open"] = {"commits": []}
    _close_cycle(run, keep=False)


def _columns(arrow_table, names):
    return {n: arrow_table.column(n).to_numpy() for n in names}


def _changelogs(path, start):
    """The changelog of each snapshot after `start` that carries one,
    in snapshot order, as numpy columns with the row kind."""
    from paimon_tpu.core.read import ROW_KIND_COL
    from paimon_tpu.table import FileStoreTable
    table = FileStoreTable.load(path)
    scan = table.new_read_builder().new_stream_scan()
    scan.restore((start or 0) + 1)
    read = table.new_read_builder().new_read()
    out = []
    while True:
        plan = scan.plan()
        if plan is None:
            return out
        if plan.splits:
            t = read.to_arrow(plan)
            cols = _columns(t, ["id", "v1", "v2", "v3"])
            cols["kind"] = t.column(ROW_KIND_COL).to_numpy() \
                .astype(np.int8)
            out.append(cols)


def verify(run):
    """Each cycle from the set-up state: every commit's changelog, in
    commit order, equals the reference's; the whole table after the
    cycle's last commit equals the reference's state.  Reads stay off
    the chip (host merges, the host's CPU device), as in
    `ingest_ycsb.verify`."""
    from paimon_tpu.table import FileStoreTable
    from chipbench.operations.ingest_ycsb import _host_cpu_device
    _close_cycle(run, keep=True)
    run.state["writer"].close()
    ref = run.state["ref"]
    t = time.perf_counter()
    with data.host_pinned_build(), _host_cpu_device():
        for n, cycle in enumerate(run.state["cycles"]):
            state = run.state["base_state"].copy()
            got = _changelogs(cycle["path"], run.state["base_id"])
            if len(got) != len(cycle["commits"]):
                raise ref.Mismatch(
                    f"cycle {n}: {len(got)} snapshots carry a changelog, "
                    f"{len(cycle['commits'])} commits were acknowledged")
            for k, (j, cl) in enumerate(zip(cycle["commits"], got)):
                want = ref.commit(state, run.state["commits"][j])
                ref.check_changelog(cl, want, f"cycle {n} commit {k}")
            table = FileStoreTable.load(cycle["path"]).to_arrow()
            ref.check_table(_columns(table, ["id", "v1", "v2", "v3"]),
                            state, f"cycle {n} table")
    run.state["verify_s"] = time.perf_counter() - t
