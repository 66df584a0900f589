"""A single-table aggregate statement through `SQLContext.sql`, one
statement per operation, over the configuration's table built in set-up
(its commits written through the program's write path, the reference
beside the build on a thread) and registered in a catalog.

Timed from the statement's text to its result table.  Every operation's
result is held whole to the reference (it is a few rows).  Rows are
counted on the input side: the rows the data files hold."""

from __future__ import annotations

import ctypes
import importlib
import os
import time
from concurrent.futures import ThreadPoolExecutor

from chipbench import data
from chipbench.operations import _table

TABLE = "tpch.lineitem"
_M_MMAP_THRESHOLD = -3          # <malloc.h>
_MMAP_THRESHOLD = 128 * 1024    # glibc's DEFAULT_MMAP_THRESHOLD


def _pin_allocator(run):
    """glibc raises its mmap threshold to the largest block a process has
    freed, so which of a statement's bucket-sized numpy temporaries come
    from a retained heap and which are mapped fresh depends on the frees
    before it: about one operation in seven ran 15 % faster than the
    others, and a run's rate hung on how many of those it drew (PERF.md
    section 6, PR 34).  Naming the threshold, at its default, switches
    that adaptation off, so every operation allocates alike."""
    try:
        ok = ctypes.CDLL(None).mallopt(_M_MMAP_THRESHOLD, _MMAP_THRESHOLD)
    except (OSError, AttributeError):
        return                  # no glibc: nothing adapts, nothing to pin
    if ok:
        run.state["mmap_threshold"] = _MMAP_THRESHOLD


def _modules(run):
    d = run.config["data"]
    return (importlib.import_module("chipbench." + d["generator"]),
            importlib.import_module("chipbench." + d["reference"]))


def _references(ref, commits, queries):
    rows = ref.live_rows(commits)
    return {name: getattr(ref, name)(rows, q["params"])
            for name, q in queries.items()}


def prepare(run):
    _pin_allocator(run)
    gen, ref = _modules(run)
    from paimon_tpu.sql.parser import parse
    for q in run.config["queries"].values():
        parse(q["sql"])     # a program that cannot read the statements
        #                     fails here, in seconds, not after the build
    t = time.perf_counter()
    commits = gen.gen_commits(run.args.seed,
                              {**run.config["data"], **run.data})
    run.setup["generate_s"] = time.perf_counter() - t
    from paimon_tpu import create_catalog
    from paimon_tpu.sql.executor import SQLContext
    catalog = create_catalog(
        {"warehouse": os.path.join(run.tmp, "warehouse")})
    table = gen.create_table(catalog, TABLE, run.config["table"])
    queries = run.config["queries"]
    with ThreadPoolExecutor(max_workers=1) as pool:
        want = pool.submit(_references, ref, commits, queries)
        t = time.perf_counter()
        with data.host_pinned_build():
            for c in commits:
                data.write_commit(table, gen.to_arrow(c),
                                  _table.BUILD_BATCHES)
        run.setup["build_s"] = time.perf_counter() - t
        t = time.perf_counter()
        run.state["want"] = want.result()
        run.setup["reference_wait_s"] = time.perf_counter() - t
    run.state.update(
        context=SQLContext(catalog, TABLE.split(".")[0]), ref=ref,
        input_rows=table.new_scan().plan().row_count,
        written_rows=sum(len(c["kind"]) for c in commits))


def _check(run, name, result, what):
    run.state["ref"].check(result, run.state["want"][name],
                           run.state["ref"].SCALES[name], what)


def before(run, i):
    return run.traffic["query"]


def operation(run, query):
    return run.state["context"].sql(query)


def after(run, i, result):
    _check(run, run.traffic["check"], result, f"operation {i}")
    run.state["last"] = result
    return run.state["input_rows"]


def warm(run):
    """Every query of the configuration once, each checked (the window's
    own statement is one of them and compiles here); their results go on
    the info line."""
    queries = run.config["queries"]
    assert run.traffic["query"] == queries[run.traffic["check"]]["sql"]
    for name, q in queries.items():
        result = operation(run, q["sql"])
        _check(run, name, result, f"warm {name}")
        run.state[f"warm_{name}"] = str(result.to_pylist())


def verify(run):
    _check(run, run.traffic["check"], run.state["last"], "last operation")
