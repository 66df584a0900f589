"""The seven migrated tier-1 hygiene lints.

These started life as ad-hoc AST walks in tests/test_lint_swallow.py,
each re-parsing every file; they now run over the shared program model
(one parse per file per run).  Semantics are unchanged — only the
exemption mechanism moved: the reviewed allowlists and the ad-hoc
`# host-ok:` marker are now uniform `# lint-ok: <rule> <reason>`
markers at the exempted site, so adding an exemption is a reviewed
diff on the line it exempts and a stale exemption is itself a finding.
"""

from __future__ import annotations

import ast
from typing import List

from paimon_tpu.analysis.engine import Finding, rule
from paimon_tpu.analysis.model import ProgramModel, except_names

_BROAD = {"Exception", "BaseException", "<bare>"}


def _broad_names(type_node):
    return [n for n in except_names(type_node) if n in _BROAD]


@rule("swallow",
      "silent broad-exception swallowing")
def check_swallow(model: ProgramModel) -> List[Finding]:
    """An `except Exception: pass` (or bare except / continue body)
    hides every error class — including the transient faults the
    maintenance plane must retry or propagate (parallel/fault.py).
    Narrow typed catches are out of scope: they are deliberate, local
    decisions.  Genuine best-effort paths carry a
    `# lint-ok: swallow <reason>` on the except line."""
    out = []
    for mod in model.modules.values():
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if len(node.body) != 1 or not isinstance(
                    node.body[0], (ast.Pass, ast.Continue)):
                continue
            if not _broad_names(node.type):
                continue
            fn = model.enclosing_function(mod, node.lineno)
            where = fn.qname.split("::")[-1] if fn else "<module>"
            out.append(Finding(
                "swallow", mod.rel, node.lineno,
                f"silent broad except in {where}: handle the error, "
                f"propagate it, or mark the reviewed best-effort path "
                f"with `# lint-ok: swallow <reason>`"))
    return out


@rule("threads",
      "bare threading.Thread outside parallel/")
def check_threads(model: ProgramModel) -> List[Finding]:
    """All threads and pools go through parallel/executors.py
    (spawn_thread / new_thread_pool) so every worker carries an
    attributable name and the no-leaked-thread tier-1 tests can key
    on it."""
    out = []
    for mod in model.modules.values():
        if mod.pkg_rel.startswith("parallel/"):
            continue               # the one reviewed home of threads
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            name = fn.attr if isinstance(fn, ast.Attribute) else \
                fn.id if isinstance(fn, ast.Name) else None
            if name == "Thread":
                out.append(Finding(
                    "threads", mod.rel, node.lineno,
                    "bare threading.Thread( outside parallel/ — use "
                    "parallel/executors.py spawn_thread/"
                    "new_thread_pool so the thread is named and "
                    "reviewable"))
    return out


@rule("sleeps",
      "bare time.sleep outside utils/backoff.py")
def check_sleeps(model: ProgramModel) -> List[Finding]:
    """Every wait in library code must be deadline-aware and
    injectable — `Backoff.pause()` for retry ladders, `wait_for()`
    for one-shot waits.  A bare sleep is an un-interruptible stall a
    timed-out request cannot escape.  Injectable sleeps stored as
    attributes (`self._sleep(...)`) are fine — only direct
    `time.sleep` / `from time import sleep` CALLS are flagged."""
    out = []
    for mod in model.modules.values():
        if mod.pkg_rel == "utils/backoff.py":
            continue          # the one reviewed home of real sleeps
        time_sleep_names = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ImportFrom) and \
                    node.module == "time":
                for alias in node.names:
                    if alias.name == "sleep":
                        time_sleep_names.add(alias.asname or alias.name)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            hit = (isinstance(fn, ast.Attribute) and
                   fn.attr == "sleep" and
                   isinstance(fn.value, ast.Name) and
                   fn.value.id in ("time", "_time")) or \
                  (isinstance(fn, ast.Name) and
                   fn.id in time_sleep_names)
            if hit:
                out.append(Finding(
                    "sleeps", mod.rel, node.lineno,
                    "bare time.sleep( outside utils/backoff.py — use "
                    "Backoff.pause() for retry ladders or "
                    "utils.backoff.wait_for() for one-shot waits"))
    return out


_NET_MODULES = {"socket", "selectors"}


@rule("sockets",
      "raw socket/selectors import outside service/async_server.py")
def check_sockets(model: ProgramModel) -> List[Finding]:
    """The event-loop request engine is the ONE reviewed home of
    non-blocking socket code: its loop owns every fd, bounds
    connections and pipelining, measures loop lag and shuts down
    cleanly.  HTTP clients use http.client, servers use
    service/async_server.AsyncHttpServer."""
    out = []
    for mod in model.modules.values():
        if mod.pkg_rel == "service/async_server.py":
            continue          # the one reviewed home of raw sockets
        for node in ast.walk(mod.tree):
            hit = False
            if isinstance(node, ast.Import):
                hit = any(a.name.split(".")[0] in _NET_MODULES
                          for a in node.names)
            elif isinstance(node, ast.ImportFrom):
                hit = bool(node.module) and \
                    node.module.split(".")[0] in _NET_MODULES
            if hit:
                out.append(Finding(
                    "sockets", mod.rel, node.lineno,
                    "raw socket/selectors import outside "
                    "service/async_server.py — ad-hoc network loops "
                    "are banned: serve through AsyncHttpServer and "
                    "talk HTTP through http.client"))
    return out


_COLLECTIVES = {"sync_global_devices", "broadcast_one_to_all",
                "process_allgather"}


@rule("collectives",
      "raw multihost collectives outside parallel/multihost.py")
def check_collectives(model: ProgramModel) -> List[Finding]:
    """multihost.py's barrier() / broadcast_value() /
    allgather_bytes() are the ONE reviewed wrap: deadline-bounded,
    barrier_wait_ms-instrumented, degrading to single-process no-ops.
    A raw jax.experimental.multihost_utils call elsewhere gets none of
    that — and a hung collective with a dead peer is exactly the
    failure the lease-based maintenance plane exists to tolerate."""
    out = []
    for mod in model.modules.values():
        if mod.pkg_rel == "parallel/multihost.py":
            continue        # the one reviewed home of collectives
        bound = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ImportFrom) and node.module \
                    and node.module.endswith("multihost_utils"):
                for alias in node.names:
                    if alias.name in _COLLECTIVES:
                        bound.add(alias.asname or alias.name)
                        out.append(Finding(
                            "collectives", mod.rel, node.lineno,
                            f"raw {alias.name} import outside "
                            f"parallel/multihost.py — use the "
                            f"deadline-bounded multihost wrappers"))
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            hit = (isinstance(fn, ast.Attribute) and
                   fn.attr in _COLLECTIVES) or \
                  (isinstance(fn, ast.Name) and fn.id in bound)
            if hit:
                out.append(Finding(
                    "collectives", mod.rel, node.lineno,
                    "raw multihost collective call outside "
                    "parallel/multihost.py — use multihost.barrier() "
                    "/ broadcast_value() / allgather_bytes()"))
    return out


@rule("distributed-init",
      "jax.distributed.initialize outside parallel/multihost.py")
def check_distributed_init(model: ProgramModel) -> List[Finding]:
    """multihost.initialize is the ONE reviewed bring-up: it opts the
    CPU backend into Gloo cross-process collectives BEFORE the backend
    initializes; a direct call elsewhere bypasses that and resurrects
    the 'Multiprocess computations aren't implemented' failure
    mode."""
    out = []
    for mod in model.modules.values():
        if mod.pkg_rel == "parallel/multihost.py":
            continue        # the one reviewed bring-up path
        init_names = set()
        dist_aliases = set()
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.ImportFrom):
                continue
            if node.module == "jax.distributed":
                for alias in node.names:
                    if alias.name == "initialize":
                        init_names.add(alias.asname or alias.name)
                        out.append(Finding(
                            "distributed-init", mod.rel, node.lineno,
                            "direct import of "
                            "jax.distributed.initialize outside "
                            "parallel/multihost.py — use "
                            "multihost.initialize()"))
            elif node.module == "jax":
                for alias in node.names:
                    if alias.name == "distributed":
                        dist_aliases.add(alias.asname or alias.name)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            hit = (isinstance(fn, ast.Attribute) and
                   fn.attr == "initialize" and
                   ((isinstance(fn.value, ast.Attribute) and
                     fn.value.attr == "distributed") or
                    (isinstance(fn.value, ast.Name) and
                     fn.value.id in dist_aliases))) or \
                  (isinstance(fn, ast.Name) and fn.id in init_names)
            if hit:
                out.append(Finding(
                    "distributed-init", mod.rel, node.lineno,
                    "direct jax.distributed.initialize( outside "
                    "parallel/multihost.py — use "
                    "multihost.initialize(), the one reviewed call "
                    "site (env defaults, peer-death heartbeat "
                    "budget)"))
    return out


# device-kernel modules whose bodies must stay traceable end to end: a
# host materialization here silently reintroduces the round-trip the
# device decode plane exists to remove (the host boundary lives in
# format/rawpage.py, which orchestrates these kernels)
_KERNEL_MODULES = ("ops/decode.py",)


@rule("host-materialization",
      "host materialization inside a device-kernel module")
def check_host_materialization(model: ProgramModel) -> List[Finding]:
    """`np.asarray(...)` / `.tolist()` / `jax.device_get(...)` inside
    ops/decode.py — keep the kernel traceable and materialize at the
    format/rawpage.py boundary instead, or mark a reviewed exception
    with `# lint-ok: host-materialization <reason>`."""
    out = []
    for pkg_rel in _KERNEL_MODULES:
        mod = model.modules.get(pkg_rel)
        if mod is None:
            continue
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if not isinstance(fn, ast.Attribute):
                continue
            hit = (fn.attr == "asarray"
                   and isinstance(fn.value, ast.Name)
                   and fn.value.id in ("np", "numpy")) \
                or fn.attr == "tolist" \
                or (fn.attr == "device_get"
                    and isinstance(fn.value, ast.Name)
                    and fn.value.id == "jax")
            if hit:
                out.append(Finding(
                    "host-materialization", mod.rel, node.lineno,
                    "host materialization (np.asarray / .tolist() / "
                    "jax.device_get) inside a device-kernel module — "
                    "materialize at the format/rawpage.py boundary "
                    "instead"))
    return out
