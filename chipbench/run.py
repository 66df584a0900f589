#!/usr/bin/env python3
"""One cell, one run: set up, warm, measure for `--seconds`, check, print.

    python3 chipbench/run.py --workload agg_compact --seed 7 \
        --seconds 30 --trace 0

Everything is found by the names in `BENCHMARK.json`: the cell names a
configuration (`configs[].file`) and a traffic mix
(`chipbench/traffic/<traffic>.json`), the mix names its kind of
operation (`chipbench/operations/<kind>.py`), and every metric that
lists the cell has `chipbench/metrics/<metric>.json`, which names a
reader (`chipbench/readers/<reader>.py`).  There is no branch on a
cell's name here.

The last line of standard output is the result, one JSON object with
the keys `correct`, `attempted`, `failed`, `metrics`, `device` and, in a
traced run, `breakdown`.  The line before it, `[chipbench] info {...}`,
says how the run went (set-up split, compiles, routes, op seconds); both
go to `chiprun_out/chipbench/<cell>.seed<n>.trace<t>.json` too.

Without a TPU the command fails and prints no result.  `--rehearsal`
runs a cell at a tiny size on the CPU to try the code: its result line
names platform `cpu` and carries no metric value.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()       # set-up starts with the process

import argparse                 # noqa: E402
import importlib                # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402
import tempfile                 # noqa: E402
import traceback                # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)    # `python3 chipbench/run.py` from anywhere

OUT_DIR = os.path.join(ROOT, "chiprun_out", "chipbench")


class Run:
    """What one run knows: the operations and readers take it whole."""

    def __init__(self, args, cell, config, traffic):
        self.args = args
        self.cell = cell                # the manifest's entry
        self.config = config            # the configuration's file
        self.traffic = traffic          # the traffic mix's file
        self.data = config["rehearsal_data" if args.rehearsal else "data"]
        self.tmp = None                 # scratch directory of this run
        self.state = {}                 # the operation kind's own
        self.ops = []                   # completed: {"rows", "seconds"}
        self.failed = 0
        self.setup = {}                 # seconds per phase of set-up
        self.setup_s = None
        self.window_s = None
        self.counters = None            # meters.Delta over the window
        self.trace = None               # trace_reduce.Reduction
        self.device = None

    @property
    def rows(self) -> int:
        return sum(o["rows"] for o in self.ops)

    @property
    def op_seconds(self) -> float:
        return sum(o["seconds"] for o in self.ops)


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny size on the CPU; prints no metric value")
    return ap.parse_args(argv)


def _load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _metrics_of(manifest, kind, cell_name):
    return [m for m in manifest[kind]
            if cell_name in m.get("workloads", [cell_name])]


def _read_metrics(run, entries):
    out = {}
    for m in entries:
        spec = _load_json("chipbench", "metrics", m["name"] + ".json")
        reader = importlib.import_module(
            "chipbench.readers." + spec["reader"])
        value = reader.read(run, spec.get("params", {}))
        if value is not None:       # nothing to read: left out of the line
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _window(run, kind, jax):
    """Operations back to back until `--seconds`; the one that crosses
    the deadline completes and counts."""
    name = "chipbench." + run.cell["traffic"]
    t_open = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.window"):
        i = 0
        while time.perf_counter() - t_open < run.args.seconds:
            try:
                before = kind.before(run, i)
                with jax.profiler.TraceAnnotation(name):
                    t0 = time.perf_counter()
                    result = kind.operation(run, before)
                    seconds = time.perf_counter() - t0
                rows = kind.after(run, i, result)
                run.ops.append({"rows": rows, "seconds": seconds})
            except Exception:           # noqa: BLE001
                # a failed operation counts, and is never tried again
                traceback.print_exc()
                run.failed += 1
                if run.failed >= 3:
                    break
            i += 1
    run.window_s = time.perf_counter() - t_open


def main(argv=None) -> int:
    args = _parse(argv)
    try:
        manifest = _load_json("BENCHMARK.json")
        cell = next(w for w in manifest["workloads"]
                    if w["name"] == args.workload)
        cfg_entry = next(c for c in manifest["configs"]
                         if c["name"] == cell["config"])
        config = _load_json(cfg_entry["file"])
        traffic = _load_json("chipbench", "traffic",
                             cell["traffic"] + ".json")
    except (OSError, StopIteration, KeyError, ValueError) as e:
        sys.stderr.write(f"chipbench: cannot load cell "
                         f"{args.workload!r}: {e!r}\n")
        return 2

    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        import jax

        import paimon_tpu.ops  # noqa: F401  x64, and places the cache
        from paimon_tpu import native

        from chipbench import data, meters, trace_reduce
        kind = importlib.import_module(
            "chipbench.operations." + traffic["operation"])
    except ImportError as e:
        sys.stderr.write(f"chipbench: cannot import the system under "
                         f"test: {e!r}\n")
        return 2

    backend = jax.default_backend()
    if not args.rehearsal and (backend != "tpu"
                               or jax.device_count() < cell["chips"]):
        sys.stderr.write(
            f"chipbench: cell {cell['name']!r} needs {cell['chips']} TPU "
            f"chip(s); JAX's backend is {backend!r} with "
            f"{jax.device_count()} device(s).  (--rehearsal tries the "
            f"code on the CPU and measures nothing.)\n")
        return 3
    if native.load() is None:
        sys.stderr.write("chipbench: the native library did not build or "
                         "load (paimon_tpu/native)\n")
        return 4
    if data.forced_routes():
        sys.stderr.write(f"chipbench: {data.forced_routes()} set; a run "
                         f"with a pinned merge route measures nothing\n")
        return 5
    # the many small programs (segment reductions, slices) compile in
    # under JAX's default threshold and would compile again in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    dev0 = jax.devices()[0]
    run = Run(args, cell, config, traffic)
    run.device = {"platform": dev0.platform, "kind": dev0.device_kind,
                  "count": jax.device_count()}
    meter = meters.CompileMeter(jax)
    run.setup["import_s"] = time.perf_counter() - _T0
    trace_dir = os.path.join(
        OUT_DIR, "traces", f"{cell['name']}.seed{args.seed}")

    with tempfile.TemporaryDirectory(prefix="chipbench_") as tmp:
        run.tmp = tmp
        try:
            t = time.perf_counter()
            kind.prepare(run)           # data, table, reference
            run.setup["prepare_s"] = time.perf_counter() - t
            compiles = meter.snapshot()
            t = time.perf_counter()
            kind.warm(run)              # one whole operation, checked
            run.setup["warm_s"] = time.perf_counter() - t
            setup_compiles = meter.since(compiles)
            setup_compiles["in_prepare"] = compiles[0]
        except Exception:               # noqa: BLE001
            traceback.print_exc()
            sys.stderr.write("chipbench: set-up FAILED\n")
            return 1
        if data.forced_routes():        # the build's own pin leaked
            sys.stderr.write(f"chipbench: {data.forced_routes()} still "
                             f"set as the window opens\n")
            return 5

        window = meters.Window(meter)   # counters as the window opens
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        run.setup_s = time.perf_counter() - _T0
        try:
            _window(run, kind, jax)
        finally:
            if args.trace:
                jax.profiler.stop_trace()
        run.counters = window.close()

        correct = False
        try:
            kind.verify(run)            # raises on the first difference
            correct = run.failed == 0 and bool(run.ops)
        except Exception:               # noqa: BLE001
            traceback.print_exc()

    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use") or 0)
               for d in jax.local_devices())
    run.device["memory_peak_bytes"] = peak
    info = {"workload": cell["name"], "seed": args.seed,
            "seconds": args.seconds, "trace": args.trace,
            "rehearsal": args.rehearsal,
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            "setup": run.setup, "setup_s": run.setup_s,
            "setup_compiles": setup_compiles,
            "window_compiles": run.counters.compiles,
            "window_s": run.window_s, "operations": len(run.ops),
            "rows": run.rows,
            "op_seconds": [o["seconds"] for o in run.ops],
            "merge_paths": run.counters.paths,
            "routes": run.counters.routes,
            "link_bytes_per_s": run.counters.link,
            "state": {k: v for k, v in run.state.items()
                      if isinstance(v, (int, float, str))}}
    result = {"correct": correct, "attempted": len(run.ops) + run.failed,
              "failed": run.failed, "metrics": {}, "device": run.device}
    if args.trace:
        path = trace_reduce.find_xplane(trace_dir)
        run.trace = trace_reduce.reduce_file(path) if path else None
        if run.trace is not None:
            run.device["busy_s"] = run.trace.busy_s
            run.device["window_s"] = run.trace.window_s
            result["breakdown"] = run.trace.breakdown()
            info["module_seconds"] = run.trace.module_seconds
    if not args.rehearsal:              # a CPU number is never a metric
        result["metrics"] = _read_metrics(run, _metrics_of(
            manifest, "per_layer" if args.trace else "end_to_end",
            cell["name"]))
    info["wall_s"] = time.perf_counter() - _T0

    info_line = json.dumps(info)
    result_line = json.dumps(result)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"{cell['name']}.seed{args.seed}."
                           f"trace{args.trace}.json"), "w") as f:
        f.write(info_line + "\n" + result_line + "\n")
    print(f"[chipbench] info {info_line}", flush=True)
    print(result_line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
