"""Run one cell of the benchmark once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json. Its configuration
file, its traffic mix (`bench/traffic/<traffic>.json`), its check limits
(`bench/cells/<name>.json`), its reference (`bench/refs/<reference>.py`,
named by the configuration) and each per-layer metric's reader
(`bench/metrics/<metric>.py`) are found by name, so a new cell, mix,
configuration or metric is new files and entries only. All that is
particular to an architecture lives in its configuration file (published
keys, `run.arch`, `run.program`, the routing lines `run.routes` expects)
and its reference module, which beside the plain forward gives the sizes,
the weights' leaf tables and the FLOPs per token; the drivers and
`program.py` name no architecture.

With `--trace 0` the result line carries the cell's end-to-end metrics;
with `--trace 1` the first seconds of the window are traced and the line
carries the per-layer metrics. Both check what the timed path produced
against the plain reference once the window has closed. The last line on
standard output is the JSON result; the numbers compared, each beside its
limit, are the last lines on standard error and the last key of the
result. Without a TPU listed in bench/peaks.json the run exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_SECONDS = 5.0


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root, name):
    return load_module(os.path.join(root, "bench", "metrics", f"{name}.py"),
                       "bench_metric_" + name.replace(".", "_")
                       .replace("-", "_")).read


class Cell:
    """Everything one run of one cell knows; the drivers fill in what
    they measured."""

    def __init__(self, spec, workload, seed, seconds, trace, t_start=None,
                 root=ROOT):
        self.spec = spec
        self.workload = workload
        self.root = root
        cfg_entry = find(spec["configs"], workload["config"], "config")
        self.cfg = load_json(root, cfg_entry["file"])
        self.mix = load_json(root, "bench", "traffic",
                             f"{workload['traffic']}.json")
        self.limits = load_json(root, "bench", "cells",
                                f"{workload['name']}.json")
        self.sizes = self.reference().sizes(self.cfg)
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.trace_seconds = min(self.seconds, TRACE_SECONDS)
        self.t_start = T_START if t_start is None else t_start
        self.chips = int(workload["chips"])
        # None: the program's readings are judged. A mode of the reference
        # ("fp8"): that control is put in the program's place and judged.
        self.control = None
        self.readings = None
        self.t0 = None
        self.kernel_calls = []
        self.model_flops = None
        self.step_span = None
        self.memory_peak = 0
        self.window_compiles = 0
        self.result = None
        self.trace_dir = None

    # -- what the drivers call ---------------------------------------------
    @property
    def key(self):
        from bench.weights import seed_key
        return seed_key(self.seed)

    def weight_maker(self):
        from bench.weights import weight_maker
        if not hasattr(self, "_maker"):
            self._maker = weight_maker(self.reference(), self.sizes,
                                       self.cfg["torch_dtype"])
        return self._maker

    def reference(self):
        name = self.cfg["reference"]
        if not hasattr(self, "_ref"):
            self._ref = load_module(
                os.path.join(self.root, "bench", "refs", f"{name}.py"),
                "bench_ref_" + name.replace(".", "_").replace("-", "_"))
        return self._ref

    def read_memory(self):
        from bench.device import peak_bytes
        return peak_bytes(self.chips)

    def set_result(self, *, attempted, failed, metrics):
        self.result = {"attempted": int(attempted), "failed": int(failed),
                       "metrics": metrics}

    def start_trace(self):
        import jax
        self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(self.trace_dir)
        ann = jax.profiler.TraceAnnotation("bench.traced")
        ann.__enter__()
        return ann

    def stop_trace(self, ann):
        import jax
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()


def driver(mix):
    return importlib.import_module(f"bench.{mix['kind']}")


def compare(checks, limits):
    """[(name, value, limit, ok)] for every number with a limit."""
    out = []
    for name, lim in limits["limits"].items():
        if name not in checks:
            raise KeyError(f"check {name!r} has a limit but no reading")
        v = checks[name]
        out.append((name, v, lim, math.isfinite(v) and v <= lim))
    return out


def judge(cell, drv, readings):
    """(correct, [(name, value, limit, ok)], checks) of the readings, or of
    the control in the program's place where `cell.control` names one."""
    checks = drv.check(cell, readings)
    compared = compare(checks, cell.limits)
    return all(ok for *_, ok in compared), compared, checks


def per_layer(cell, label):
    """The per-layer metrics of this cell that find something to read."""
    from bench import device
    from bench import trace as T
    tr = T.load(T.find_xplane(cell.trace_dir))
    r = argparse.Namespace(trace=tr, peak=device.peaks(label["kind"]),
                           chips=cell.chips, sizes=cell.sizes,
                           kernel_calls=cell.kernel_calls,
                           model_flops=cell.model_flops,
                           step_span=cell.step_span)
    e2e = set(cell.result["metrics"])
    out = {}
    for m in cell.spec["per_layer"]:
        cells = m.get("workloads")
        if cells is None and m["moves"] not in e2e:
            continue
        if cells is not None and cell.workload["name"] not in cells:
            continue
        v = load_reader(cell.root, m["name"])(r)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    lo, hi = tr.window
    label = dict(label, busy_s=T.busy_seconds(tr), window_s=hi - lo)
    breakdown = {"device_ops": T.top_ops(tr), "idle_gaps": T.idle_gaps(tr)}
    return out, label, breakdown


def run_cell(cell, label):
    """Drive the cell, check it, and build the result line's dict."""
    from bench.device import BenchError, say
    drv = driver(cell.mix)
    readings = cell.readings = drv.run(cell)
    setup_s = cell.t0 - cell.t_start
    if cell.window_compiles:
        raise BenchError(f"{cell.window_compiles} compiles inside the window")
    res = dict(cell.result)
    metrics = {}
    if not cell.trace:
        for m in cell.spec["end_to_end"]:
            if cell.workload["name"] not in m.get(
                    "workloads", [cell.workload["name"]]):
                continue
            v = setup_s if m["name"] == "setup_s" else \
                res["metrics"][m["name"]]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(label, memory_peak_bytes=cell.memory_peak)
    breakdown = None
    if cell.trace:
        metrics, device, breakdown = per_layer(cell, device)
        shutil.rmtree(cell.trace_dir, ignore_errors=True)
    correct, compared, checks = judge(cell, drv, readings)
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n: {"value": v, "limit": lim}
                     for n, v, lim, _ in compared}
    say(f"compile: {cell.clock.seconds:.3f}s over {cell.clock.compiles} "
        f"compiles, {cell.clock.cache_hits} persistent-cache hits")
    for n, v, lim, ok in compared:
        say(f"check {n} {v!r} limit {lim!r} {'ok' if ok else 'FAILED'}")
    return out, checks


def prepare(name, seed, seconds, trace, t_start=None, watch=None):
    """Guard the device, point the compile cache into the checkout, and
    build the cell; raises BenchError without a TPU from peaks.json.
    `watch` is the (RouteLog, CompileClock) pair of an earlier cell in the
    same process: the program logs each route once per process."""
    from bench.device import (CompileClock, RouteLog, device_label,
                              drop_reroutes, say, BenchError)
    spec = load_json(ROOT, "BENCHMARK.json")
    workload = find(spec["workloads"], name, "workload")
    cell = Cell(spec, workload, seed, seconds, trace, t_start=t_start)
    for var in drop_reroutes():
        say(f"ignoring {var}: the benchmark runs the default route")
    label = device_label(cell.chips)
    from bench import program
    program.compile_cache(CACHE_DIR)
    from repro.kernels.ops import use_interpret
    if use_interpret():
        raise BenchError("Pallas kernels would run in interpret mode")
    cell.routes, cell.clock = watch or (RouteLog(), CompileClock())
    say(f"cell {name}: config {workload['config']}, traffic "
        f"{workload['traffic']}, seed {seed}, {seconds}s, trace {trace}; "
        f"device {label}")
    return cell, label


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.device import BenchError
    try:
        cell, label = prepare(args.workload, args.seed, args.seconds,
                              args.trace)
        out, _ = run_cell(cell, label)
    except (BenchError, KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
