"""Run one cell of ``BENCHMARK.json`` once and print the result line.

Everything that belongs to one cell is found by name:

* the configuration: the file ``BENCHMARK.json`` names for it;
* the traffic mix: ``bench/traffic/<cell>.json``, whose ``driver`` names
  a module ``bench/drivers/<driver>.py`` with ``setup``, ``window``,
  ``release`` and ``check``;
* each per-layer metric: ``bench/metrics/<metric>.py`` with ``read(ctx)``,
  which returns a number or None (nothing to read: left out of the line).

A run: find a TPU with the cell's chips (else exit nonzero, print no
result), set up (timed as ``setup_s``), run the window (end-to-end
metrics with ``--trace 0``; with ``--trace 1`` a traced window of the
traffic's ``trace_seconds`` and the per-layer metrics), read the peak
device memory, free the program's state, compare with the float32
reference, print each compared number beside its limit on stderr, and
print one JSON line last on stdout.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
SRC = os.path.join(ROOT, "src")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class CellError(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def with_pending(bench: Dict[str, Any], workload: str) -> Dict[str, Any]:
    """``bench`` with the entries of ``bench/pending/<workload>.json``
    added: a cell whose files are kept but that ``BENCHMARK.json`` does
    not list yet (see PERF.md), for the tools and the tests.  ``run.py``
    never adds them."""
    path = os.path.join(BENCH_DIR, "pending", f"{workload}.json")
    if any(w["name"] == workload for w in bench["workloads"]) or \
            not os.path.isfile(path):
        return bench
    out = dict(bench)
    for key, entries in load_json(path).items():
        out[key] = list(bench[key]) + entries
    return out


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as fh:
        return json.load(fh)


def load_module(kind: str, name: str):
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise CellError(f"no {kind} module {path}")
    mod_name = f"chipbench_{kind}_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(bench: Dict[str, Any], workload: str) -> Dict[str, Any]:
    """The cell's entry, configuration, traffic and metric entries."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = configs[cell["config"]]
    config = load_json(os.path.join(ROOT, conf["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic", f"{workload}.json"))

    def listed(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if listed(m)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if listed(m) and m["moves"] in moved]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": per_layer}


class Ctx:
    """What a driver and the metric readers share for one run."""

    def __init__(self, name: str, seed: int, seconds: float, trace: bool,
                 cell: Dict[str, Any], config: Dict[str, Any],
                 traffic: Dict[str, Any]):
        self.name, self.seed, self.seconds, self.trace = (
            name, seed, seconds, trace)
        self.cell, self.config, self.traffic = cell, config, traffic
        self.chips = int(cell.get("chips", 1))
        self.devices: List[Any] = []
        self.peak = None
        self.stash: Dict[str, Any] = {}
        self.length = seconds          # the window the driver runs
        self.window_s = 0.0            # the window it ran, host clock
        self.trace_data = None
        self.trace_window = None       # (start_ns, end_ns) on the trace
        self.trace_window_s = None
        self.busy_s = None
        self.t_start = time.perf_counter()

    def mark(self, what: str) -> None:
        """Log a set-up phase's end, in seconds from the process start."""
        log(f"{what} at {time.perf_counter() - self.t_start:.3f}s")


class CompileCounter:
    """Counts backend compilations through ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.n = 0

        def on_event(name, _secs, **_kw):
            if name.endswith("backend_compile_duration"):
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)


def setup_jax(require_tpu: bool, chips: int):
    """Find the chips and put the compile cache at a fixed path inside the
    checkout (whatever the environment says: the path is part of the
    cache's key).  ``require_tpu=False`` is the tests' path: any backend,
    and no cache."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise CellError(f"no {SRC}/repro: run from a checkout of the "
                        "repository")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    import jax

    devs = jax.devices()
    if require_tpu:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        if devs[0].platform != "tpu":
            raise CellError(f"JAX found no TPU (platform "
                            f"{devs[0].platform!r}); the benchmark measures "
                            "nothing on another backend")
        if len(devs) < chips:
            raise CellError(f"the cell needs {chips} chips; JAX sees "
                            f"{len(devs)}")
    return devs


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def reduce_trace(ctx: Ctx) -> Optional[Dict[str, Any]]:
    from chipbench import trace as tr

    data = tr.load(TRACE_DIR)
    ctx.trace_data = data
    ctx.trace_window = tr.window_of(data)
    ctx.trace_window_s = (ctx.trace_window[1] - ctx.trace_window[0]) * 1e-9
    busy = tr.busy_ns(data, ctx.trace_window)
    used = sorted(busy)[:ctx.chips]
    ctx.busy_s = sum(busy[p] for p in used) / len(used) * 1e-9
    return {"device_ops": tr.top_ops(data, ctx.trace_window),
            "idle_gaps": tr.idle_gaps(data, ctx.trace_window)}


def prepare(workload: str, seed: int, seconds: float, trace: bool, *,
            require_tpu: bool = True,
            overrides: Optional[Dict[str, Dict[str, Any]]] = None,
            bench: Optional[Dict[str, Any]] = None):
    """Resolve the cell, find the chips, and build its context and driver.
    ``overrides`` replace configuration or traffic entries (the tests'
    small sizes)."""
    bench = bench if bench is not None else load_benchmark()
    res = resolve(bench, workload)
    for part, extra in (overrides or {}).items():
        res[part].update(extra)
    ctx = Ctx(workload, seed, seconds, trace, res["cell"], res["config"],
              res["traffic"])
    if trace:
        ctx.length = min(seconds, res["traffic"].get("trace_seconds",
                                                     seconds))
    driver = load_module("drivers", res["traffic"]["driver"])
    devs = setup_jax(require_tpu, ctx.chips)
    ctx.devices = devs[:ctx.chips]
    from chipbench import peaks

    ctx.peak = peaks.peak_for(devs[0].device_kind) if require_tpu else \
        peaks.PEAKS["TPU v5 lite"]
    return ctx, driver, res, devs


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             overrides: Optional[Dict[str, Dict[str, Any]]] = None,
             bench: Optional[Dict[str, Any]] = None,
             on_trace=None) -> Dict[str, Any]:
    """One run of one cell (see the module docstring); ``on_trace(ctx)``
    sees the reduced trace before the readers do."""
    ctx, driver, res, devs = prepare(workload, seed, seconds, trace,
                                     require_tpu=require_tpu,
                                     overrides=overrides, bench=bench)
    d0 = devs[0]
    import jax

    compiles = CompileCounter()
    log(f"{len(devs)} device(s) ready at {time.perf_counter() - t_start:.3f}s")
    ctx.t_start = t_start
    driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    log(f"setup {setup_s:.3f}s; {compiles.n} compilations in set-up")

    n_before = compiles.n
    breakdown = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # no per-Python-call events
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("chipbench.window"):
            e2e = driver.window(ctx)
    finally:
        if trace:
            jax.profiler.stop_trace()
    in_window = compiles.n - n_before
    log(f"window {ctx.window_s:.3f}s; {in_window} compilations inside the "
        "window")
    peak_bytes = memory_peak(ctx.devices)

    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        try:
            breakdown = reduce_trace(ctx)
            if on_trace is not None:
                on_trace(ctx)
        finally:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        for m in res["per_layer"]:
            value = load_module("metrics", m["name"]).read(ctx)
            if value is None:
                log(f"metric {m['name']}: nothing to read")
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in res["end_to_end"]:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": "s"}
            elif m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    driver.release(ctx)
    checks = driver.check(ctx)
    attempted = int(ctx.stash.get("attempted", 0))
    failed = int(ctx.stash.get("failed", 0))
    for c in checks:
        log(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}) "
            + ("ok" if c["ok"] else "FAIL"))
    out: Dict[str, Any] = {
        "correct": bool(checks) and all(c["ok"] for c in checks)
        and failed == 0,
        "attempted": attempted, "failed": failed, "metrics": metrics,
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(devs), "memory_peak_bytes": peak_bytes},
    }
    if trace:
        out["device"]["busy_s"] = ctx.busy_s
        out["device"]["window_s"] = ctx.trace_window_s
        out["breakdown"] = breakdown
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None
         ) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start)
    except CellError as e:
        log(f"refused: {e}")
        return 3
    print(json.dumps(out), flush=True)
    return 0
