"""One run of one cell: device check, the cell's driver, the metric
readers, the result line.

The cell names its configuration and traffic in BENCHMARK.json; the
configuration's ``kind`` names the driver (``bench/drivers/<kind>.py``),
and each metric is read by ``bench/metrics/<metric name>.py``.  A new
cell or metric is new files and new entries, never an edit here.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
from typing import Optional

from . import trace as tracelib
from .common import BENCH_DIR, BenchError, Run, enable_compile_cache, \
    device_info, load_cell, metrics_for


class Tracer:
    """The JAX profiler over one window, into a temporary directory;
    Python function tracing stays off."""

    def __init__(self):
        self.dir: Optional[str] = None
        self.view: Optional[tracelib.TraceView] = None

    def start(self) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()
        try:
            path = tracelib.find_xplane(self.dir)
            self.view = tracelib.TraceView(tracelib.load_events(path))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def driver(kind: str):
    return importlib.import_module(f"bench.drivers.{kind}")


def reader(metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             allow_cpu: bool = False, overrides: Optional[dict] = None,
             spec: Optional[dict] = None) -> dict:
    """Run one cell and return its result object.  Tests only:
    ``overrides`` replaces keys of the configuration and the traffic
    (``{"config": {...}, "traffic": {...}}``), and ``spec`` stands for
    BENCHMARK.json."""
    cell, config, traffic = load_cell(name, spec)
    if overrides:
        config = {**config, **overrides.get("config", {})}
        traffic = {**traffic, **overrides.get("traffic", {})}
    enable_compile_cache()
    device = device_info(cell["chips"], allow_cpu=allow_cpu)
    tracer = Tracer()
    run: Run = driver(config["kind"]).run(cell, config, traffic, seed,
                                          seconds, trace, device, tracer)
    run.trace = tracer.view
    metrics = {}
    for m in metrics_for(name, trace, spec):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": dev}
    if trace and run.trace is not None:
        lo, hi = run.trace.span("bench.window")
        dev["busy_s"] = run.trace.busy_ns(lo, hi) * 1e-9
        dev["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = tracelib.breakdown(run.trace, lo, hi)
    result["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                          for c in run.checks}
    result["_run"] = run
    return result


def main(args) -> int:
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except BenchError as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    run = result.pop("_run")
    for k, v in run.values.items():
        if isinstance(v, (int, float, str, dict)) or k in (
                "impls", "failures", "losses", "ref_losses"):
            print(f"[bench] {k}: {v}", file=sys.stderr)
    print(f"[bench] setup_s: {run.setup_s}", file=sys.stderr)
    for name, c in result["compared"].items():
        print(f"[bench] compared {name}: {c['value']} (limit "
              f"{c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
