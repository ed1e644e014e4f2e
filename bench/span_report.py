#!/usr/bin/env python3
"""Where a serving cell's time goes, read from the program's own spans.

    python3 bench/span_report.py --workload <cell> --seed <n> \\
        [--out span_report.json]

Sets a ``serve_plane`` cell up once, as ``bench/run.py`` does, and then
traces up to ``WINDOWS`` windows of the benchmark's ``run_seconds`` each
(window ``i`` offers the requests of seed ``n + i``, keeping the answers
of its checked sample as ``bench/run.py`` does), stopping after the
first window whose device sat idle for ``STOP_GAP_MS`` or longer at a
stretch.  For each window it reports:

* every ``morpheus.*`` span seen: how many, and the stats they carry;
* the four span metrics (``bench/metrics/``) and the device idle share;
  the metrics again on the trace as ``bench/run.py`` loads it
  (``metrics_as_bench``: every Python thread on one line);
* per window size ``k``: how many windows, and the quantiles of the
  host time after their device work that ``fanback_p99_ms`` reads;
* each device idle gap of 50 ms or more, with what every host thread
  was in during it (its innermost event, program span or not);
* the latency of the median requests, split into sender lateness,
  submit, queue wait, fill, dispatch, the wait behind the window in
  flight, the window's own steps (device busy, then idle until the
  batcher retired it), the copy to the host, and what is left.

Then it traces one forced recompile cycle, and reports the cost of a
span with the profiler off and whether the device's operations carry
the data plane's named scopes.  Needs the chip; writes one JSON object
to ``--out`` and prints a summary.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import spans as S  # noqa: E402
from bench.lib import trace as T  # noqa: E402
from bench.lib.common import quantile  # noqa: E402

METRICS = ("batcher_blocked_share.serve", "fanback_p99_ms",
           "dispatch_host_ms.serve", "gc_pause_ms.serve")
SCOPES = ("attention", "moe.router", "moe.hot", "moe.generic", "tables.",
          "hot_gather/")
MS = 1e6
WINDOWS = 10            # traced windows at most
STOP_GAP_MS = 100.0     # a device idle gap this long ends the search
REPORT_GAP_MS = 50.0    # idle gaps this long are attributed


class ThreadTracer:
    """The profiler over one stretch, each host thread on a line of its
    own (``spans.load_thread_events``)."""

    def __enter__(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="span-report-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        try:
            self.view = T.TraceView(S.load_thread_events(
                T.find_xplane(self.dir)))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return False


def as_bench(view):
    """``view`` with each host line named by its thread name alone, as
    :func:`bench.lib.trace.load_events` names it."""
    return T.TraceView([
        e if e.plane.startswith(T.DEVICE_PLANE_PREFIX)
        else dataclasses.replace(e, line=e.line.rsplit("#", 1)[0])
        for e in view.events])


def span_cost_us(n: int = 200_000):
    """Microseconds per span with two stats, profiler off (None for a
    program without spans)."""
    try:
        from repro.core.tracing import span
    except ImportError:
        return None
    t = time.perf_counter()
    for i in range(n):
        with span("cost", w=i, role="spec"):
            pass
    return (time.perf_counter() - t) / n * 1e6


def inventory(view, lo: float, hi: float) -> dict:
    """Per span name: how many, the stats they carry, and the duration
    and self time (ms) of those that start in [lo, hi]."""
    program = S.program_spans(view.events)
    tree = S.SpanTree(program)
    out: dict = {}
    for e in program:
        d = out.setdefault(e.name, {"count": 0, "stats": {}, "ms": [],
                                    "self_ms": []})
        d["count"] += 1
        for k, v in e.stats.items():
            d["stats"].setdefault(k, str(v))
        if lo <= e.start_ns < hi:
            d["ms"].append(e.dur_ns / MS)
            d["self_ms"].append(tree.self_ns(e) / MS)
    for d in out.values():
        for key in ("ms", "self_ms"):
            v = sorted(d.pop(key))
            d[key] = {"n": len(v), "sum": sum(v),
                      "mean": statistics.fmean(v) if v else None,
                      "p50": quantile(v, 0.5) if v else None,
                      "p99": quantile(v, 0.99) if v else None,
                      "max": v[-1] if v else None}
    return out


def innermost_by_line(view, lo: float, hi: float) -> dict:
    """Per host line, the innermost event open over [lo, hi] that
    overlaps it most, with the overlap in ms."""
    out: dict = {}
    for e, ov in S.innermost_open(view.events, lo, hi, prefix=""):
        if e.line not in out:           # longest overlap first
            out[e.line] = {"name": e.name, "overlap_ms": ov / MS,
                           "stats": {k: str(v) for k, v in
                                     e.stats.items()}}
    return out


def gaps(view, lo: float, hi: float) -> list:
    if not view.device_planes:
        return []
    out = []
    for s, e in view.idle_gaps(lo, hi, view.device_planes[0]):
        if e - s < REPORT_GAP_MS * MS:
            continue
        out.append({
            "at_ms": (s - lo) / MS, "ms": (e - s) / MS,
            "program": [[x.name, ov / MS, {k: str(v) for k, v in
                                           x.stats.items()}]
                        for x, ov in S.innermost_open(view.events, s, e)],
            "threads": innermost_by_line(view, s, e)})
    return sorted(out, key=lambda g: -g["ms"])


def fanback_by_k(view, lo: float, hi: float) -> dict:
    """Per window size ``k`` (the stat of the window's ``pack`` span):
    the windows retired in [lo, hi] and the quantiles of their
    ``retire.d2h`` + ``retire.fanback`` time (ms), the quantity that
    ``fanback_p99_ms`` takes the 99th percentile of over all windows."""
    tree = S.SpanTree(S.program_spans(view.events, "morpheus.batcher."))
    pumps = S.by_w(S.named(tree.spans, "morpheus.batcher.pump"))
    retires = S.starting_in(S.named(tree.spans, "morpheus.batcher.retire"),
                            lo, hi)
    per_k: dict = {}
    for w, r in S.by_w(retires).items():
        packs = tree.children(pumps[w], "morpheus.batcher.pack") \
            if w in pumps else []
        k = str(packs[0].stats.get("k")) if packs else "?"
        per_k.setdefault(k, []).append(
            sum(c.dur_ns for c in tree.children(r)
                if c.name in ("morpheus.batcher.retire.d2h",
                              "morpheus.batcher.retire.fanback")) / MS)
    return {k: {"windows": len(v), "p50": quantile(v, 0.5),
                "p99": quantile(v, 0.99), "max": max(v)}
            for k, v in sorted(per_k.items())}


def decompose(view, offered: dict, latency_ms: list) -> dict:
    """Each request's latency split along the spans of its window."""
    sender = offered["sender"]
    late = sender.lateness_s() * 1e3
    submits = sorted(view.annotations("bench.submit"))
    if len(submits) != len(sender.requests):
        return {"error": f"{len(submits)} submit spans for "
                         f"{len(sender.requests)} requests"}
    tree = S.SpanTree(S.program_spans(view.events))

    def child(e, name):
        kids = tree.children(e, name)
        return kids[0] if kids else None

    pumps = S.by_w(S.named(tree.spans, "morpheus.batcher.pump"))
    retires = S.by_w(S.named(tree.spans, "morpheus.batcher.retire"))
    rows, prev_done, i = [], None, 0
    for w in sorted(pumps):
        p, r = pumps[w], retires.get(w)
        fill, pack = child(p, "morpheus.batcher.fill"), \
            child(p, "morpheus.batcher.pack")
        disp = child(p, "morpheus.runtime.step_many")
        if pack is None or disp is None or r is None:
            continue
        wait = child(r, "morpheus.batcher.retire.wait")
        d2h = child(r, "morpheus.batcher.retire.d2h")
        fan = child(r, "morpheus.batcher.retire.fanback")
        for _ in range(int(pack.stats["rows"])):
            if i >= len(submits):
                break
            s0, s1 = submits[i]
            c = {"sender_late": float(late[i]), "submit": (s1 - s0) / MS}
            t = s1
            for name, end in (("queue", fill.start_ns),
                              ("fill", fill.end_ns),
                              ("dispatch", disp.end_ns),
                              ("behind_inflight", prev_done or 0.0)):
                c[name] = max(0.0, end - t) / MS
                t = max(t, end)
            busy = view.busy_ns(t, wait.end_ns) if view.device_planes \
                else 0.0
            c["own_steps_busy"] = busy / MS
            c["own_steps_idle"] = max(0.0, wait.end_ns - t - busy) / MS
            t = max(t, wait.end_ns)
            c["d2h"] = max(0.0, d2h.end_ns - t) / MS if d2h else 0.0
            c["residual"] = latency_ms[i] - sum(c.values())
            c["fanback_after"] = fan.dur_ns / MS
            c["latency"] = latency_ms[i]
            c["w"] = w
            rows.append(c)
            i += 1
        prev_done = wait.end_ns
    if not rows:
        return {"error": "no request matched a window"}
    lat = sorted(c["latency"] for c in rows)
    q = statistics.quantiles(lat, n=20)
    band = [c for c in rows if q[8] <= c["latency"] <= q[10]]
    keys = [k for k in rows[0] if k != "w"]
    return {"requests": len(rows),
            "median_band": {k: statistics.fmean(c[k] for c in band)
                            for k in keys},
            "band_requests": len(band),
            "medians": {k: statistics.median(c[k] for c in rows)
                        for k in keys}}


def scope_check(view) -> dict:
    """Which device lines exist, which stats the operations carry, and
    whether any device event names a scope of the data plane."""
    dev = [e for e in view.events
           if e.plane.startswith(T.DEVICE_PLANE_PREFIX)]
    lines = sorted({e.line for e in dev})
    stat_keys = sorted({k for e in dev for k in e.stats})
    found = {}
    for scope in SCOPES:
        hits = [e for e in dev if scope in " ".join(
            [e.name] + [str(v) for v in e.stats.values()])]
        found[scope] = {"events": len(hits),
                        "example": hits[0].name[:160] if hits else None,
                        "lines": sorted({e.line for e in hits})}
    return {"device_lines": lines, "stat_keys": stat_keys,
            "scopes": found}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default="span_report.json")
    args = ap.parse_args(argv)

    from bench.drivers import serve_plane as drv
    from bench.lib import harness
    from bench.lib.common import benchmark_spec, device_info, \
        enable_compile_cache, load_cell, rng
    from bench.lib.readers import idle_share

    report: dict = {"span_cost_off_us": span_cost_us()}
    cell, config, traffic = load_cell(args.workload)
    seconds = float(benchmark_spec()["run_seconds"])
    enable_compile_cache()
    device_info(cell["chips"])
    t0 = time.perf_counter()
    plane = drv.Plane(config, traffic, args.seed)
    windows = []
    try:
        plane.warm_shapes(rng(args.seed, "warm-shapes"))
        plane.fe.start()
        drv.warm_traffic(plane, traffic)
        plane.fe.drain(timeout=drv.WAIT_AFTER_CLOSE_S)
        report["setup_s"] = time.perf_counter() - t0
        readers = {m: harness.reader(m) for m in METRICS}
        for k in range(WINDOWS):
            due, rows, sample, _ = drv.window_requests(
                traffic, config, args.seed + k, seconds)
            with ThreadTracer() as tr:
                offered = drv.offer(plane, due, rows, seconds,
                                    keep=set(sample),
                                    annotate=drv.annotation)
            view = tr.view
            times = drv.request_times(offered)
            run = SimpleNamespace(trace=view)
            bench_run = SimpleNamespace(trace=as_bench(view))
            lo, hi = view.span("bench.window")
            w = {"seed": args.seed + k,
                 "p50_ms": quantile(times["latency_ms"], 0.5),
                 "p99_ms": quantile(times["latency_ms"], 0.99),
                 "idle_share": idle_share(run),
                 "metrics": {m: r(run) for m, r in readers.items()},
                 "metrics_as_bench": {m: r(bench_run)
                                      for m, r in readers.items()},
                 "fanback_by_k": fanback_by_k(view, lo, hi),
                 "gaps": gaps(view, lo, hi),
                 "decomposition": decompose(view, offered,
                                            times["latency_ms"]),
                 "spans": inventory(view, lo, hi)}
            if k == 0:
                report["scope_check"] = scope_check(view)
            windows.append(w)
            print(json.dumps({key: w[key] for key in
                              ("seed", "p50_ms", "p99_ms", "idle_share",
                               "metrics", "metrics_as_bench",
                               "fanback_by_k")}), flush=True)
            del view, tr, offered, run, bench_run
            if w["gaps"] and w["gaps"][0]["ms"] >= STOP_GAP_MS:
                break
        with ThreadTracer() as tr:
            plane.rt.recompile(block=True)
        report["cycle"] = [
            [e.name, e.dur_ns / MS, {k: str(v) for k, v in
                                     e.stats.items()}]
            for e in S.program_spans(tr.view.events)
            if e.name.startswith(("morpheus.cycle", "morpheus.engine",
                                  "morpheus.snapshot"))]
    finally:
        plane.close()
    report["windows"] = windows
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(report, indent=1, default=str))
    summary = {"span_cost_off_us": report["span_cost_off_us"],
               "setup_s": report.get("setup_s"),
               "windows": len(windows),
               "longest_gaps_ms": [w["gaps"][0]["ms"] if w["gaps"]
                                   else 0.0 for w in windows],
               "cycle_spans": len(report["cycle"])}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
