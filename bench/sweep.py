#!/usr/bin/env python3
"""The knee of a serving cell: its traffic at rising fixed rates through
one plane set up once, one window per rate.

    python bench/sweep.py --workload <cell> --seed <n> --seconds 5 \
        --rates 100 200 400 800

Prints one JSON line per rate: offered and completed requests, the
latency quantiles from the due time, the queue wait, the sender's
lateness and how long the last answer came after the window closed.  The
knee is the highest rate whose queue does not grow through the window;
a cell's traffic file fixes its rate at about four fifths of it.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench.drivers import serve_plane as drv
    from bench.lib.common import device_info, enable_compile_cache, \
        load_cell, quantile, rng
    cell, config, traffic = load_cell(args.workload)
    enable_compile_cache()
    device_info(cell["chips"])
    t = time.perf_counter()
    plane = drv.Plane(config, traffic, args.seed)
    try:
        plane.warm_shapes(rng(args.seed, "warm-shapes"))
        plane.fe.start()
        drv.warm_traffic(plane, traffic)
        plane.fe.drain(timeout=60.0)
        print(json.dumps({"setup_s": time.perf_counter() - t,
                          "impls": sorted(plane.impls())}), flush=True)
        for rate in args.rates:
            due, rows = drv.draw(traffic, config, rate, args.seconds,
                                 rng(args.seed, f"sweep-{rate}"))
            c0 = plane.rt.engine.compile_count
            off = drv.offer(plane, due, rows, args.seconds)
            t = drv.request_times(off)
            last = max((r.arrival_ts + r.timing["total_s"]
                        for r in off["sender"].requests
                        if r is not None and r.status == "ok"),
                       default=float("nan"))
            print(json.dumps({
                "rate": rate, "offered": len(rows),
                "completed_ok": t["completed_ok"],
                "p50_ms": quantile(t["latency_ms"], 0.5),
                "p99_ms": quantile(t["latency_ms"], 0.99),
                "queue_p99_ms": quantile(t["queue_wait_ms"], 0.99)
                if t["queue_wait_ms"] else None,
                "late_p99_ms": quantile(t["lateness_ms"], 0.99),
                "drain_after_close_s": last - off["closed"],
                "compiles": plane.rt.engine.compile_count - c0}),
                flush=True)
            plane.fe.drain(timeout=120.0)
    finally:
        plane.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
