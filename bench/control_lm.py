#!/usr/bin/env python3
"""The control of a ``serve_lm`` cell: the plain reference computed one
precision below the configuration's (``control_precision``), in the
program's place, on the requests the window of each seed would check.

    python bench/control_lm.py --workload <cell> --seconds <s> --seeds 1 2

One JSON line per seed, with the gap of the token the lower precision
puts first (``gap_max``), to hold against the cell's limit.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(config: dict, traffic: dict, seed: int, seconds: float
             ) -> dict:
    from bench.drivers import serve_lm as drv
    _, rows, sample, _ = drv.window_requests(traffic, config, seed, seconds)
    gaps = drv.reference_gaps(config, traffic, seed, rows,
                              {i: None for i in sample},
                              precision=config["control_precision"])
    return {"gap_max": drv.gap_max(gaps), "tokens": int(gaps.size)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench.lib.common import device_info, enable_compile_cache, \
        load_cell
    cell, config, traffic = load_cell(args.workload)
    enable_compile_cache()
    device_info(cell["chips"])
    for seed in args.seeds:
        r = readings(config, traffic, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "precision": config["control_precision"], **r}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
