#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the control and the
planted faults, at a cell's own size, one process for many seeds.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

The control is the plain reference put in the program's place and
computed one precision below the configuration's
(``control_precision``).  For a serving cell it reads, on the requests
the window of that seed would check, the gap of the token the lower
precision puts first.  For a training cell it reads the loss, first
gradient and update gaps of the lower-precision reference's first steps,
and those of the reference with each planted fault: half of the batch's
tokens left out of the mean, one label altered where the batch is
produced.  (A step that returns its state unchanged reads 1 by the
update gap's measure and needs no run.)  One JSON line per seed.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def serve_readings(config: dict, traffic: dict, seed: int,
                   seconds: float) -> dict:
    from bench.drivers import serve_plane as drv
    _, rows, sample, _ = drv.window_requests(traffic, config, seed, seconds)
    gaps = drv.reference_gaps(config, traffic, seed, rows,
                              {i: None for i in sample},
                              precision=config["control_precision"])
    return {"gap_max": drv.gap_max(gaps), "tokens": int(gaps.size)}


def train_readings(config: dict, traffic: dict, seed: int) -> dict:
    from bench.drivers.train_lm import ref_null_leaves
    from bench.refs import dense_lm as ref
    n = traffic["check_steps"]
    rows = ref.make_batches(config, traffic, seed, n)
    want = ref.train_steps(config, seed, rows, n)
    exclude = ref_null_leaves(want["grad_norm"])

    def gaps(got: dict) -> dict:
        return {"loss_gap": max(abs(a - b) / abs(b) for a, b in
                                zip(got["loss"], want["loss"])),
                "grad_gap": ref.worst_leaf_gap(got["grad_norm"],
                                               want["grad_norm"]),
                "update_gap": ref.worst_leaf_gap(got["change"],
                                                 want["change"], exclude)}

    out = {"control": gaps(ref.train_steps(
        config, seed, rows, n, config["control_precision"]))}
    half = rows.shape[2] // 2
    out["half_batch"] = gaps(ref.train_steps(config, seed,
                                             rows[:, :, :half + 1], n))
    altered = rows.at[:, 0, -1].set((rows[:, 0, -1] + 1)
                                    % config["vocab_size"])
    out["token_altered"] = gaps(ref.train_steps(config, seed, altered, n))
    out["excluded_leaves"] = sorted(exclude)
    return out


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
        if config["kind"] == "serve_plane":
            r = serve_readings(config, traffic, seed, args.seconds)
        else:
            r = train_readings(config, traffic, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "precision": config["control_precision"], **r}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
