#!/usr/bin/env python3
"""The chip benchmark of the Morpheus serving and training paths.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the machine it is started on: sets it
up from the seed (weights, tables, compiled programs; JAX's compile cache
lives in ``<checkout>/.jax_cache``), measures for ``--seconds``, checks
the answers of the timed path against a plain reference, and prints one
JSON object as the last line of standard output.  ``--trace 0`` reports
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiler trace of the window.  Without a TPU, with fewer chips than the
cell asks for, or on a device kind that ``bench/peaks.json`` lacks, it
exits nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


if __name__ == "__main__":
    from bench.lib.harness import main
    sys.exit(main(parse_args()))
