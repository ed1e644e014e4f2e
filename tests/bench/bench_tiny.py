"""Small sizes of the benchmark's configurations, for runs on the CPU."""
import json
from pathlib import Path

import pytest

SERVE = {"hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "vocab_size": 2048,
         "num_hidden_layers": 1}
TRAIN = {"hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "vocab_size": 256, "num_hidden_layers": 2}
TRAIN_TRAFFIC = {"seq": 64, "batch": 2, "min_step_s": 0.0005}
# cells out of BENCHMARK.json whose drivers are still tested: name -> file
PARKED = {"starcoder2-train":
          Path(__file__).parent / "data" / "starcoder2-train.json"}


def spec(cell):
    """BENCHMARK.json, with ``cell``'s entries added where it is parked."""
    from bench.lib import common
    out = common.benchmark_spec()
    if cell in PARKED:
        parked = json.loads(PARKED[cell].read_text())
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            out[key] = out[key] + parked[key]
    return out


def load_cell(cell):
    from bench.lib import common
    return common.load_cell(cell, spec(cell))


def run(cell, seed, seconds=1.0, trace=False, traffic=None, config=None):
    """One run of ``cell`` at the small size on the CPU, without the
    persistent compile cache."""
    from bench.lib import harness
    _, file_config, file_traffic = load_cell(cell)
    serve = file_config["kind"] == "serve_plane"
    small = TRAIN_TRAFFIC
    if serve:
        small = {"rate_per_s": 40.0,
                 "token_ids": min(file_traffic["token_ids"],
                                  SERVE["vocab_size"])}
    overrides = {"config": {**(SERVE if serve else TRAIN), **(config or {})},
                 "traffic": {**small, **(traffic or {})}}
    mp = pytest.MonkeyPatch()
    mp.setattr(harness, "enable_compile_cache", lambda: None)
    try:
        return harness.run_cell(cell, seed, seconds, trace,
                                allow_cpu=True, overrides=overrides,
                                spec=spec(cell))
    finally:
        mp.undo()
