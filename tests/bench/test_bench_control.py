"""The control: the plain reference computed one precision below the
configuration's, in the program's place, must come out not correct by
at least one of the cell's compared numbers (here at a small size; its
readings at the cells' own sizes are in PERF.md)."""
import importlib.util
from pathlib import Path

from bench_tiny import SERVE, TRAIN, TRAIN_TRAFFIC, load_cell

ROOT = Path(__file__).resolve().parents[2]


def control():
    spec = importlib.util.spec_from_file_location(
        "bench_control", ROOT / "bench" / "control.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_control_fails_the_limit():
    _, config, traffic = load_cell("phi35moe-skewed")
    config = {**config, **SERVE}
    traffic = {**traffic, "rate_per_s": 40.0,
               "token_ids": min(traffic["token_ids"], SERVE["vocab_size"])}
    r = control().serve_readings(config, traffic,
                                 seed=2**31 + 3, seconds=2.0)
    assert r["gap_max"] > traffic["limits"]["gap_max"], r


def test_train_control_and_faults_fail_a_limit():
    _, config, traffic = load_cell("starcoder2-train")
    config = {**config, **TRAIN}
    traffic = {**traffic, **TRAIN_TRAFFIC}
    r = control().train_readings(config, traffic, seed=9)
    limits = traffic["limits"]
    for case in ("control", "half_batch"):
        assert any(r[case][k] > limits[k] for k in limits), (case, r)
