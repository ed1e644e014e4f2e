"""The ``dsv2-skewed`` cell on the CPU at a small size: a whole run comes
out correct with its hot experts held here and its counters read; the
reference answers both ways at near ties; the fp8 control fails the
limit; the operation counts match the sizes worked out by hand."""
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from bench.lib import counts_mla_moe
from bench.lib.harness import reader, run_cell
from bench.refs import deepseek_v2 as ref
from bench_tiny import load_cell, spec

ROOT = Path(__file__).resolve().parents[2]
CELL = "dsv2-skewed"
# the configuration's shapes, cut to a small size: 32 routed experts in 8
# groups of 4, this chip holding group 0 (experts 0-3)
SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "intermediate_size": 96, "moe_intermediate_size": 32,
         "n_routed_experts": 4, "vocab_size": 256, "num_hidden_layers": 3,
         "deployment": {"chips_per_layer": 8, "router_experts": 32,
                        "held_first_expert": 0, "vocab_first_id": 0}}
# 4 held experts and 2 of group 1, as the cell's 0-3 and 20-21
SMALL_TRAFFIC = {"rate_per_s": 40.0,
                 "router_bias": {"experts": [0, 1, 2, 3, 4, 5],
                                 "value": 6.0}}


def small(tie=None):
    _, config, traffic = load_cell(CELL)
    config = {**config, **SMALL}
    if tie is not None:
        config["router_tie"] = {"value": tie}
    return config, {**traffic, **SMALL_TRAFFIC}


def test_small_run_is_correct_and_reads_its_counters():
    _, config, _ = load_cell(CELL)
    serving = {**config["serving"], "tick_s": 1.0}
    mp = pytest.MonkeyPatch()
    from bench.lib import harness
    mp.setattr(harness, "enable_compile_cache", lambda: None)
    try:
        result = run_cell(CELL, 2**31 + 11, 1.0, False, allow_cpu=True,
                          overrides={"config": {**SMALL, "serving": serving},
                                     "traffic": SMALL_TRAFFIC},
                          spec=spec(CELL))
    finally:
        mp.undo()
    run = result.pop("_run")
    assert result["correct"], result["compared"]
    assert run.values["hot_experts"] and \
        set(run.values["hot_experts"]) <= {0, 1, 2, 3}
    stats = run.values["moe_stats"]
    assert len(stats["slots"]) == 2
    # one hot set per MoE layer; set-up stopped at the first cycle whose
    # plan held every required site
    assert sorted(run.values["hot_sets"]) == ["router#0", "router#1"]
    need = {"batch_shape", "hot_cache", "moe_fastpath"}
    cycles = run.values["warm_cycles"]
    assert len(cycles) == run.values["warm_ticks"]
    assert need <= set(cycles[len(cycles) - 1]["impls"])
    assert not any(need <= set(c["impls"])
                   for i, c in cycles.items() if i < len(cycles) - 1)
    # 4 of each token's 6 slots land on held experts
    assert sum(stats["held_slots"]) * 6 == sum(stats["slots"]) * 4
    share = reader("moe_fast_share.serve")(run)
    assert share is not None and 0.0 <= share <= 100.0


def _answers(tie, seed=3):
    config, _ = small(tie)
    # every expert held, so that any swap shows; 4 favoured experts, so
    # that 2 of each token's 6 come from its third group
    config["n_routed_experts"] = 32
    weights = ref.make_weights(config, seed, {"experts": [0, 1, 2, 3],
                                              "value": 6.0})
    embed = ref.make_embedding(config, seed)
    toks = np.random.default_rng(seed).integers(0, 32, (4, 16))
    return np.asarray(ref.forward(weights, embed, toks, config))


def test_without_a_near_tie_the_answers_agree():
    out = _answers(tie=0.0)
    assert out.shape[0] == ref.N_ANSWERS
    for other in out[1:]:
        np.testing.assert_array_equal(out[0], other)


def test_a_token_routed_the_other_way_at_a_tie_has_no_gap():
    wide = _answers(tie=1e9)      # every boundary taken the other way
    assert not np.array_equal(wide[0], wide[1])
    assert not np.array_equal(wide[0], wide[2])
    for other in wide[1:]:
        assert np.all(ref.token_gaps(other, wide) == 0.0)


def test_fp8_control_fails_the_limit():
    path = ROOT / "bench" / "control_lm.py"
    mod_spec = importlib.util.spec_from_file_location("control_lm", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    config, traffic = small()
    r = mod.readings(config, traffic, seed=2**31 + 7, seconds=2.0)
    assert r["gap_max"] > traffic["limits"]["gap_max"], r


def test_counts_at_the_published_widths():
    c = json.loads((ROOT / "bench" / "configs" /
                    "deepseek-v2-ep8-7l.json").read_text())
    # one MLA block holds 149.2 M parameters (q_a 7.9, q_b 37.7, kv_a 2.9,
    # kv_b 16.8, o 83.9): 2 operations each a token
    mla_params = (5120 * 1536 + 1536 * 128 * 192 + 5120 * 576
                  + 512 * 128 * 256 + 128 * 128 * 5120)
    assert mla_params == pytest.approx(149.2e6, rel=1e-3)
    s = 16
    want = (7 * (s * 2 * mla_params + 2 * (s * (s + 1) // 2) * 128 * 320)
            + s * 2 * 3 * 5120 * 12288                     # dense layer
            + 6 * s * 2 * (5120 * 160 + 3 * 5120 * 3072)   # router, shared
            + s * 2 * 5120 * 12800)                        # unembedding
    assert counts_mla_moe.mla_moe_request_flops(c, s) == want
    assert counts_mla_moe.routed_slot_flops(c) == 2 * 3 * 5120 * 1536
    # 16 requests a step with 4 hot held experts a token (the sizing):
    # about 1.1 TFLOP, the attention projections 0.54 of it
    step = 16 * counts_mla_moe.mla_moe_request_flops(c, s) \
        + 256 * 4 * 6 * counts_mla_moe.routed_slot_flops(c)
    assert step == pytest.approx(1.1e12, rel=0.1)
    assert 256 * 7 * 2 * mla_params == pytest.approx(0.54e12, rel=0.02)
