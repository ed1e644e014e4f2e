"""DeepSeek-V2 on the serving path against a plain float32 reference
(``tests/deepseek_v2_ref.py``), on seeded random weights at a small
size: MLA with q-LoRA, both norms and YaRN; group-limited routing; the
expert layer that holds one chip's share; the share-aware fast path; and
the whole serve step through ``MorpheusRuntime``.  phi3.5-moe's routing
and fast path stay as they were."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepseek_v2_ref as ref
from repro.core import EngineConfig, MorpheusRuntime, SketchConfig
from repro.core.passes.branch_inject import moe_ffn_hotpath
from repro.models.attention import init_mla_attention, mla_forward
from repro.models.config import LayerSpec, MLAConfig, ModelConfig, \
    MoEConfig, YaRNConfig
from repro.models.layers import ffn, init_rmsnorm
from repro.models.moe import init_moe, moe_ffn_local, route, route_for, \
    route_grouped
from repro.models.params import Initializer, unzip
from repro.models.transformer import init_layer
from repro.serving import LMServeConfig, build_lm_tables, \
    make_lm_serve_step

D, E = 64, 32


def tiny(q_lora=24, yarn=True, held=tuple(range(4)), scoring="softmax",
         n_group=8, top_k=6):
    return ModelConfig(
        d_model=D, n_heads=4, n_kv_heads=4, n_layers=3, vocab=128, d_ff=32,
        rope_scaling=YaRNConfig() if yarn else None,
        mla=MLAConfig(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                      v_head_dim=16, q_lora_rank=q_lora),
        moe=MoEConfig(num_experts=E, top_k=top_k, expert_d_ff=32,
                      num_shared=2, shared_d_ff=16, scoring=scoring,
                      n_group=n_group, topk_group=3 if n_group > 1 else 1,
                      routed_scaling=16.0 if scoring == "softmax" else 1.0,
                      held=held),
        first_k_dense=1, first_dense_d_ff=96)


def _init(fn, cfg, seed):
    vals, _ = unzip(fn(Initializer(jax.random.PRNGKey(seed),
                                   dtype=jnp.float32), cfg))
    return vals


def _plane_params(cfg, seed):
    """Weights in the serve step's layout from the model's own init:
    the leading dense layers, then MoE layers holding ``cfg.moe.held``."""
    ini = Initializer(jax.random.PRNGKey(seed), dtype=jnp.float32)
    layers = [init_layer(ini, cfg, LayerSpec(kind="attn", ffn="dense"),
                         d_ff_override=cfg.first_dense_d_ff)
              for _ in range(cfg.first_k_dense)]
    layers += [init_layer(ini, cfg, LayerSpec(kind="attn", ffn="moe"))
               for _ in range(cfg.n_layers - cfg.first_k_dense)]
    vals, _ = unzip({"layers": layers,
                     "final_norm": init_rmsnorm(ini, cfg.d_model),
                     "unembed": ini.normal((cfg.d_model, cfg.vocab),
                                           ("embed", "vocab"))})
    return vals


def _scale_norms(tree, seed):
    """Norm scales away from 1, so that a missing norm shows."""
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map_with_path(
        lambda path, v: jnp.asarray(rng.uniform(0.5, 1.5, v.shape),
                                    v.dtype)
        if "norm" in jax.tree_util.keystr(path) else v, tree)


# (q-LoRA rank, YaRN, request length, key block)
MLA_CASES = {"qlora-yarn-s16": (24, True, 16, 512),
             "qlora-yarn-s40-block16": (24, True, 40, 16),
             "fullrank-plain-s16": (0, False, 16, 512)}


@pytest.mark.parametrize("q_lora,yarn,seq,block", MLA_CASES.values(),
                         ids=MLA_CASES.keys())
def test_mla_matches_reference(q_lora, yarn, seq, block):
    cfg = tiny(q_lora=q_lora, yarn=yarn)
    p = _scale_norms(_init(init_mla_attention, cfg, seq), seq)
    x = jax.random.normal(jax.random.PRNGKey(seq + 1), (2, seq, D))
    got, _ = mla_forward(p, cfg, x, jnp.arange(seq, dtype=jnp.int32),
                         block=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.mla(p, cfg, x)),
                               rtol=1e-4, atol=1e-5)


def _tie_logits(rng):
    """Router logits whose 3rd and 4th groups tie on their largest, and
    whose 4th group would change the top 6."""
    lg = rng.normal(-6.0, 0.5, (8, E)).astype(np.float32)
    lg[:, 0], lg[:, 4], lg[:, 8] = 5.0, 4.0, 3.0         # groups 0, 1, 2
    lg[:, 12], lg[:, 13], lg[:, 14] = 3.0, 2.9, 2.8      # group 3 ties 2
    return lg


ROUTE_CASES = {"random": lambda rng: rng.normal(0, 2, (64, E)).astype(
                   np.float32),
               "groups-3-and-4-tie": _tie_logits}


@pytest.mark.parametrize("make", ROUTE_CASES.values(), ids=ROUTE_CASES.keys())
def test_grouped_routing_matches_masked_topk(make):
    moe = tiny().moe
    logits = make(np.random.default_rng(0))
    eye = jnp.eye(logits.shape[0], dtype=jnp.float32)
    gates, ids, _ = route_grouped(jnp.asarray(logits), eye, moe)
    want_g, want_ids = ref.route(logits, moe)
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    np.testing.assert_allclose(np.asarray(gates), want_g, rtol=1e-6)


@pytest.mark.parametrize("scoring,n_group,top_k", [("softmax", 8, 6),
                                                  ("topk_softmax", 1, 2)],
                         ids=["deepseek-group-limited", "mixtral-top2"])
def test_held_shares_sum_to_the_uncut_layer(scoring, n_group, top_k):
    """The 8 chips' shares of one layer, each computing only its own
    experts' part, with the shared experts (computed on every chip)
    counted once, add up to the whole layer."""
    cfg = tiny(held=(), scoring=scoring, n_group=n_group, top_k=top_k)
    p = _init(init_moe, cfg, 7)
    x = jax.random.normal(jax.random.PRNGKey(8), (48, D))
    total = ffn(p["shared"], x)
    for k in range(8):
        held = tuple(range(4 * k, 4 * k + 4))
        share = {**p, **{w: p[w][jnp.asarray(held)]
                         for w in ("w1", "w3", "w2")}}
        y, _ = moe_ffn_local(share, x, dataclasses.replace(cfg.moe,
                                                           held=held))
        total = total + y
    want = ref.moe_layer(p, cfg.moe, x, tuple(range(E)))
    np.testing.assert_allclose(np.asarray(total), want, rtol=1e-4,
                               atol=1e-5)


# (experts the router favours, hot set planned, the fast branch taken)
FAST_CASES = {"hit": ((0, 1, 2, 3, 4, 5), (0, 1, 2, 3), 1),
              "held-miss": ((0, 1, 2, 3, 4, 5), (0, 1, 2), 0),
              "remote-slots-only": ((8, 9, 12, 13, 16, 17), (0,), 1)}


@pytest.mark.parametrize("favoured,hot,hit", FAST_CASES.values(),
                         ids=FAST_CASES.keys())
def test_share_aware_fast_branch(favoured, hot, hit):
    cfg = tiny()
    p = _init(init_moe, cfg, 3)
    bias = np.zeros(E, np.float32)
    bias[list(favoured)] = 8.0
    p["b_router"] = jnp.asarray(bias)
    x = jax.random.normal(jax.random.PRNGKey(4), (64, D))
    y, m = jax.jit(lambda x: moe_ffn_hotpath(p, x, cfg, hot))(x)
    want, _ = moe_ffn_local(p, x, cfg.moe)
    assert int(m["fastpath_hit"]) == hit
    # float32 sums of the same products, fused differently
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(want + ffn(p["shared"], x)),
        ref.moe_layer(p, cfg.moe, x, cfg.moe.held_ids), rtol=1e-4,
        atol=1e-5)


def test_serve_step_through_runtime_matches_reference():
    cfg = tiny()
    scfg = LMServeConfig(model=cfg, n_slots=16)
    params = _scale_norms(_plane_params(cfg, 11), 11)
    bias = np.zeros(E, np.float32)
    bias[[0, 1, 2, 3, 4, 5]] = 6.0
    for lp in params["layers"][cfg.first_k_dense:]:
        lp["ffn"]["b_router"] = jnp.asarray(bias)
    tables = build_lm_tables(scfg)
    embed = np.asarray(tables["vocab_embed"].fields["vec"])
    rng = np.random.default_rng(12)
    batch = {"tokens": jnp.asarray(rng.integers(0, 16, (4, 8)), jnp.int32),
             "class_id": jnp.zeros(4, jnp.int32),
             "slot": jnp.arange(4, dtype=jnp.int32)}
    rt = MorpheusRuntime(make_lm_serve_step(scfg), tables, params, batch,
                         cfg=EngineConfig(
                             features={"vision_enabled": False,
                                       "track_sessions": True},
                             sketch=SketchConfig(sample_every=1, max_hot=32,
                                                 hot_coverage=0.8),
                             moe_router_table="router", mesh=None))
    try:
        want = np.asarray(ref.forward(params, embed, batch["tokens"], cfg))
        for _ in range(3):
            generic = np.asarray(rt.step(batch))
        rt.recompile(block=True)
        hot = {s: spec.hot_keys for s, spec in rt.plan.sites
               if spec.impl == "moe_fastpath"}
        assert len(hot) == 2 and all(set(k) == {0, 1, 2, 3}
                                     for k in hot.values()), hot
        stats0 = {k: np.asarray(v) for k, v in
                  rt.state.tables["moe_stats"].items()}
        spec = np.asarray(rt.step(batch))
        stats1 = {k: np.asarray(v) for k, v in
                  rt.state.tables["moe_stats"].items()}
    finally:
        rt.close()
    for got in (generic, spec):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    # one step on the fast branch in each MoE layer, 4 of 6 slots held
    np.testing.assert_array_equal(stats1["fast_steps"] - stats0["fast_steps"],
                                  [1, 1])
    np.testing.assert_array_equal(
        (stats1["held_slots"] - stats0["held_slots"]) * 6,
        (stats1["slots"] - stats0["slots"]) * 4)


def test_phi_route_and_fast_branch_unchanged():
    """Mixtral-style routing is the old ``route`` to the bit, and phi's
    fast branch takes the hit and matches the slow one (bitwise inside
    one program: tests/test_conformance.py)."""
    cfg = ModelConfig(d_model=D, moe=MoEConfig(num_experts=16, top_k=2,
                                               expert_d_ff=128))
    p = _init(init_moe, cfg, 5)
    bias = np.zeros(16, np.float32)
    bias[[0, 1, 2]] = 6.0
    p["b_router"] = jnp.asarray(bias)
    x = jax.random.normal(jax.random.PRNGKey(6), (64, D))
    for a, b in zip(route_for(p, x, cfg.moe),
                    route(p["w_router"], x, 2, p["b_router"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    y, m = jax.jit(lambda x: moe_ffn_hotpath(p, x, cfg, (0, 1, 2)))(x)
    want, _ = jax.jit(lambda x: moe_ffn_local(p, x, cfg.moe))(x)
    assert int(m["fastpath_hit"]) == 1
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
