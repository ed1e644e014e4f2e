"""Cells of kind ``serve_lm``: open-loop requests through the serving
plane of a ``models/`` configuration (``repro.serving.lm``: MLA, routed
experts of which this chip holds a share, shared experts) under the
Morpheus runtime.

The timed path, the set-up and the window are those of ``serve_plane``
(its ``offer``, ``request_times``, ``replay`` and ``check_sessions`` are
used as they are): ``ServingFrontend.submit`` -> ``DynamicBatcher`` ->
``MorpheusRuntime.step_many`` on the active executable.  What differs is
the plane (DeepSeek-V2's weights, bfloat16, from the seed), a longer
set-up allowance for its compiles, the reference
(``bench/refs/deepseek_v2.py``), and three readings: the ``moe_stats``
counters before and after the window, read as the sessions table is; the
fast path's hot experts by MoE layer, which must be held here; and what
each warm cycle of set-up compiled and planned.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from ..lib.common import BenchError, Check, Run, memory_peak_bytes, rng
from ..refs import deepseek_v2 as ref
from . import serve_plane as sp
# draw, offer and request_times are also what bench/sweep.py drives
from .serve_plane import check_sessions, draw, gap_max, offer, payloads, \
    request_times, replay, stack, window_requests  # noqa: F401

WAIT_AFTER_CLOSE_S = sp.WAIT_AFTER_CLOSE_S
# seven full-width layers compile about ten times as long as the phi
# plane's one; the cycles run their compiles to the end either way
WARM_TIMEOUT_S = 1200.0
# the fewest: set-up still cycles until the plan is built from traffic
# and holds every required site; a further cycle re-plans from a fresh
# sketch whose candidate ring rarely holds the same hot keys twice, and
# so compiles every window shape again for traffic that has not changed
WARM_CYCLES = 1


def model_config(config: dict):
    """The ``ModelConfig`` the configuration file states, holding this
    chip's routed experts."""
    from repro.models.config import MLAConfig, ModelConfig, MoEConfig, \
        YaRNConfig
    dep, rope = config["deployment"], config["rope_scaling"]
    first = dep["held_first_expert"]
    return ModelConfig(
        name=config["name"], family="moe",
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["moe_intermediate_size"], vocab=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        rope_scaling=YaRNConfig(
            factor=float(rope["factor"]),
            original_max_position=rope["original_max_position_embeddings"],
            beta_fast=float(rope["beta_fast"]),
            beta_slow=float(rope["beta_slow"]),
            mscale=float(rope["mscale"]),
            mscale_all_dim=float(rope["mscale_all_dim"])),
        rms_eps=config["rms_norm_eps"],
        mla=MLAConfig(kv_lora_rank=config["kv_lora_rank"],
                      qk_nope_dim=config["qk_nope_head_dim"],
                      qk_rope_dim=config["qk_rope_head_dim"],
                      v_head_dim=config["v_head_dim"],
                      q_lora_rank=config["q_lora_rank"]),
        moe=MoEConfig(num_experts=dep["router_experts"],
                      top_k=config["num_experts_per_tok"],
                      expert_d_ff=config["moe_intermediate_size"],
                      num_shared=config["n_shared_experts"],
                      shared_d_ff=config["moe_intermediate_size"],
                      scoring=config["scoring_func"],
                      n_group=config["n_group"],
                      topk_group=config["topk_group"],
                      routed_scaling=float(config["routed_scaling_factor"]),
                      held=tuple(range(first,
                                       first + config["n_routed_experts"]))),
        first_k_dense=config["first_k_dense_replace"],
        first_dense_d_ff=config["intermediate_size"])


def serve_config(config: dict):
    from repro.serving import LMServeConfig
    a = config["assumed"]
    return LMServeConfig(model=model_config(config), n_classes=a["classes"],
                         n_adapters=a["adapters"],
                         n_slots=a["session_slots"])


class Plane(sp.Plane):
    """One serving plane of a ``models/`` configuration, built through
    the program's entry points; warmed, read and closed as the
    ``serve_plane`` plane is."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 params=None):
        import jax
        from repro.core import ControllerConfig, EngineConfig, \
            MorpheusController, MorpheusRuntime, SketchConfig
        from repro.serving import build_lm_tables, make_lm_serve_step
        from repro.serving.frontend import FrontendConfig, ServingFrontend

        s = config["serving"]
        self.config, self.traffic, self.seed = config, traffic, seed
        self.cfg = serve_config(config)
        if params is None:
            params = ref.make_weights(config, seed, traffic["router_bias"])
        embed = np.asarray(ref.make_embedding(config, seed))
        jax.block_until_ready(params)
        self.controller = MorpheusController(
            ControllerConfig(workers=s["recompile_workers"]))
        ecfg = EngineConfig(
            features={"vision_enabled": False, "track_sessions": True},
            sketch=SketchConfig(**s["sketch"]),
            moe_router_table="router", mesh=None, cache_ns="bench-serve-lm")
        example = stack(payloads(traffic, config, s["max_batch"],
                                 rng(seed, "example")))
        self.rt = MorpheusRuntime(make_lm_serve_step(self.cfg),
                                  build_lm_tables(self.cfg), params,
                                  example, cfg=ecfg,
                                  controller=self.controller,
                                  plane_id="plane-0")
        self.rt.control_update("vocab_embed", {"vec": embed})
        del embed, params
        self.fcfg = FrontendConfig(
            capacity=s["queue_capacity"], max_batch=s["max_batch"],
            ladder=tuple(s["ladder"]), max_wait_s=s["max_wait_ms"] * 1e-3,
            window_k_max=s["window_k_max"], inflight=s["inflight"],
            default_slo_s=s["deadline_s"])
        self.fe = ServingFrontend(self.rt, self.fcfg, keep_outputs=True)
        self.tick_s = float(s["tick_s"])

    def moe_stats(self) -> Dict[str, np.ndarray]:
        t = self.rt.state.tables["moe_stats"]
        return {k: np.asarray(v) for k, v in t.items()}

    def hot_experts(self) -> List[int]:
        """Every expert a ``moe_fastpath`` site of the plan holds hot."""
        return sorted({int(k) for keys in self.hot_sets().values()
                       for k in keys})

    def hot_sets(self) -> Dict[str, List[int]]:
        """Each ``moe_fastpath`` site's hot experts, by site."""
        return {sid: [int(k) for k in spec.hot_keys]
                for sid, spec in self.rt.plan.sites
                if spec.impl == "moe_fastpath"}


def warm_traffic(plane: Plane, traffic: dict) -> dict:
    """``serve_plane.warm_traffic`` with this kind's allowance and cycle
    count, recording what each cycle left: the compiles it ran, the
    plan's sites and each MoE layer's hot experts."""
    from repro.core import plan_batch_shape
    need = set(traffic.get("require_impls", []))
    t_end = time.monotonic() + WARM_TIMEOUT_S
    gen = rng(sp.WARM_SEED, "warm-traffic")
    cycles = []

    def send() -> None:
        due, rows = draw(traffic, plane.config, traffic["rate_per_s"],
                         plane.tick_s, gen)
        offer(plane, due, rows, plane.tick_s)

    while time.monotonic() < t_end:
        send()
        c0, t0 = plane.rt.engine.compile_count, time.monotonic()
        plane.controller.schedule_all()
        plane.controller.drain()
        cycles.append({"compiles": plane.rt.engine.compile_count - c0,
                       "s": round(time.monotonic() - t0, 1),
                       "impls": sorted(plane.impls()),
                       "hot": plane.hot_sets()})
        if len(cycles) >= WARM_CYCLES \
                and plan_batch_shape(plane.rt.plan) is not None \
                and need <= plane.impls():
            send()
            return {"ticks": len(cycles), "cycles": cycles}
    raise BenchError(f"no plan from traffic within {WARM_TIMEOUT_S:.0f}"
                        f" s: cycles {cycles}, required {sorted(need)}")


def reference_gaps(config: dict, traffic: dict, seed: int,
                   rows: List[dict], outputs: Dict[int, np.ndarray],
                   precision: str = "float32", block: int = 16,
                   weights=None) -> np.ndarray:
    """Gaps of the served tokens of ``outputs`` against the reference,
    in blocks of ``block`` requests; with ``precision`` below the
    configuration's, the gaps of that precision's own greedy tokens (the
    control).  ``weights``: the plane's own, where it still holds them
    (two copies do not fit one chip beside each other)."""
    import jax
    if weights is None:
        weights = ref.make_weights(config, seed, traffic["router_bias"])
    embed = ref.make_embedding(config, seed)
    idx = sorted(outputs)
    gaps = []
    for s in range(0, len(idx), block):
        ids = idx[s:s + block]
        toks = np.stack([rows[i]["tokens"] for i in ids])
        want = np.asarray(ref.forward(weights, embed, toks, config))
        if precision == "float32":
            served = np.stack([outputs[i] for i in ids])
        else:
            served = np.asarray(ref.forward(weights, embed, toks, config,
                                            precision)[0])
        gaps.append(ref.token_gaps(served, want).ravel())
    del weights, embed
    jax.clear_caches()
    return np.concatenate(gaps) if gaps else np.zeros(0)


def run(cell: dict, config: dict, traffic: dict, seed: int,
        seconds: float, trace: bool, device: dict, tracer) -> Run:
    import jax
    t_setup = time.perf_counter()
    weights = ref.make_weights(config, seed, traffic["router_bias"])
    plane = Plane(config, traffic, seed, weights)
    try:
        plane.warm_shapes(rng(seed, "warm-shapes"))
        plane.fe.start()
        warm = warm_traffic(plane, traffic)
        plane.fe.drain(timeout=WAIT_AFTER_CLOSE_S)
        if trace:
            # the generic replay after the window must not compile there
            jax.block_until_ready(plane.rt.run_generic(stack(payloads(
                traffic, config, plane.fcfg.ladder_resolved()[-1],
                rng(seed, "warm-generic")))))
        setup_s = time.perf_counter() - t_setup
        stats0 = plane.rt.stats.snapshot()
        compiles0 = plane.rt.engine.compile_count
        sess0 = plane.sessions()
        moe0 = plane.moe_stats()

        due, rows, sample, last = window_requests(traffic, config, seed,
                                                  seconds)
        n = len(rows)
        if trace:
            tracer.start()
        offered = offer(plane, due, rows, seconds, keep=set(sample),
                        annotate=sp.annotation)
        window_compiles = plane.rt.engine.compile_count - compiles0
        stats1 = plane.rt.stats.snapshot()
        plane.controller.drain()
        impls = sorted(plane.impls())
        hot, hot_sets = plane.hot_experts(), plane.hot_sets()
        sess1 = plane.sessions()
        moe1 = plane.moe_stats()
        extra = {}
        if trace:
            extra = replay(plane, rows, traffic["replay_batches"],
                           sp.annotation)
            tracer.stop()
        failures = plane.failures()
        peak = memory_peak_bytes(device["devices"])
        reqs = offered["sender"].requests
        outputs = {i: np.asarray(reqs[i].output) for i in sample
                   if reqs[i] is not None and reqs[i].status == "ok"
                   and reqs[i].output is not None}
        bad_count, bad_token, checked = check_sessions(
            sess0, sess1, rows, reqs, last, sample)
    finally:
        plane.close()
    times = request_times(offered)
    del plane, offered
    gc.collect()
    gaps = reference_gaps(config, traffic, seed, rows, outputs,
                          weights=weights)
    del weights
    delta = {k: stats1[k] - stats0[k] for k in
             ("steps", "deopt_steps", "degraded_steps", "instr_steps",
              "pad_rows", "batches_formed", "requests_completed",
              "cache_misses")}
    moe = {k: (moe1[k] - moe0[k]).tolist() for k in moe0}
    generic = (delta["deopt_steps"] + delta["degraded_steps"]) \
        / max(delta["steps"], 1)
    missing = n - times["completed_ok"]
    need = set(traffic.get("require_impls", []))
    held = set(serve_config(config).model.moe.held_ids)
    checks = [Check("missing", missing, 0),
              Check("gap_max", gap_max(gaps), traffic["limits"]["gap_max"]),
              Check("session_counts_bad", bad_count, 0),
              Check("last_token_bad", bad_token, 0),
              Check("serving_failures", len(failures), 0),
              Check("impls_missing", len(need - set(impls)), 0),
              Check("hot_experts_not_held", len(set(hot) - held), 0),
              Check("generic_share", generic,
                    traffic["limits"]["generic_share"])]
    values = {**times, **extra, **delta,
              "requests": n, "request_tokens": config["assumed"][
                  "request_tokens"],
              "window_compiles": window_compiles,
              "impls": impls, "hot_experts": hot,
              "hot_sets": hot_sets, "warm_ticks": warm["ticks"],
              "warm_cycles": dict(enumerate(warm["cycles"])),
              "moe_stats": moe,
              "sessions_checked": checked, "gaps": gaps,
              "failures": failures}
    return Run(cell, config, traffic, device.get("peaks"), seconds,
               setup_s, attempted=n, failed=missing,
               memory_peak_bytes=peak, checks=checks, values=values)
