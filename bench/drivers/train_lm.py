"""Cells of kind ``train_lm``: optimizer steps through the program's
``TrainSupervisor``, as ``launch/train.py`` drives it.

Set-up builds one supervisor with its compiled step and the state made
from the seed, and drives it through its first ``check_steps`` steps on
rows that all differ; those steps are what the reference follows.  The
same supervisor and state then run the window: steps until ``seconds``
have passed, each loss read back as the trainer logs it, the window
ended by the last step's result.  After it, the peak memory is read, the
program's state freed, and the reference run from the seed.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from ..lib.common import BenchError, Check, Run, memory_peak_bytes
from ..refs import dense_lm as ref

def model_config(config: dict):
    """The program's model configuration of ``program_arch`` with the
    sizes the configuration file states."""
    from repro.configs import get_config
    m = ref.dims(config)
    cfg = get_config(config["program_arch"]).replace(
        n_layers=m["layers"], d_model=m["d"], n_heads=m["h"],
        n_kv_heads=m["kv"], head_dim=m["hd"], d_ff=m["f"], vocab=m["v"],
        rms_eps=m["eps"], rope_theta=m["theta"])
    if cfg.ffn_act != "gelu" or cfg.ffn_gated or not cfg.tie_embeddings \
            or cfg.padded_vocab != cfg.vocab or cfg.moe is not None:
        raise BenchError(f"the program's {config['program_arch']} is not "
                         "the model the configuration states")
    return cfg


def check_layout(model, state) -> None:
    """The benchmark's state has the program's parameter layout."""
    import jax
    from repro.models import unzip
    want, _ = unzip(model.init(jax.random.PRNGKey(0), abstract=True))
    sig = lambda t: jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)),
                                 t)
    if sig(want) != sig(state["params"]):
        raise BenchError("parameter layout differs from the program's")


def run(cell: dict, config: dict, traffic: dict, seed: int,
        seconds: float, trace: bool, device: dict, tracer) -> Run:
    import jax
    from repro.models import Model
    from repro.optim import AdamWConfig
    from repro.training import SupervisorConfig, TrainSupervisor

    n_check = traffic["check_steps"]
    t_setup = time.perf_counter()
    model = Model(model_config(config))
    state = ref.make_state(config, seed)
    check_layout(model, state)
    # a fresh row for every step the window can take
    n_rows = n_check + int(np.ceil(seconds / traffic["min_step_s"])) + 1
    rows = ref.make_batches(config, traffic, seed, n_rows)
    batches = [ref.split(rows[i]) for i in range(n_rows)]
    opt_cfg = AdamWConfig(**config["optimizer"])
    sup = TrainSupervisor(model, opt_cfg, state, batches[0],
                          cfg=SupervisorConfig(), log_fn=lambda m: None)
    losses, grad_norms, change = [], None, None
    try:
        for i in range(n_check):
            with jax.profiler.TraceAnnotation("bench.train_step"):
                state, metrics = sup.step(state, batches[i])
                losses.append(float(metrics["loss"]))
            if i == 0:
                grad_norms = ref.program_grad_norms(state["opt"],
                                                    opt_cfg.b1)
        change = ref.program_change(state["opt"], config, seed)
        jax.block_until_ready(state)
        setup_s = time.perf_counter() - t_setup

        if trace:
            tracer.start()
        step, nonfinite = n_check, 0
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            while time.perf_counter() - t0 < seconds:
                if step >= n_rows:
                    raise BenchError("the window ran out of rows: steps "
                                     f"under {traffic['min_step_s']} s")
                with jax.profiler.TraceAnnotation("bench.train_step"):
                    state, metrics = sup.step(state, batches[step])
                    nonfinite += not np.isfinite(float(metrics["loss"]))
                step += 1
            jax.block_until_ready(state)
        window_s = time.perf_counter() - t0
        if trace:
            tracer.stop()
        stats = sup.stats()
        peak = memory_peak_bytes(device["devices"])
    finally:
        sup.close()
    del state, sup, batches, metrics
    gc.collect()
    want = ref.train_steps(config, seed, rows, n_check)
    del rows
    exclude = ref_null_leaves(want["grad_norm"])
    checks = [
        Check("loss_gap", max(abs(a - b) / abs(b) for a, b in
                              zip(losses, want["loss"])),
              traffic["limits"]["loss_gap"]),
        Check("grad_gap", ref.worst_leaf_gap(grad_norms,
                                             want["grad_norm"]),
              traffic["limits"]["grad_gap"]),
        Check("update_gap", ref.worst_leaf_gap(change, want["change"],
                                               exclude),
              traffic["limits"]["update_gap"]),
    ]
    steps = step - n_check
    tokens = steps * traffic["batch"] * traffic["seq"]
    values = {"steps": steps, "window_s": window_s,
              "tokens_per_s": tokens / window_s, "tokens": tokens,
              "losses": losses, "ref_losses": want["loss"],
              "excluded_leaves": sorted(exclude),
              "grad_norm_leaves": {k: [grad_norms[k], want["grad_norm"][k]]
                                   for k in want["grad_norm"]},
              "supervisor": {k: stats[k] for k in
                             ("step_faults", "retried_steps", "quarantines",
                              "sync_compiles", "bg_compiles")}}
    return Run(cell, config, traffic, device.get("peaks"), seconds,
               setup_s, attempted=steps, failed=nonfinite,
               memory_peak_bytes=peak, checks=checks, values=values)


def ref_null_leaves(grad_norms: dict) -> set:
    """Leaves whose reference gradient is nought to rounding (under a
    thousandth of the median leaf's): AdamW moves them by round-off
    alone, so their change is not compared."""
    med = float(np.median(list(grad_norms.values())))
    return {k for k, v in grad_norms.items() if v < 1e-3 * med}
