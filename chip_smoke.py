#!/usr/bin/env python3
"""Bring-up check: the Morpheus serve loop and a train step on a TPU.

    python chip_smoke.py             # one chip: serve phase, train phase
    python chip_smoke.py --chips 4   # four chips: sharded serving only

Every phase goes through the entry points a user calls and checks what
comes out; any failed check exits nonzero.  Without a TPU the script
exits nonzero before any phase, and it prints its result line only when
every phase passed:

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

**Serve phase** (``launch/serve.py:run_frontend_serve``): open-loop
Poisson arrivals -> ``ServingFrontend`` -> dynamic batcher -> seqlock
dispatch, with ``MorpheusController`` recompiling beside the trace.  The
plane is the repo's stand-in serving plane (``serving/dataplane.py``),
not phi3.5-moe itself: it has phi3.5-moe-42b-a6.6b's published widths
(d_model 4096, 32 heads, d_ff 6400, vocab 32064, 16 experts, top-2) with
its depth cut to 1 layer, float32 weights from a seed.  The skewed trace
must make the plan claim the ``hot_cache`` site (Pallas ``hot_gather``)
on ``vocab_embed`` and the ``moe_fastpath`` site on ``router``; the
specialized executable must hold the kernel and match the generic one.

**Train phase** (``launch/train.py`` -> ``TrainSupervisor``):
starcoder2-3b at every published width, depth cut to 4 layers
(``--layers 4``), a few AdamW steps at sequence 2048.

**--chips 4**: the serve trace on ``data_plane_mesh()`` over all four
devices (batch and sketches sharded, sketches psum-merged at plan time),
then the same trace on one device in the same process; the two plans
must pick the same hot keys and experts and their outputs must agree.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Specialized-vs-generic tolerance, per output row (one token's logits):
# ||spec - generic|| <= SERVE_RTOL * ||generic||.  The two executables
# contract differently (the fast path runs dense over the hot experts'
# sliced weights, the generic path a ragged dispatch over all 16), and on
# the TPU float32 matmuls run as bf16 passes (2^-8 relative per product),
# so they are not bitwise equal.  A wrong embedding row or a wrong expert
# changes a row's logits by order 1, far above this bound.
SERVE_RTOL = 2e-2

TRAIN_ARCH, TRAIN_LAYERS, TRAIN_SEQ, TRAIN_BATCH, TRAIN_STEPS = \
    "starcoder2-3b", 4, 2048, 1, 3


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", -1))


def serve_config():
    from repro.serving import ServeConfig
    return ServeConfig(d_model=4096, n_layers=1, n_heads=32, d_ff=6400,
                       vocab=32064, n_experts=16, top_k=2)


def fixed_batch(cfg):
    import jax
    from repro.serving import make_synthetic_batch
    return make_synthetic_batch(cfg, jax.random.PRNGKey(7), 8,
                                locality="high")


def row_rel_err(out, ref) -> float:
    """Largest per-row relative L2 error of ``out`` against ``ref``."""
    import numpy as np
    out = np.asarray(out, np.float64).reshape(-1, out.shape[-1])
    ref = np.asarray(ref, np.float64).reshape(-1, ref.shape[-1])
    num = np.linalg.norm(out - ref, axis=1)
    den = np.maximum(np.linalg.norm(ref, axis=1), 1e-30)
    return float((num / den).max())


def replan_from(rt, batch) -> None:
    """Re-plan ``rt`` from the sketches of exactly one sampled step on
    ``batch``: a deterministic plan input, where the trace's sampled
    windows depend on timing.  The first recompile opens a fresh sketch
    window (and reinstalls the instrumented twin if the sampler had
    swapped it out), the step records ``batch`` into it, and the second
    plans from it."""
    rt.sampler.pin(1)
    rt.recompile(block=True)
    rt.step(batch)
    rt.recompile(block=True)


def run_serve(cfg, mesh: str, replan: bool = False) -> dict:
    """One traced serve run; checks the loop's health and the plan, and
    returns the plan's hot sets and the specialized output on the fixed
    batch (with ``replan``, after re-planning from that batch alone).
    Releases the plane before returning."""
    import numpy as np
    from repro.launch.serve import run_frontend_serve, serving_failures

    t0 = time.perf_counter()
    # ~8 s of traffic, a recompile tick every 2 s: the first cycle must
    # see a sampled window, because a cycle's swap drops the samples
    # taken while it compiled, and at full width that outlasts a short
    # trace (at 32 req/s over 1 s ticks the plan on the chip had none)
    stats, ctl, rts, fes = run_frontend_serve(
        planes=1, requests=64, rate=8.0, arrival="poisson", batch_size=8,
        ladder=(1, 8), window_k_max=2, slo_ms=600_000.0,
        recompile_every_s=2.0, locality="high", serve_cfg=cfg, mesh=mesh,
        seed=0)
    rt = rts[0]
    try:
        phase_s = time.perf_counter() - t0
        log(f"serve[{mesh}]: {phase_s:.1f}s total, trace "
            f"{stats['wall_s']:.1f}s, set-up and drain "
            f"{phase_s - stats['wall_s']:.1f}s, "
            f"compiles={rt.engine.compile_count}")
        reqs = stats["request_objs"]
        bad = [r.status for r in reqs if r.status != "ok"]
        check(len(reqs) == 64 and not bad,
              f"requests not all ok: {len(reqs)} submitted, {bad}")
        failures = serving_failures(ctl)
        check(not failures, f"serving failures: {failures}")
        cs = ctl.stats()
        log(f"serve[{mesh}]: recompiles={cs.scheduler['completed']} "
            f"sampled_steps={rt.stats.instr_steps} steps={rt.stats.steps}")
        check(cs.scheduler["completed"] >= 1,
              f"no recompile completed: {cs.scheduler}")
        check(cs.health[rt.plane_id]["state"] == "healthy",
              f"plane not healthy: {cs.health}")
        batch = fixed_batch(cfg)
        if replan:
            replan_from(rt, batch)
        log(f"serve[{mesh}]: plan {[(s, sp.impl) for s, sp in rt.plan.sites]}")
        hot = rt.plan.site("vocab_embed#0")
        check(hot is not None and hot.impl == "hot_cache",
              f"vocab_embed#0 is not hot_cache: {hot}")
        experts = rt.hot_experts()
        check(bool(experts), "router has no moe_fastpath site")
        check("tpu_custom_call" in rt.exec.as_text(),
              "specialized executable holds no Pallas kernel")

        deopts = rt.stats.deopt_steps
        out = np.asarray(rt.step(batch))
        check(rt.stats.deopt_steps == deopts,
              "fixed batch was served by the generic deopt target")
        ref = np.asarray(rt.run_generic(batch))
        check(out.shape == (8, cfg.seq, cfg.vocab) and
              np.isfinite(out).all(), f"bad output {out.shape}")
        err = row_rel_err(out, ref)
        log(f"serve[{mesh}]: specialized vs generic row rel err {err:.3e} "
            f"(bound {SERVE_RTOL})")
        check(err <= SERVE_RTOL, f"specialized != generic: {err:.3e}")
        result = {"hot_keys": sorted(hot.hot_keys),
                  "experts": sorted(experts), "out": out}
    finally:
        ctl.close()
        rt.close()                   # joins the background warms
        warm_errors = list(rt.stats.warm_errors)
        del rt, rts, fes, ctl, stats
        gc.collect()
    check(not warm_errors, f"background warms failed: {warm_errors}")
    return result


def serve_phase(device) -> None:
    cfg = serve_config()
    run_serve(cfg, mesh="none")
    log(f"serve: peak_bytes_in_use={peak_bytes(device)}")


def sharded_serve_phase(devices) -> None:
    """The serve trace on all devices, then on one.  Each is re-planned
    from the same batch, so the sharded sketches (psum-merged across
    devices) must yield the single device's plan and outputs."""
    cfg = serve_config()
    sharded = run_serve(cfg, mesh="auto", replan=True)
    log(f"serve[auto]: peak_bytes_in_use per device "
        f"{[peak_bytes(d) for d in devices]}")
    single = run_serve(cfg, mesh="none", replan=True)
    check(sharded["hot_keys"] == single["hot_keys"],
          f"hot keys differ: {sharded['hot_keys']} vs {single['hot_keys']}")
    check(sharded["experts"] == single["experts"],
          f"hot experts differ: {sharded['experts']} vs {single['experts']}")
    err = row_rel_err(sharded["out"], single["out"])
    log(f"serve: sharded vs single-device row rel err {err:.3e}")
    check(err <= SERVE_RTOL, f"sharded != single-device: {err:.3e}")


def train_phase(device) -> None:
    import numpy as np
    from repro.launch import train as train_mod

    args = train_mod.parse_args([
        "--arch", TRAIN_ARCH, "--layers", str(TRAIN_LAYERS),
        "--seq", str(TRAIN_SEQ), "--batch", str(TRAIN_BATCH),
        "--steps", str(TRAIN_STEPS), "--ckpt-every", "0",
        "--log-every", "1"])
    t0 = time.perf_counter()
    res = train_mod.train(args)
    log(f"train: {res['n_params'] / 1e6:.1f}M params, {TRAIN_STEPS} steps "
        f"in {time.perf_counter() - t0:.1f}s (set-up and compile "
        f"included), loss={res['loss']:.4f}, "
        f"peak_bytes_in_use={peak_bytes(device)}")
    st = res["stats"]
    check(res["rc"] == 0 and np.isfinite(res["loss"]),
          f"train failed: rc={res['rc']} loss={res['loss']}")
    for name in ("step_faults", "retried_steps", "quarantines",
                 "failed_activations"):
        check(st[name] == 0, f"train {name}={st[name]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded serving comparison")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[smoke] no TPU: JAX found {dev.platform}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"[smoke] --chips {args.chips} but JAX found {len(devices)}",
              file=sys.stderr)
        return 1
    log(f"device kind={dev.device_kind} count={len(devices)} "
        f"compile cache={cache_dir}")
    try:
        if args.chips == 4:
            sharded_serve_phase(devices)
        else:
            serve_phase(dev)
            train_phase(dev)
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
