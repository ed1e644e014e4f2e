"""Training driver.

CPU-runnable end-to-end: reduced configs of any assigned architecture,
the real AdamW/train_step path, atomic+async checkpointing, failure
injection with resume, straggler monitoring — and, through
:class:`~repro.training.TrainSupervisor`, the Morpheus robustness
contract on the train step itself: hot-expert respecialization compiled
off-thread and swapped at deterministic barriers, deopt to the resident
generic step on fault or mispredict, checkpoint-coupled plan state
(``--resume`` revalidates the active specialization with zero
training-thread compiles), and a mid-run device-loss arc
(``--device-loss-at-step``) that snapshots, shrinks the mesh, elastic-
reshards and continues degraded while re-specializing in background.

Fault taxonomy (see distributed/fault.py):

  * ``--fail-at-step N`` — SIGKILL-equivalent *process crash*: the
    exception escapes the driver; rerun with ``--resume`` restores the
    latest atomic checkpoint and replays **bit-exactly** (the
    supervisor's executable sequence is a deterministic function of the
    trajectory, carried in checkpoint meta).
  * ``--step-fault-at N`` — *in-process* fault at the supervisor's
    boundary: deopts to generic, retries the same batch, never loses an
    optimizer step; the run continues and re-specializes.
  * ``--device-loss-at-step N`` — elastic arc: snapshot → mesh shrink →
    reshard → degraded generic → background re-specialization;
    ``--grow-back-after K`` grows the mesh back K steps later.

Examples:
    python -m repro.launch.train --arch llama3-8b --smoke --steps 50
    python -m repro.launch.train --arch phi3.5-moe-42b-a6.6b --smoke \
        --steps 40 --fail-at-step 25 --resume   # crash + recover
    python -m repro.launch.train --arch starcoder2-3b --layers 4 \
        --seq 2048 --batch 1 --steps 3          # full width, 4 layers
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import latest_step, restore, save, save_async
from ..configs import get_config
from ..data import DataConfig, TokenPipeline
from ..distributed.fault import FailureInjector, SimulatedDeviceLoss, \
    SimulatedFailure, StragglerMonitor
from ..models import Model, unzip
from ..models.params import zip_axes
from ..optim import AdamWConfig, init_opt_state
from ..training import SupervisorConfig, TrainSupervisor
from .compile_cache import enable_compile_cache


def build_state(model: Model, key, abstract=False):
    params_pspec = model.init(key, abstract=abstract)
    opt_pspec = init_opt_state(params_pspec, abstract=abstract)
    params, params_axes = unzip(params_pspec)
    opt, opt_axes = unzip(opt_pspec)
    return ({"params": params, "opt": opt},
            {"params": params_axes, "opt": opt_axes})


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the model's depth to N layers, every width "
                    "as configured")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--ckpt-async", action="store_true")
    ap.add_argument("--keep-last", type=int, default=None,
                    help="retain only the newest N checkpoints "
                    "(default: keep everything)")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at-step", type=int, default=None,
                    help="process-crash injection (escapes the driver; "
                    "resume from the latest checkpoint)")
    ap.add_argument("--step-fault-at", type=int, default=None,
                    help="in-process fault at the supervisor boundary "
                    "(deopt + retry, no lost step)")
    ap.add_argument("--device-loss-at-step", type=int, default=None,
                    help="simulate losing a device: snapshot + mesh "
                    "shrink + elastic reshard + degraded continue")
    ap.add_argument("--grow-back-after", type=int, default=None,
                    help="grow the mesh back N steps after the device "
                    "loss")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--respecialize-every", type=int, default=0,
                    help="Morpheus on the training backend: every N steps "
                    "re-plan hot experts from router statistics and swap "
                    "in the branch-injected train step (0 = off)")
    ap.add_argument("--hot-coverage", type=float, default=0.95)
    return ap.parse_args(argv)


def train(args: argparse.Namespace) -> dict:
    """Run the training loop ``args`` describes.  Returns ``rc`` (0 ok,
    2 on a non-finite loss), the last ``loss``, the supervisor's
    ``stats`` and ``n_params``."""
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.layers is not None:
        cfg = cfg.replace(n_layers=args.layers)
    model = Model(cfg)
    key = jax.random.PRNGKey(args.seed)

    state, _ = build_state(model, key)
    n_params = sum(int(np.prod(x.shape)) for x in
                   jax.tree.leaves(state["params"]))
    print(f"[train] {cfg.name}: {n_params/1e6:.1f}M params", flush=True)

    dcfg = DataConfig(vocab=cfg.vocab, seq=args.seq,
                      global_batch=args.batch, seed=args.seed,
                      media_tokens=cfg.num_media_tokens,
                      d_model=cfg.d_model,
                      enc_seq=(args.seq // cfg.enc_seq_divisor
                               if cfg.encdec else 0))
    pipe = TokenPipeline(dcfg)

    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                          total_steps=args.steps)
    ckpt_dir = args.ckpt_dir or f"/tmp/repro_ckpt_{cfg.name}"

    # the supervisor owns the step executables: the resident generic is
    # compiled here (the one training-thread compile of the run);
    # specialized steps compile on its scheduler thread
    fault_injector = FailureInjector(seed=args.seed)
    sup = TrainSupervisor(
        model, opt_cfg, state, pipe.peek_batch(),
        cfg=SupervisorConfig(respecialize_every=args.respecialize_every,
                             hot_coverage=args.hot_coverage,
                             microbatches=args.microbatches),
        injector=fault_injector, ckpt_dir=ckpt_dir,
        meta_fn=lambda: {"arch": cfg.name},
        log_fn=lambda m: print(f"[train] {m}", flush=True))

    start_step = 0
    if args.resume and latest_step(ckpt_dir) is not None:
        state, meta = restore(ckpt_dir, None, state)
        pipe.load_state_dict(meta["data"])
        start_step = meta["step"]
        # revalidate-or-deopt: the checkpointed plan re-stages for
        # activation at start_step and compiles in background — the
        # first step waits at the barrier, the trainer never retraces
        sup.restore_spec(meta.get("morpheus"), resume_step=start_step)
        print(f"[train] resumed from step {start_step}", flush=True)

    crash_injector = FailureInjector(fail_at_step=args.fail_at_step,
                                     seed=args.seed)
    straggler = StragglerMonitor(
        on_straggler=lambda s, t: print(
            f"[train] straggler mitigation fired at step {s} "
            f"({t*1e3:.0f} ms)", flush=True))

    def ckpt_meta():
        return {"data": pipe.state_dict(), "arch": cfg.name,
                "morpheus": sup.spec_meta()}

    pending = None
    rc = 0
    loss = float("nan")
    try:
        for step in range(start_step, args.steps):
            # process-crash injection: escapes the driver (the
            # SIGKILL-equivalent arc — resume from the checkpoint)
            crash_injector.check(step)
            if args.step_fault_at is not None and step == args.step_fault_at:
                fault_injector.arm_next(
                    SimulatedFailure(f"injected failure at step {step}"))
            if (args.device_loss_at_step is not None
                    and step == args.device_loss_at_step):
                fault_injector.arm_next(
                    SimulatedDeviceLoss(f"device lost at step {step}"))
            if (args.device_loss_at_step is not None
                    and args.grow_back_after is not None
                    and step == (args.device_loss_at_step
                                 + args.grow_back_after)):
                state = sup.recover_devices(state)
            t0 = time.time()
            batch = pipe.next_batch()
            state, metrics = sup.step(state, batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            straggler.observe(step, dt)

            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"[train] step={step} loss={loss:.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e} {dt*1e3:.0f}ms",
                      flush=True)
            if not np.isfinite(loss):
                print("[train] non-finite loss — aborting", flush=True)
                rc = 2
                break
            if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
                if pending is not None:
                    pending.join()       # surface async write errors
                if args.ckpt_async:      # before queuing the next one
                    pending = save_async(ckpt_dir, step + 1, state,
                                         ckpt_meta(),
                                         keep_last=args.keep_last)
                else:
                    save(ckpt_dir, step + 1, state, ckpt_meta(),
                         keep_last=args.keep_last)
        if pending is not None:
            pending.join()               # re-raises write failures —
            pending = None               # a lost checkpoint fails loudly
        if rc == 0:
            print(f"[train] done at step {args.steps}", flush=True)
    finally:
        if pending is not None:
            try:
                pending.join(timeout=60.0)
            except Exception as e:       # noqa: BLE001 — already failing
                print(f"[train] async checkpoint write failed: {e}",
                      flush=True)
        stats = sup.stats()
        sup.close()
    return {"rc": rc, "loss": loss, "stats": stats, "n_params": n_params}


def main(argv=None) -> int:
    args = parse_args(argv)
    enable_compile_cache()
    return train(args)["rc"]


if __name__ == "__main__":
    sys.exit(main())
