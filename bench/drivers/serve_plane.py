"""Cells of kind ``serve_plane``: open-loop requests through the
serving plane under the Morpheus runtime.

The timed path is the program's own: ``ServingFrontend.submit`` ->
``DynamicBatcher`` -> ``MorpheusRuntime.step_many`` on the active
executable.  Set-up builds the plane from the seed, warms every window
shape the cell's batcher can form, and sends the cell's traffic for
``tick_s`` seconds at a time, with a ``MorpheusController`` recompile
cycle run to its end after each, for at least ``WARM_CYCLES`` cycles and
until the plan is built from traffic and holds every site the traffic
requires, and then for ``tick_s`` seconds more, so that the executables
the last cycle swapped in have run before the window.  The warm traffic
is the same for every seed.  The window then offers ``rate x seconds``
requests on an absolute schedule to that plan; the controller schedules
no cycle in it, so nothing compiles there (a cycle re-plans and
compiles even for an unchanged hot set, PERF.md).  After the window
closes, the run waits for every request, reads the sessions table, the
plan and the peak memory, frees the plane, and checks a sample of the
served answers against the plain reference.
"""
from __future__ import annotations

import gc
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from ..lib import arrivals
from ..lib.common import BenchError, Check, Run, memory_peak_bytes, rng
from ..refs import serve_plane as ref

WAIT_AFTER_CLOSE_S = 60.0
WARM_TIMEOUT_S = 240.0
# the most cycles set-up needed on the chip, so that every run pays the same
WARM_CYCLES = 3
WARM_SEED = 0


def annotation(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def serve_config(config: dict):
    from repro.serving import ServeConfig
    a = config["assumed"]
    return ServeConfig(
        d_model=config["hidden_size"], n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        n_experts=config["num_local_experts"],
        top_k=config["num_experts_per_tok"], n_classes=a["classes"],
        n_adapters=a["adapters"], n_slots=a["session_slots"],
        seq=a["request_tokens"])


def payloads(traffic: dict, config: dict, n: int,
             gen: np.random.Generator) -> List[Dict[str, np.ndarray]]:
    """``n`` request rows: ``seq`` token ids uniform over the first
    ``token_ids`` ids, a class uniform over the first ``classes``, a slot
    uniform over ``slots``."""
    a = config["assumed"]
    if traffic["token_ids"] > config["vocab_size"]:
        raise BenchError("the traffic draws token ids beyond the vocabulary")
    toks = gen.integers(0, traffic["token_ids"], (n, a["request_tokens"]),
                        dtype=np.int32)
    cls = gen.integers(0, traffic["classes"], n, dtype=np.int32)
    slot = gen.integers(0, traffic["slots"], n, dtype=np.int32)
    return [{"tokens": toks[i], "class_id": cls[i], "slot": slot[i]}
            for i in range(n)]


def stack(rows: List[dict]) -> dict:
    import jax.numpy as jnp
    return {k: jnp.asarray(np.stack([r[k] for r in rows]))
            for k in rows[0]}


class Plane:
    """One serving plane built through the program's entry points."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        import jax
        from repro.core import ControllerConfig, EngineConfig, \
            MorpheusController, MorpheusRuntime, SketchConfig
        from repro.serving import build_fleet
        from repro.serving.frontend import FrontendConfig, ServingFrontend

        s = config["serving"]
        self.config, self.traffic, self.seed = config, traffic, seed
        self.cfg = serve_config(config)
        params = ref.make_weights(config, seed, traffic["router_bias"])
        embed = np.asarray(ref.make_embedding(config, seed))
        jax.block_until_ready(params)
        [(step_fn, tables)] = build_fleet(self.cfg, jax.random.PRNGKey(0),
                                          1)
        self.controller = MorpheusController(
            ControllerConfig(workers=s["recompile_workers"]))
        ecfg = EngineConfig(
            features={"vision_enabled": False, "track_sessions": True},
            sketch=SketchConfig(**s["sketch"]),
            moe_router_table="router", mesh=None, cache_ns="bench-serve")
        example = stack(payloads(traffic, config, s["max_batch"],
                                 rng(seed, "example")))
        self.rt = MorpheusRuntime(step_fn, tables, params, example,
                                  cfg=ecfg, controller=self.controller,
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

    def warm_shapes(self, gen: np.random.Generator) -> None:
        """Compile every window the batcher can form: each ladder bucket
        at K=1 and the largest bucket at K=2..window_k_max."""
        from repro.serving import make_request_batch
        ladder = self.fcfg.ladder_resolved()
        rows = payloads(self.traffic, self.config, ladder[-1], gen)
        for b in ladder:
            self.rt.warm_fused([make_request_batch(rows[:b], b)])
        primary = make_request_batch(rows, ladder[-1])
        for k in range(2, self.fcfg.window_k_max + 1):
            self.rt.warm_fused([primary] * k)

    def impls(self) -> set:
        return {spec.impl for _, spec in self.rt.plan.sites}

    def failures(self) -> list:
        from repro.launch.serve import serving_failures
        return serving_failures(self.controller)

    def sessions(self) -> Dict[str, np.ndarray]:
        t = self.rt.state.tables["sessions"]
        return {k: np.asarray(v) for k, v in t.items()}

    def close(self) -> None:
        self.fe.stop(drain=True)
        self.controller.close()
        self.rt.close()


def draw(traffic: dict, config: dict, rate: float, seconds: float,
         gen: np.random.Generator) -> tuple:
    """The due times and request rows of ``rate x seconds`` requests."""
    due = arrivals.schedule(traffic["arrival"], rate, seconds, gen)
    return due, payloads(traffic, config, len(due), gen)


def window_requests(traffic: dict, config: dict, seed: int,
                    seconds: float) -> tuple:
    """The window's due times and rows, and the sample of them whose
    answers are checked, all from the seed."""
    due, rows = draw(traffic, config, traffic["rate_per_s"], seconds,
                     rng(seed, "window"))
    sample, last = choose_sample(traffic, rows, rng(seed, "sample"))
    return due, rows, sample, last


def offer(plane: "Plane", due: np.ndarray, rows: List[dict],
          seconds: float, keep: Optional[set] = None,
          annotate=None) -> dict:
    """Submit ``rows[i]`` at ``due[i]`` seconds into a window of
    ``seconds``; then wait up to a minute past the close for every answer.
    Outputs are dropped as requests finish, except for the indices in
    ``keep``."""
    keep = keep or set()
    pending: deque = deque()
    sender: Optional[arrivals.Sender] = None

    def on_sent(i: int) -> None:
        pending.append(i)
        while pending and sender.requests[pending[0]].done:
            j = pending.popleft()
            if j not in keep:
                sender.requests[j].output = None

    t0 = time.monotonic() + 0.05
    sender = arrivals.Sender(plane.fe.submit, rows, due, t0, on_sent,
                             annotate)
    ann = annotate or (lambda name: _Null())
    sender.start()
    time.sleep(max(t0 - time.monotonic(), 0.0))
    with ann("bench.window"):
        time.sleep(max(t0 + seconds - time.monotonic(), 0.0))
    closed = time.monotonic()
    sender.join(timeout=WAIT_AFTER_CLOSE_S)
    deadline = closed + WAIT_AFTER_CLOSE_S
    for r in sender.requests:
        if r is not None:
            r.wait(max(deadline - time.monotonic(), 0.0))
    for j, r in enumerate(sender.requests):
        if r is not None and j not in keep:
            r.output = None
    return {"t0": t0, "due": due, "sender": sender, "closed": closed}


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def request_times(offered: dict) -> dict:
    """Per request: latency from its due time (inf when it never
    completed ok), lateness of the send, and queue wait."""
    t0, due, sender = offered["t0"], offered["due"], offered["sender"]
    lat, queue = [], []
    ok = 0
    for i, r in enumerate(sender.requests):
        if r is not None and r.status == "ok":
            done = r.arrival_ts + r.timing["total_s"]
            lat.append((done - (t0 + due[i])) * 1e3)
            queue.append(r.timing["queue_wait_s"] * 1e3)
            ok += 1
        else:
            lat.append(float("inf"))
    late = sender.lateness_s() * 1e3
    return {"latency_ms": lat, "queue_wait_ms": queue,
            "lateness_ms": [float(x) if np.isfinite(x) else float("inf")
                            for x in late],
            "completed_ok": ok}


def warm_traffic(plane: Plane, traffic: dict) -> dict:
    """Send the cell's traffic for ``tick_s`` seconds, then let the
    controller run a recompile cycle to its end; repeat for at least
    ``WARM_CYCLES`` cycles and until the plan is built from traffic (it
    holds the batch-shape site) and holds every site the traffic
    requires.  Then send it for ``tick_s`` seconds more with no cycle,
    so that the first windows of the executables the last cycle swapped
    in fall in set-up.  The traffic is drawn from one fixed seed, so that
    every run's cycles see the same requests."""
    from repro.core import plan_batch_shape
    need = set(traffic.get("require_impls", []))
    t_end = time.monotonic() + WARM_TIMEOUT_S
    ticks = 0
    gen = rng(WARM_SEED, "warm-traffic")

    def send() -> None:
        due, rows = draw(traffic, plane.config, traffic["rate_per_s"],
                         plane.tick_s, gen)
        offer(plane, due, rows, plane.tick_s)

    while time.monotonic() < t_end:
        send()
        plane.controller.schedule_all()
        plane.controller.drain()
        ticks += 1
        if ticks >= WARM_CYCLES \
                and plan_batch_shape(plane.rt.plan) is not None \
                and need <= plane.impls():
            send()
            return {"ticks": ticks}
    raise BenchError(f"no plan from traffic within {WARM_TIMEOUT_S:.0f} s"
                     f": impls {sorted(plane.impls())}, required "
                     f"{sorted(need)}")


def choose_sample(traffic: dict, rows: List[dict], gen) -> tuple:
    """The requests whose answers are checked: half of them the last
    request to their slot (so the sessions table can be read against
    them), the rest any others, all drawn from the seed."""
    n = min(traffic["check_requests"], len(rows))
    last = {}
    for i, r in enumerate(rows):
        last[int(r["slot"])] = i
    lasts = np.array(sorted(last.values()))
    a = gen.choice(lasts, min(n // 2, len(lasts)), replace=False)
    rest = np.setdiff1d(np.arange(len(rows)), a)
    b = gen.choice(rest, min(n - len(a), len(rest)), replace=False)
    return sorted(int(i) for i in np.concatenate([a, b])), last


def check_sessions(before: dict, after: dict, rows: List[dict],
                   requests: List, last: dict, sample: List[int]
                   ) -> tuple:
    """The sessions table the plane wrote in the window.  Each step
    writes ``count + 1`` and the greedy last token for the slots in its
    batch, so a slot's count grows by 1..(its requests), an untouched
    slot's not at all, and a slot's ``last_token`` is the greedy token
    of the last request to it when no other request to it shared that
    request's window."""
    n_req = np.zeros_like(before["count"])
    for r in rows:
        n_req[int(r["slot"])] += 1
    delta = after["count"] - before["count"]
    bad_count = int(np.sum((n_req == 0) & (delta != 0))
                    + np.sum((n_req > 0) & ((delta < 1) | (delta > n_req))))
    bad_token = checked = 0
    by_slot: Dict[int, List[int]] = {}
    for i, r in enumerate(rows):
        by_slot.setdefault(int(r["slot"]), []).append(i)
    for i in sample:
        slot = int(rows[i]["slot"])
        if last.get(slot) != i or requests[i] is None \
                or requests[i].output is None:
            continue
        r = requests[i]
        taken = r.arrival_ts + r.timing["queue_wait_s"]
        prev = [j for j in by_slot[slot] if j < i]
        if prev:
            p = requests[prev[-1]]
            if p is None or p.status != "ok" or \
                    p.arrival_ts + p.timing["total_s"] >= taken:
                continue
        checked += 1
        greedy = int(np.asarray(r.output)[-1].argmax())
        bad_token += int(after["last_token"][slot] != greedy)
    return bad_count, bad_token, checked


def gap_max(gaps: np.ndarray) -> float:
    """The widest gap of the served tokens; no tokens read as infinitely
    wrong."""
    return float(gaps.max()) if gaps.size else float("inf")


def reference_gaps(config: dict, traffic: dict, seed: int,
                   rows: List[dict], outputs: Dict[int, np.ndarray],
                   precision: str = "float32", block: int = 16
                   ) -> np.ndarray:
    """Gaps of the served tokens of ``outputs`` against the reference,
    computed in blocks of ``block`` requests; with ``precision`` below
    the configuration's, the gaps of that precision's own greedy
    tokens (the control)."""
    import jax
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


def replay(plane: Plane, rows: List[dict], n: int, annotate) -> dict:
    """After the window (traced runs only): the same ``n`` batches of
    window requests through the active specialized executable and
    through the generic one, each call waited for inside its own span."""
    import jax
    b = plane.fcfg.ladder_resolved()[-1]
    plane.rt.sampler.pin(1 << 30)      # the specialized executable alone
    batches = [stack(rows[i * b:(i + 1) * b]) for i in range(n)
               if (i + 1) * b <= len(rows)]
    before = plane.rt.stats.instr_steps
    for batch in batches:
        with annotate("bench.replay.spec"):
            jax.block_until_ready(plane.rt.step(batch))
    sampled = plane.rt.stats.instr_steps - before
    for batch in batches:
        with annotate("bench.replay.generic"):
            jax.block_until_ready(plane.rt.run_generic(batch))
    return {"replay_batches": len(batches), "replay_sampled": sampled}


def run(cell: dict, config: dict, traffic: dict, seed: int,
        seconds: float, trace: bool, device: dict, tracer) -> Run:
    import jax
    t_setup = time.perf_counter()
    plane = Plane(config, traffic, seed)
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

        due, rows, sample, last = window_requests(traffic, config, seed,
                                                  seconds)
        n = len(rows)
        if trace:
            tracer.start()
        offered = offer(plane, due, rows, seconds, keep=set(sample),
                        annotate=annotation)
        window_compiles = plane.rt.engine.compile_count - compiles0
        stats1 = plane.rt.stats.snapshot()
        plane.controller.drain()
        impls = sorted(plane.impls())
        sess1 = plane.sessions()
        extra = {}
        if trace:
            extra = replay(plane, rows, traffic["replay_batches"],
                           annotation)
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
    gaps = reference_gaps(config, traffic, seed, rows, outputs)
    delta = {k: stats1[k] - stats0[k] for k in
             ("steps", "deopt_steps", "degraded_steps", "instr_steps",
              "pad_rows", "batches_formed", "requests_completed",
              "cache_misses")}
    generic = (delta["deopt_steps"] + delta["degraded_steps"]) \
        / max(delta["steps"], 1)
    missing = n - times["completed_ok"]
    need = set(traffic.get("require_impls", []))
    checks = [Check("missing", missing, 0),
              Check("gap_max", gap_max(gaps), traffic["limits"]["gap_max"]),
              Check("session_counts_bad", bad_count, 0),
              Check("last_token_bad", bad_token, 0),
              Check("serving_failures", len(failures), 0),
              Check("impls_missing", len(need - set(impls)), 0),
              Check("generic_share", generic,
                    traffic["limits"]["generic_share"])]
    values = {**times, **extra, **delta,
              "requests": n, "request_tokens": config["assumed"][
                  "request_tokens"],
              "window_compiles": window_compiles,
              "impls": impls, "warm_ticks": warm["ticks"],
              "sessions_checked": checked, "gaps": gaps,
              "failures": failures}
    return Run(cell, config, traffic, device.get("peaks"), seconds,
               setup_s, attempted=n, failed=missing,
               memory_peak_bytes=peak, checks=checks, values=values)
