"""Model operations of the window's real (non-pad) request tokens over
the device's busy time at the chip's bf16 peak, in percent (profiler
trace and program counter).  Every request counts MLA, the dense layer,
the routers, the shared experts and the unembedding slice
(``bench/lib/counts_mla_moe.py``); the routed experts count per slot
routed to an expert held here, the ``moe_stats`` counters' held slots
over the window scaled to the real tokens' share of the routed slots."""
from bench.lib.counts_mla_moe import mla_moe_request_flops, \
    routed_slot_flops
from bench.lib.readers import window_busy_s


def read(run):
    busy = window_busy_s(run)
    stats = run.values.get("moe_stats")
    if not busy or not stats or run.peaks is None:
        return None
    v, c = run.values, run.config
    slots = sum(stats["slots"])
    real = v["requests_completed"] * v["request_tokens"] \
        * c["num_experts_per_tok"] * len(stats["slots"])
    held = sum(stats["held_slots"]) * real / slots if slots else 0.0
    flops = v["requests_completed"] * mla_moe_request_flops(
        c, v["request_tokens"]) + held * routed_slot_flops(c)
    return 100.0 * flops / (busy * run.peaks["bf16_flops"])
