"""Model operations of the window's real (non-pad) request tokens, top-k
experts per token, over the device's busy time at the chip's bf16 peak,
in percent (profiler trace)."""
from bench.lib.counts import serve_plane_flops
from bench.lib.readers import window_busy_s


def read(run):
    busy = window_busy_s(run)
    if not busy or run.peaks is None:
        return None
    v = run.values
    flops = v["requests_completed"] * serve_plane_flops(
        run.config, v["request_tokens"])
    return 100.0 * flops / (busy * run.peaks["bf16_flops"])
