"""After the window, the device time of the active (specialized)
executable over the generic one's on the same batches of window
requests (profiler trace of the replay spans)."""
from bench.lib.readers import span_busy_s


def read(run):
    spec = span_busy_s(run, "bench.replay.spec")
    generic = span_busy_s(run, "bench.replay.generic")
    if not spec or not generic:
        return None
    return spec / generic
