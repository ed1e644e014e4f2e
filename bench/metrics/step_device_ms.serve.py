"""Device time per serving step in the window: the time some operation
ran on the device, over the steps dispatched (profiler trace)."""
from bench.lib.readers import window_busy_s


def read(run):
    busy, steps = window_busy_s(run), run.values["steps"]
    if busy is None or not steps:
        return None
    return busy * 1e3 / steps
