"""Share of the window with no operation on the device (profiler
trace)."""
from bench.lib.readers import idle_share


def read(run):
    return idle_share(run)
