"""Share of its roofline that the Pallas ``hot_gather`` kernel reaches in
the window, in percent: the least time of the bytes each call needs at
the chip's HBM bandwidth, over the kernel's device time (profiler
trace).  A call's rows and width come from its output shape in the
trace; the bytes are its ids and its rows written once.  The distinct
rows it reads are not in the trace and are not counted, so the share is
at most the kernel's true one (a call of 128 rows over 32 distinct ids
reads a fifth of its bytes)."""
from bench.lib.counts import gather_bytes, roofline_s
from bench.lib.readers import kernel_calls


def read(run):
    calls = kernel_calls(run, "hot_gather")
    if not calls or run.peaks is None:
        return None
    need = sum(roofline_s(0.0, gather_bytes(rows, width, distinct=0),
                          run.peaks)
               for rows, width, _ in calls)
    return 100.0 * need / sum(dur for _, _, dur in calls)
