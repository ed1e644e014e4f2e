"""Share of the window's steps with slots routed to experts held here
that took the MoE fast branch, in percent, summed over the MoE layers:
the deltas of the plane's ``moe_stats`` counters (``fast_steps`` over
``held_steps``) read before and after the window (program counter).
Nothing where the run has no such counters."""


def read(run):
    stats = run.values.get("moe_stats")
    if not stats:
        return None
    held = sum(stats["held_steps"])
    return 100.0 * sum(stats["fast_steps"]) / held if held else None
