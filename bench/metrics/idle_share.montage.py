"""idle_share.montage: percent of the traced window in which no operation
ran on the device (1 - busy / window, from the profiler trace). Moves
``suite_scenarios_per_s``."""

from bench import trace


def read(ctx):
    if ctx.trace is None or not ctx.trace["device"]:
        return None
    return trace.idle_share_pct(ctx.trace)
