"""idle_share.search: percent of the traced window in which no operation ran on
the device (1 - busy / window, from the profiler trace). Moves
``search_s``."""

from bench import trace


def read(ctx):
    if ctx.trace is None or not ctx.trace["device"]:
        return None
    return trace.idle_share_pct(ctx.trace)
