"""device_ms.search: milliseconds per search in which an operation ran on
the device inside the benchmark's span around ``ga_schedule`` (the
generation steps, the initial fitness and the hill climb). Moves
``search_s``."""

from bench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    busy = [b for b in trace.busy_inside_s(ctx.trace, "search") if b > 0]
    return 1e3 * sum(busy) / len(busy) if busy else None
