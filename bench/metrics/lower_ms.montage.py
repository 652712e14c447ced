"""lower_ms.montage: milliseconds from the start of each suite call (the
benchmark's span around ``simulate_suite``) to the first device
operation inside it: host lowering, join rows, batching, jitter draws
and gathers. Moves ``suite_scenarios_per_s``."""

from bench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    d = trace.first_device_op_s(ctx.trace, "suite")
    return 1e3 * sum(d) / len(d) if d else None
