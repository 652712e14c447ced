"""relax_roofline.montage: percent of the device time inside the
benchmark's ``suite`` spans that one acyclic relaxation pass over the
call's real edges needs at the chip's peak (``bench/work.py``,
``bench/peaks.json``): per scenario, every predecessor edge and one
in-order edge per subtask, not the padded columns (the kind's ``work``).

Least time over device busy time, as ``relax_roofline.suite`` reads it;
which of bytes or operations binds is noted in the run's info line.
Moves ``suite_scenarios_per_s``."""

from bench import trace, work


def read(ctx):
    if ctx.trace is None or ctx.peak is None or "suite" not in ctx.work:
        return None
    busy = [b for b in trace.busy_inside_s(ctx.trace, "suite") if b > 0]
    if not busy:
        return None
    least, bound = work.least_time(ctx.work["suite"], ctx.peak)
    ctx.notes["relax_roofline.montage"] = {
        "bound": bound, "least_s": least,
        "busy_s_per_call": sum(busy) / len(busy)}
    return 100.0 * least * len(busy) / sum(busy)
