"""relax_roofline.suite: percent of the device time inside the benchmark's
``suite`` spans that one acyclic relaxation pass needs at the chip's
peak (``bench/work.py``, ``bench/peaks.json``), one pass per
scenario of a suite call.

Least time over device busy time; the denominator is all device time in
the spans, whatever ran there, so a change of implementation reads the
same work. Which of bytes or operations binds is noted in the run's
info line. Moves ``suite_scenarios_per_s``."""

from bench import trace, work


def read(ctx):
    if ctx.trace is None or ctx.peak is None or "suite" not in ctx.work:
        return None
    busy = [b for b in trace.busy_inside_s(ctx.trace, "suite") if b > 0]
    if not busy:
        return None
    least, bound = work.least_time(ctx.work["suite"], ctx.peak)
    ctx.notes["relax_roofline.suite"] = {"bound": bound, "least_s": least,
                           "busy_s_per_call": sum(busy) / len(busy)}
    return 100.0 * least * len(busy) / sum(busy)
