"""candidate_us.search: microseconds of device time per candidate a
search evaluated: device busy time inside the benchmark's ``search``
spans over the ``ga.candidates`` the program counted in the same
searches (``repro.obs``). The note gives each of the search's counters
per search. Moves ``search_s``."""

from bench import progspans, trace

NOTED = ("ga.candidates", "ga.generations", "ga.refine_rounds",
         "ga.step_traces", "jit.traces", "jit.cache_loads",
         "lower.population_arrays.hit", "lower.population_arrays.miss")


def read(ctx):
    found = progspans.calls(ctx, "search", "ga.schedule")
    n = progspans.counted(found, "ga.candidates")
    if not n:
        return None
    counts = {k: progspans.counted(found, k) for k in NOTED}
    ctx.notes["candidate_us.search"] = {
        k: v / len(found) for k, v in counts.items() if v is not None}
    return 1e6 * sum(trace.busy_inside_s(ctx.trace, "search")) / n
