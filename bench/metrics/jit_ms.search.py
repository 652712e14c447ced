"""jit_ms.search: milliseconds per search that JAX spent tracing,
lowering and compiling (a persistent-cache load included), from the
program's ``jit.trace_s``, ``jit.lower_s`` and ``jit.compile_s`` counts
under each search's ``ga.schedule`` root (``repro.obs``). The note gives
the three apart. Moves ``search_s``."""

from bench import progspans

PARTS = ("jit.trace_s", "jit.lower_s", "jit.compile_s")


def read(ctx):
    found = progspans.calls(ctx, "search", "ga.schedule")
    parts = {k: progspans.counted(found, k) for k in PARTS}
    if found is None or all(v is None for v in parts.values()):
        return None
    ms = {k: 1e3 * (v or 0.0) / len(found) for k, v in parts.items()}
    ctx.notes["jit_ms.search"] = ms
    return sum(ms.values())
