"""batch_ms.montage: milliseconds per suite call in the program's
``suite.batch`` span (``repro.obs``): ``batch_scenarios`` padding the
lowered scenarios to one shape, join rows included, with its wave walk,
on the host. The note gives every phase of the call and the call's
counters, per call. Moves ``suite_scenarios_per_s``."""

from bench import progspans

PHASES = ("suite.lower", "suite.batch", "lower.join", "suite.jitter",
          "suite.gather", "suite.relax")
NOTED = ("suite.scenarios", "lower.graph_arrays.hit",
         "lower.graph_arrays.miss", "lower.join_rows", "jit.traces",
         "jit.cache_loads")


def read(ctx):
    found = progspans.calls(ctx, "suite", "suite.call")
    if found is not None:
        note = {k + "_ms": progspans.mean_ms(found, k) for k in PHASES}
        counts = {k: progspans.counted(found, k) for k in NOTED}
        note.update({k: v / len(found) for k, v in counts.items()
                     if v is not None})
        ctx.notes["batch_ms.montage"] = note
    return progspans.mean_ms(found, "suite.batch")
