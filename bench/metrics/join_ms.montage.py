"""join_ms.montage: milliseconds per suite call in the program's
``lower.join`` spans (``repro.obs``): filling the join rows through
which a wide join's subtask reads its predecessors, and building a
graph's join layout where it is not cached yet. A program without join
rows records no such span and reads as nothing. Moves
``suite_scenarios_per_s``."""

from bench import progspans


def read(ctx):
    return progspans.mean_ms(progspans.calls(ctx, "suite", "suite.call"),
                             "lower.join")
