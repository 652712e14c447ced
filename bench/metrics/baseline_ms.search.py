"""baseline_ms.search: milliseconds per search in the program's
``ga.baseline`` span (``repro.obs``): the engine schedule the search is
seeded with, and its encoding, on the host. Moves ``search_s``."""

from bench import progspans


def read(ctx):
    return progspans.mean_ms(progspans.calls(ctx, "search", "ga.schedule"),
                             "ga.baseline")
