"""decode_ms.search: milliseconds per search in the program's
``ga.decode`` span (``repro.obs``): the gap-filling host decode of the
best vector and the comparison with the baseline. Moves ``search_s``."""

from bench import progspans


def read(ctx):
    return progspans.mean_ms(progspans.calls(ctx, "search", "ga.schedule"),
                             "ga.decode")
