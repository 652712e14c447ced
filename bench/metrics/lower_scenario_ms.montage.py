"""lower_scenario_ms.montage: milliseconds per suite call in the
program's ``suite.lower`` span (``repro.obs``): one ``lower_scenario``
per scenario, on the host. Moves ``suite_scenarios_per_s``."""

from bench import progspans


def read(ctx):
    return progspans.mean_ms(progspans.calls(ctx, "suite", "suite.call"),
                             "suite.lower")
