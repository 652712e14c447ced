"""relax_sweeps.search: relaxation sweeps per kernel fitness call of a
search, from the program's ``relax.sweeps`` over ``relax.calls`` under
each search's ``ga.schedule`` root (``repro.obs``). The kernel fitness
stops its sweeps at the fixpoint, with S (the layout's rows) as the
bound; the note gives the bound per call (``relax.sweep_bound`` over
``relax.calls``) and the calls per search. A program that counts no
sweeps (the scan fitness, or one without the early stop) reads as
nothing. Moves ``search_s``."""

from bench import progspans


def read(ctx):
    found = progspans.calls(ctx, "search", "ga.schedule")
    calls = progspans.counted(found, "relax.calls")
    sweeps = progspans.counted(found, "relax.sweeps")
    if not calls or sweeps is None:
        return None
    bound = progspans.counted(found, "relax.sweep_bound")
    ctx.notes["relax_sweeps.search"] = {
        "sweep_bound_per_call": None if bound is None else bound / calls,
        "calls_per_search": calls / len(found)}
    return sweeps / calls
