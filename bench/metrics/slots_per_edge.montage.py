"""slots_per_edge.montage: predecessor slots the relaxation kernel is
given per real edge, over the window's suite calls: the program's
``lower.edge_slots`` (B x S x (P + 1) of each batch) over its
``lower.edges`` (every predecessor edge and every in-order edge). The
note gives both per call and the join rows a call adds
(``lower.join_rows``). A program that does not count them reads as
nothing. Moves ``suite_scenarios_per_s``."""

from bench import progspans

NOTED = ("lower.edge_slots", "lower.edges", "lower.join_rows")


def read(ctx):
    found = progspans.calls(ctx, "suite", "suite.call")
    counts = {k: progspans.counted(found, k) for k in NOTED}
    if found is None or not counts["lower.edge_slots"] \
            or not counts["lower.edges"]:
        return None
    ctx.notes["slots_per_edge.montage"] = {
        k: v / len(found) for k, v in counts.items() if v is not None}
    return counts["lower.edge_slots"] / counts["lower.edges"]
