"""The program's own spans and counters (``repro.obs``), lined up with the
trace, for the per-layer readers that read them.

The program records its spans on its host clock (``perf_counter_ns``),
the trace keeps only the benchmark's spans, on the profiler's clock.
Each window call is therefore lined up on its own: the benchmark's span
around a call (``search``, ``suite``) and the program's root span of
that call (``ga.schedule``, ``suite.call``) enclose the same work, so

* **window selection**: the window's calls are the last N roots of the
  cell's root name, N the number of the benchmark's spans in the window
  (the set-up call falls out);
* **alignment**: call i's program spans move by the start of the i-th
  benchmark span in the trace minus the start of the i-th root; a root
  whose length differs from its benchmark span's by more than
  :data:`MAX_DISAGREE_NS` reads as nothing;
* **idle attribution**: each idle stretch of chip 0 inside a call's
  benchmark span is charged to the innermost aligned program span that
  covers it. A stretch that crosses a span's edge is cut there and each
  piece charged by its midpoint, so a gap that runs from one phase into
  the next is shared between them.

A program without ``repro.obs`` (or one that records nothing for the
cell) reads as nothing: every function returns ``None``.
"""

from __future__ import annotations

from . import trace as trace_lib

MAX_DISAGREE_NS = 1_000_000


def record() -> dict | None:
    """The program's span record, or ``None`` where it keeps none."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.snapshot()


def select(spans: list[dict], bench: list[tuple[int, int]], root: str
           ) -> tuple[list[dict], int] | None:
    """``(calls, worst)``: per benchmark span ``(start, end)`` of the trace,
    in order, the program's call lined up with it, and the largest
    disagreement of lengths in ns. A call is ``{"root": ..., "offset":
    ns, "spans": [(name, start, end), ...]}`` with every span of the call
    (root included) on the trace's clock. ``None`` when fewer roots than
    benchmark spans were recorded."""
    n = len(bench)
    roots = sorted((s for s in spans if s["name"] == root),
                   key=lambda s: s["start_ns"])
    if n == 0 or len(roots) < n:
        return None
    roots = roots[-n:]
    by_root: dict[int, list[dict]] = {r["id"]: [] for r in roots}
    for s in spans:
        if s["root"] in by_root:
            by_root[s["root"]].append(s)
    calls, worst = [], 0
    for r, (bs, be) in zip(roots, bench):
        worst = max(worst, abs((r["end_ns"] - r["start_ns"]) - (be - bs)))
        off = bs - r["start_ns"]
        calls.append({"root": r, "offset": off,
                      "spans": [(s["name"], s["start_ns"] + off,
                                 s["end_ns"] + off)
                                for s in by_root[r["id"]]]})
    return calls, worst


def idle_by_span(trace: dict, bench: list[tuple[int, int]],
                 calls: list[dict]) -> dict[str, int]:
    """Idle ns of chip 0 inside each benchmark span, summed by the name of
    the innermost program span that covers it (``None`` where none)."""
    merged = trace_lib.union(d[:2] for d in trace["device"] if d[3] == 0)
    out: dict[str, int] = {}
    for (bs, be), call in zip(bench, calls):
        sp = call["spans"]
        cuts = sorted({bs, be, *(t for _, s, e in sp for t in (s, e)
                                 if bs < t < be)})
        busy = [(max(s, bs), min(e, be)) for s, e in merged
                if s < be and e > bs]
        for a, b in zip(cuts, cuts[1:]):
            idle = (b - a) - trace_lib.covered(busy, a, b)
            if idle <= 0:
                continue
            mid = (a + b) // 2
            holders = [x for x in sp if x[1] <= mid < x[2]]
            name = min(holders, key=lambda x: x[2] - x[1])[0] if holders \
                else None
            out[name] = out.get(name, 0) + idle
    return out


def calls(ctx, bench_name: str, root: str) -> list[dict] | None:
    """The window's calls of a traced run, lined up with the trace, or
    ``None``: where the program keeps no record, where it recorded fewer
    calls than the benchmark made, or where a root's length differs from
    its benchmark span's by more than :data:`MAX_DISAGREE_NS`. The first
    reader of a run notes, under ``progspans.<bench_name>``, the largest
    disagreement and the idle time per call charged to each program
    span, or why it read nothing."""
    cache = ctx.__dict__.setdefault("_progspans", {})
    if bench_name in cache:
        return cache[bench_name]
    cache[bench_name] = None
    snap = record()
    if ctx.trace is None or snap is None:
        return None
    note = ctx.notes["progspans." + bench_name] = {}
    bench = trace_lib.spans(ctx.trace, bench_name)
    got = None
    if len(bench) == len(ctx.host_spans.get(bench_name, ())):
        got = select(snap["spans"], bench, root)
    if got is None:
        note["refused"] = (f"{len(bench)} {bench_name} spans traced, "
                           f"{sum(s['name'] == root for s in snap['spans'])}"
                           f" {root} recorded")
        return None
    found, worst = got
    note["max_disagree_ms"] = worst / 1e6
    if worst > MAX_DISAGREE_NS:
        note["refused"] = "a root disagrees with its benchmark span"
        return None
    idle = idle_by_span(ctx.trace, bench, found)
    total = sum(idle.values())
    phase = sum(v for k, v in idle.items() if k not in (None, root))
    note.update({
        "calls": len(found),
        "idle_ms_per_call": {str(k): v / 1e6 / len(found)
                             for k, v in sorted(idle.items(),
                                                key=lambda kv: str(kv[0]))},
        "idle_in_phase_share": phase / total if total else None})
    cache[bench_name] = found
    return found


def mean_ms(found: list[dict] | None, name: str) -> float | None:
    """Mean over the calls of the time spent in spans called ``name``."""
    if not found or not any(n == name for c in found
                            for n, _, _ in c["spans"]):
        return None
    per = [sum(e - s for n, s, e in c["spans"] if n == name) for c in found]
    return sum(per) / len(per) / 1e6


def counted(found: list[dict] | None, name: str) -> float | None:
    """What the window's roots counted under ``name`` in all, or ``None``
    where none counted it."""
    if not found or not any(name in c["root"]["counts"] for c in found):
        return None
    return sum(c["root"]["counts"].get(name, 0) for c in found)
