"""The program's spans lined up with the trace (``bench/progspans.py``):
window selection past a set-up call, per-call alignment, the refusal of a
root that disagrees with its benchmark span, idle attribution against a
hand-computed answer on the recorded suite trace, and the six readers
through a traced run of the harness on the CPU."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import discover, progspans

from test_trace import TRACE, sweep_busy

MS = 1_000_000


def _span(sid, name, start, end, parent=None, root=None, counts=None):
    return {"name": name, "id": sid, "parent": parent,
            "root": sid if root is None else root, "start_ns": start,
            "end_ns": end, "error": None, "counts": counts or {}}


def _call(first_id, t0, length, phases, counts=None):
    """A root ``call`` at ``t0`` (program clock) of ``length`` ns with
    children ``[(name, start, end)]`` relative to ``t0``, in closing
    order."""
    kids = [_span(first_id + 1 + i, n, t0 + a, t0 + b, first_id, first_id)
            for i, (n, a, b) in enumerate(phases)]
    return kids + [_span(first_id, "call", t0, t0 + length,
                         counts=counts)]


def test_window_selection_skips_the_setup_call():
    spans = (_call(1, 0, 50 * MS, [("setup.only", 0, 10 * MS)],
                   {"n": 99})
             + _call(10, 100 * MS, 30 * MS, [("p", 0, 5 * MS)], {"n": 1})
             + _call(20, 200 * MS, 40 * MS, [("p", 0, 7 * MS)], {"n": 2}))
    bench = [(1000 * MS, 1030 * MS), (2000 * MS, 2040 * MS)]
    calls, worst = progspans.select(spans, bench, "call")
    assert [c["root"]["id"] for c in calls] == [10, 20]
    assert worst == 0
    assert all(n != "setup.only" for c in calls for n, _, _ in c["spans"])
    assert progspans.counted(calls, "n") == 3
    assert progspans.mean_ms(calls, "p") == pytest.approx(6.0)
    assert progspans.mean_ms(calls, "absent") is None
    # fewer roots than benchmark spans: nothing to read
    assert progspans.select(spans, bench * 2, "call") is None
    assert progspans.select(spans, bench, "other") is None


def test_alignment_moves_each_call_by_its_own_offset():
    # the program's clock runs from an unrelated origin, and the offset
    # differs from call to call (the two clocks drift)
    spans = (_call(1, 5_000 * MS, 20 * MS, [("a", 2 * MS, 9 * MS)])
             + _call(5, 5_100 * MS, 20 * MS, [("a", 4 * MS, 11 * MS)]))
    bench = [(30 * MS, 50 * MS), (130 * MS + 250_000, 150 * MS + 250_000)]
    calls, worst = progspans.select(spans, bench, "call")
    assert [c["offset"] for c in calls] == [30 * MS - 5_000 * MS,
                                            130 * MS + 250_000 - 5_100 * MS]
    assert calls[0]["spans"] == [("a", 32 * MS, 39 * MS),
                                 ("call", 30 * MS, 50 * MS)]
    assert calls[1]["spans"] == [("a", 134 * MS + 250_000,
                                  141 * MS + 250_000),
                                 ("call", 130 * MS + 250_000,
                                  150 * MS + 250_000)]
    assert worst == 0


@pytest.mark.parametrize("diff,ok", [(900_000, True), (1_000_000, True),
                                     (1_000_001, False), (-1_500_000, False)])
def test_root_that_disagrees_with_its_bench_span(monkeypatch, diff, ok):
    spans = (_call(1, 0, 20 * MS, [("p", 0, MS)])
             + _call(3, 100 * MS, 20 * MS + diff, [("p", 0, MS)]))
    monkeypatch.setattr(progspans, "record",
                        lambda: {"spans": spans, "counters": {}})
    trace = {"device": [], "chips": 1,
             "spans": [["window", 0, 200 * MS], ["call", 0, 20 * MS],
                       ["call", 100 * MS, 120 * MS]]}
    ctx = SimpleNamespace(trace=trace, host_spans={"call": [0.02, 0.02]},
                          notes={})
    got = progspans.calls(ctx, "call", "call")
    assert (got is not None) == ok
    note = ctx.notes["progspans.call"]
    assert note["max_disagree_ms"] == abs(diff) / 1e6
    assert ("refused" in note) != ok
    if ok:
        assert progspans.mean_ms(got, "p") == 1.0
        assert note["idle_ms_per_call"]["p"] == 1.0


def test_idle_attribution_on_the_recorded_trace():
    """Two suite calls of the recorded trace, with program spans laid by
    hand: lowering, batching, jitter and gathers fill the first second of
    each call, before the first device operation at 1016.8 ms (1021.9 ms
    in the second call); the relaxation runs from 1000 ms to 1660 ms,
    and the root to its end. The second root is 0.5 ms shorter than its
    benchmark span, which leaves its last 0.5 ms to no program span."""
    bench = [tuple(s[1:]) for s in TRACE["spans"] if s[0] == "suite"]
    phases = [("suite.lower", 0, 600 * MS), ("suite.batch", 600 * MS,
                                              900 * MS),
              ("suite.jitter", 900 * MS, 950 * MS),
              ("suite.gather", 950 * MS, 1000 * MS),
              ("suite.relax", 1000 * MS, 1660 * MS)]
    lengths = [bench[0][1] - bench[0][0], bench[1][1] - bench[1][0] - MS // 2]
    spans = (_call(1, 7 * 10**12, lengths[0], phases)
             + _call(10, 7 * 10**12 + 3 * 10**9, lengths[1], phases))
    spans = [dict(s, name="suite.call") if s["name"] == "call" else s
             for s in spans]
    calls, worst = progspans.select(spans, bench, "suite.call")
    assert worst == MS // 2
    got = progspans.idle_by_span(TRACE, bench, calls)

    def idle(a, b):
        return (b - a) - sweep_busy(TRACE["device"], a, b)

    want = {None: idle(bench[1][1] - MS // 2, bench[1][1])}
    for (bs, _), length in zip(bench, lengths):
        for name, a, b in phases + [("suite.call", 1660 * MS, length)]:
            want[name] = want.get(name, 0) + idle(bs + a, bs + b)
    assert got == want
    # the host phases before the first device operation are idle whole
    assert got["suite.lower"] == 2 * 600 * MS
    assert got["suite.gather"] == 2 * 50 * MS
    assert got[None] == MS // 2
    # every idle nanosecond inside the two benchmark spans is charged once
    total = sum((e - s) - sweep_busy(TRACE["device"], s, e) for s, e in bench)
    assert sum(got.values()) == total


# ---- the readers, through a traced run of the harness on the CPU ----------

TINY = json.loads((Path(__file__).parent / "data" / "tiny.json").read_text())
SEARCH = {"kind": "search", "graph_seed": 1,
          "ga": {"pop_size": 8, "generations": 3, "elite": 2,
                 "tournament": 3, "elite_bias": 0.25, "refine_rounds": 2,
                 "refine_moves": 6},
          "limits": {"mapping_violation": 1e-9, "makespan_s": 1e9}}
NEW = {"search-bl260c": ["baseline_ms.search", "jit_ms.search",
                         "decode_ms.search", "candidate_us.search"],
       "suite-bl260c": ["lower_scenario_ms.suite", "batch_ms.suite"]}


@pytest.mark.parametrize("kind,cell_name", [("search", "search-bl260c"),
                                            ("suite", "suite-bl260c")])
def test_readers_read_a_traced_run(harness, kind, cell_name):
    layer = [m for m in discover.benchmark()["per_layer"]
             if m["name"] in NEW[cell_name]]
    assert [m["workloads"] for m in layer] == [[cell_name]] * len(layer)
    cell = discover.Cell(
        name=f"tiny-{kind}", chips=1, config=TINY["configs"]["tiny"],
        traffic=SEARCH if kind == "search" else dict(TINY["traffic"][kind]),
        kind=discover.kind(kind), end_to_end=[], per_layer=layer,
        readers={m["name"]: discover.reader(m["name"]) for m in layer})
    # a window of one or two calls: a host stall on a shared CPU between
    # a benchmark span and its root (more than 1 ms) would refuse the run
    result = harness.execute(cell, 2**31 + 7, 0.01, True)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == set(NEW[cell_name])
    for name, m in result["metrics"].items():
        assert m["value"] >= 0.0, name
