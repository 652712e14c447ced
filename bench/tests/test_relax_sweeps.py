"""The reader of ``relax_sweeps.search`` on made-up span records: sweeps
per fitness call over the window's searches, the bound per call in its
note, and nothing where the program counts no sweeps."""

from types import SimpleNamespace

import pytest

from bench import discover, progspans

MS = 1_000_000
READER = discover.reader("relax_sweeps.search")


def _search(sid, t0, counts):
    return {"name": "ga.schedule", "id": sid, "parent": None, "root": sid,
            "start_ns": t0, "end_ns": t0 + 20 * MS, "error": None,
            "counts": counts}


def _ctx(monkeypatch, roots):
    monkeypatch.setattr(progspans, "record",
                        lambda: {"spans": roots, "counters": {}})
    bench = [["search", 100 * MS * i, 100 * MS * i + 20 * MS]
             for i in range(1, len(roots))]
    trace = {"device": [], "chips": 1,
             "spans": [["window", 0, 10**10]] + bench}
    return SimpleNamespace(trace=trace, notes={},
                           host_spans={"search": [0.02] * len(bench)})


def test_sweeps_per_call_over_the_window(monkeypatch):
    # the set-up search (the first root) falls out of the window
    roots = [_search(1, 0, {"relax.calls": 5, "relax.sweeps": 5000,
                            "relax.sweep_bound": 5 * 759}),
             _search(2, 10 * MS, {"relax.calls": 28, "relax.sweeps": 3500,
                                  "relax.sweep_bound": 28 * 759}),
             _search(3, 50 * MS, {"relax.calls": 27, "relax.sweeps": 2930,
                                  "relax.sweep_bound": 27 * 759})]
    ctx = _ctx(monkeypatch, roots)
    assert READER.read(ctx) == pytest.approx(6430 / 55)
    assert ctx.notes["relax_sweeps.search"] == {
        "sweep_bound_per_call": pytest.approx(759.0),
        "calls_per_search": pytest.approx(27.5)}


@pytest.mark.parametrize("counts", [
    {},                                         # a program without the count
    {"relax.calls": 0, "relax.sweeps": 0},      # the scan fitness
])
def test_nothing_where_no_sweeps_are_counted(monkeypatch, counts):
    roots = [_search(1, 0, dict(counts)), _search(2, 10 * MS, dict(counts))]
    ctx = _ctx(monkeypatch, roots)
    assert READER.read(ctx) is None
    assert "relax_sweeps.search" not in ctx.notes


def test_registered_for_the_search_cell():
    m = {m["name"]: m for m in discover.benchmark()["per_layer"]}
    assert m["relax_sweeps.search"]["workloads"] == ["search-bl260c"]
    assert m["relax_sweeps.search"]["moves"] == "search_s"
