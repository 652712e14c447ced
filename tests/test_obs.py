"""Spans and counters (``repro.obs``): the record itself — nesting, counts
charged to the open root, spans closed by an exception, the bounded ring,
JIT events charged to the innermost span — and the instrumentation of the
two hot paths it serves: a device mapping search and a suite call."""

import numpy as np
import pytest

from repro import obs
from repro.core import (SynthParams, dell_poweredge_1950, generate_app,
                        get_scheduler, simulate_suite)
from repro.search import GAParams, ga_schedule


@pytest.fixture(autouse=True)
def fresh_record():
    obs.reset()
    yield
    obs.reset()


def _by_name(spans):
    return {s["name"]: s for s in spans}


def test_nesting_and_parent_ids():
    with obs.span("a"):
        with obs.span("b"):
            with obs.span("c"):
                pass
        with obs.span("d"):
            pass
    spans = obs.snapshot()["spans"]
    assert [s["name"] for s in spans] == ["c", "b", "d", "a"]   # by closing
    s = _by_name(spans)
    assert s["a"]["parent"] is None and s["a"]["root"] == s["a"]["id"]
    assert s["b"]["parent"] == s["a"]["id"]
    assert s["c"]["parent"] == s["b"]["id"]
    assert s["d"]["parent"] == s["a"]["id"]
    assert {x["root"] for x in spans} == {s["a"]["id"]}
    assert len({x["id"] for x in spans}) == 4
    for x in spans:
        assert x["start_ns"] <= x["end_ns"] and x["error"] is None
    assert s["a"]["start_ns"] <= s["b"]["start_ns"] <= s["c"]["start_ns"]
    assert s["c"]["end_ns"] <= s["b"]["end_ns"] <= s["d"]["start_ns"]
    assert s["d"]["end_ns"] <= s["a"]["end_ns"]


def test_counts_charged_to_the_open_root():
    obs.count("x", 5)                       # no span open: counter only
    with obs.span("call"):
        obs.count("x")
        with obs.span("phase"):
            obs.count("x", 2)
            obs.count("y", 0.5)
    with obs.span("other"):
        obs.count("x", 10)
    snap = obs.snapshot()
    s = _by_name(snap["spans"])
    assert s["call"]["counts"] == {"x": 3, "y": 0.5}
    assert s["phase"]["counts"] == {}
    assert s["other"]["counts"] == {"x": 10}
    assert snap["counters"] == {"x": 18, "y": 0.5}


def test_span_closed_by_an_exception():
    with pytest.raises(ValueError):
        with obs.span("outer"):
            with obs.span("inner"):
                raise ValueError("boom")
    s = _by_name(obs.snapshot()["spans"])
    assert s["inner"]["error"] == "ValueError"
    assert s["outer"]["error"] == "ValueError"
    assert s["inner"]["end_ns"] <= s["outer"]["end_ns"]
    # the thread's stack is empty again: the next span is a root
    with obs.span("next"):
        pass
    nxt = _by_name(obs.snapshot()["spans"])["next"]
    assert nxt["parent"] is None and nxt["error"] is None


def test_ring_drops_its_oldest_entries():
    extra = 7
    for i in range(obs.RING + extra):
        with obs.span(f"s{i}"):
            pass
    spans = obs.snapshot()["spans"]
    assert len(spans) == obs.RING
    assert obs.dropped == extra
    assert spans[0]["name"] == f"s{extra}"
    assert spans[-1]["name"] == f"s{obs.RING + extra - 1}"
    obs.reset()
    assert obs.dropped == 0 and obs.snapshot() == {"spans": [],
                                                   "counters": {}}


def test_jit_trace_charged_to_the_innermost_span():
    import jax

    def f(x):
        return x * 3.0 + 1.0

    with obs.span("call"):
        with obs.span("prepare"):
            pass
        with obs.span("step"):
            jax.jit(f)(np.arange(4.0)).block_until_ready()
    snap = obs.snapshot()
    s = _by_name(snap["spans"])
    assert s["step"]["counts"]["jit.traces"] >= 1
    assert s["step"]["counts"]["jit.trace_s"] > 0
    assert s["step"]["counts"]["jit.compile_s"] > 0
    assert "jit.traces" not in s["prepare"]["counts"]
    # the root holds everything counted under it
    for k, v in s["step"]["counts"].items():
        assert s["call"]["counts"][k] == pytest.approx(v)
    assert snap["counters"]["jit.traces"] == s["call"]["counts"]["jit.traces"]


PHASES = ["ga.baseline", "ga.inputs", "ga.generations", "ga.refine",
          "ga.decode"]


def test_device_search_spans_and_candidates():
    app = generate_app(SynthParams(n_tasks=(10, 16)), 1)
    m = dell_poweredge_1950()
    par = GAParams(pop_size=8, generations=3, refine_rounds=3,
                   refine_moves=6, device=True)
    ga_schedule(app, m, seed=3, params=par)
    snap = obs.snapshot()
    spans = snap["spans"]
    root = spans[-1]
    assert root["name"] == "ga.schedule" and root["parent"] is None
    children = [s for s in spans if s["parent"] == root["id"]]
    assert [s["name"] for s in children] == PHASES
    assert all(s["root"] == root["id"] for s in spans)
    c = root["counts"]
    assert c["ga.generations"] == par.generations
    assert 1 <= c["ga.refine_rounds"] <= par.refine_rounds
    full = len(app.tasks) * (m.n_cores - 1)
    assert c["ga.candidates"] == (par.generations + 1) * par.pop_size \
        + c["ga.refine_rounds"] * min(par.refine_moves, full)
    # the generation step is traced at most once a search, never per
    # generation
    assert c["ga.step_traces"] <= 1
    assert c.get("lower.population_arrays.hit", 0) \
        + c.get("lower.population_arrays.miss", 0) == 1
    assert snap["counters"]["ga.candidates"] == c["ga.candidates"]


def test_suite_call_counts_its_scenarios():
    apps = [generate_app(SynthParams(n_tasks=(8, 12)), s) for s in (1, 2)]
    m = dell_poweredge_1950()
    scheds = [get_scheduler("engine")(a, m) for a in apps]
    b = 6
    simulate_suite(apps * 3, m, scheds * 3, jitter=0.01, seeds=range(b),
                   backend="pallas")
    spans = obs.snapshot()["spans"]
    root = spans[-1]
    assert root["name"] == "suite.call" and root["parent"] is None
    assert root["counts"]["suite.scenarios"] == b
    assert [s["name"] for s in spans if s["parent"] == root["id"]] == \
        ["suite.lower", "suite.batch", "suite.jitter", "suite.gather",
         "suite.relax"]
    assert all(s["root"] == root["id"] for s in spans)
    assert root["counts"].get("lower.graph_arrays.hit", 0) \
        + root["counts"].get("lower.graph_arrays.miss", 0) == b
