"""The reduction from trace to metrics, on a small recorded trace: the
device operations and spans of two calls of a traced ``suite-bl260c``
run on one TPU v5 lite (``data/trace_suite.json``). Each number is
checked against a sweep over the raw events written out here."""

import json
from pathlib import Path

import pytest

from bench import trace

TRACE = json.loads((Path(__file__).parent / "data" / "trace_suite.json")
                   .read_text())


def sweep_busy(events, lo, hi):
    """Nanoseconds of [lo, hi) in which at least one event runs: a
    +1/-1 sweep over clipped event edges."""
    edges = []
    for s, e, *_ in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            edges += [(s, 1), (e, -1)]
    busy, depth, last = 0, 0, None
    for t, d in sorted(edges, key=lambda x: (x[0], -x[1])):
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_busy_and_idle_share():
    lo, hi = trace.window(TRACE)
    want = sweep_busy(TRACE["device"], lo, hi)
    assert want > 0
    assert trace.busy_s(TRACE) == pytest.approx(want / 1e9, abs=1e-12)
    assert trace.window_s(TRACE) == pytest.approx((hi - lo) / 1e9)
    assert trace.idle_share_pct(TRACE) == pytest.approx(
        100 * (1 - want / (hi - lo)))


def test_device_time_inside_each_span():
    got = trace.busy_inside_s(TRACE, "suite")
    spans = trace.spans(TRACE, "suite")
    assert len(got) == len(spans) == 2
    for (s, e), g in zip(spans, got):
        assert g == pytest.approx(sweep_busy(TRACE["device"], s, e) / 1e9,
                                  abs=1e-12)
    # the two spans hold all of the device time of the window
    assert sum(got) == pytest.approx(trace.busy_s(TRACE), rel=1e-9)


def test_first_device_op_after_span_start():
    got = trace.first_device_op_s(TRACE, "suite")
    for (s, e), g in zip(trace.spans(TRACE, "suite"), got):
        first = min(d[0] for d in TRACE["device"] if s <= d[0] < e)
        assert g == pytest.approx((first - s) / 1e9)
    # the host lowers for most of a second before the kernel starts
    assert all(0.5 < g < 1.5 for g in got)


def test_empty_span_has_no_first_op():
    t = dict(TRACE, spans=TRACE["spans"] + [["idle", 0, 10]])
    assert trace.first_device_op_s(t, "idle") == []
    assert trace.busy_inside_s(t, "idle") == [0.0]


def test_breakdown():
    ops = trace.top_ops(TRACE)
    assert len(ops) <= 10
    # the sweep loop and the relaxation kernel inside it
    assert {o[0] for o in ops[:2]} == {"%while",
                                       "%closed_call.4 tpu_custom_call"}
    assert ops == sorted(ops, key=lambda o: -o[1])
    gaps = trace.idle_gaps(TRACE)
    assert gaps and all(g[0] in ("suite", "between calls") for g in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    lo, hi = trace.window(TRACE)
    idle = (hi - lo) / 1e9 - trace.busy_s(TRACE)
    assert sum(g[1] for g in gaps) <= idle + 1e-9


def test_short_name():
    hlo = ('%closed_call.4 = f32[320,896]{1,0} custom-call(f32[320,896]{1,0} '
           '%copy.9), custom_call_target="tpu_custom_call"')
    assert trace.short_name(hlo) == "%closed_call.4 tpu_custom_call"
    assert trace.short_name("%copy.9 = f32[8]{0} copy(%x)") == "%copy.9"
