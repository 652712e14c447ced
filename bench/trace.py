"""From a profiler trace to the numbers the per-layer readers use.

A trace is first reduced to plain events (:func:`extract`):

* ``device``: ``[start_ns, end_ns, name]`` of every operation that ran
  on a chip (the ``XLA Ops`` line of each TPU plane), with the plane's
  index;
* ``spans``: ``[name, start_ns, end_ns]`` of the benchmark's own host
  spans (``jax.profiler.TraceAnnotation`` names that start with
  ``bench:``), on the same clock.

Everything after that is arithmetic on those lists, kept here so that
every run computes the same number in the same way, and checked by
``bench/tests/test_trace.py`` on a small recorded trace.
"""

from __future__ import annotations

import glob
import os

SPAN_PREFIX = "bench:"


def extract(log_dir: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no trace under {log_dir}")
    data = ProfileData.from_file(files[-1])
    device: list[list] = []
    spans: list[list] = []
    chips = 0
    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and name[len("/device:TPU:"):].isdigit():
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get("XLA Ops") or lines.get("XLA Modules")
            if line is None:
                continue
            for ev in line.events:
                device.append([int(ev.start_ns), int(ev.end_ns),
                               short_name(ev.name), chips])
            chips += 1
        elif name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.name[len(SPAN_PREFIX):],
                                      int(ev.start_ns), int(ev.end_ns)])
    device.sort()
    spans.sort(key=lambda s: s[1])
    return {"device": device, "spans": spans, "chips": chips}


def short_name(hlo: str) -> str:
    """``%name`` of an HLO instruction's text, with the target of a
    custom call (``%closed_call.4 tpu_custom_call``)."""
    name = hlo.split(" = ", 1)[0]
    key = 'custom_call_target="'
    if key in hlo:
        name += " " + hlo.split(key, 1)[1].split('"', 1)[0]
    return name


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted ``(start, end)`` covering the given intervals."""
    out: list[list[int]] = []
    for s, e in sorted((int(iv[0]), int(iv[1])) for iv in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi)`` covered by merged intervals."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)


def window(trace: dict) -> tuple[int, int]:
    """The measured window: the ``window`` span."""
    for name, s, e in trace["spans"]:
        if name == "window":
            return s, e
    raise ValueError("trace has no bench:window span")


def busy_s(trace: dict) -> float:
    """Seconds in the window in which an operation ran, averaged over
    the chips traced."""
    lo, hi = window(trace)
    chips = max(1, trace.get("chips", 1))
    total = 0
    for c in range(chips):
        merged = union(d[:2] for d in trace["device"] if d[3] == c)
        total += covered(merged, lo, hi)
    return total / chips / 1e9


def window_s(trace: dict) -> float:
    lo, hi = window(trace)
    return (hi - lo) / 1e9


def idle_share_pct(trace: dict) -> float:
    return 100.0 * (1.0 - busy_s(trace) / window_s(trace))


def spans(trace: dict, name: str) -> list[tuple[int, int]]:
    return [(s, e) for n, s, e in trace["spans"] if n == name]


def busy_inside_s(trace: dict, name: str) -> list[float]:
    """Per span called ``name``, seconds in which an operation ran on
    the device inside it (chip 0)."""
    merged = union(d[:2] for d in trace["device"] if d[3] == 0)
    return [covered(merged, s, e) / 1e9 for s, e in spans(trace, name)]


def first_device_op_s(trace: dict, name: str) -> list[float]:
    """Per span called ``name`` that holds a device operation, seconds
    from the span's start to the first operation that starts inside it."""
    starts = [d[0] for d in trace["device"] if d[3] == 0]
    out = []
    i = 0
    for s, e in spans(trace, name):
        while i < len(starts) and starts[i] < s:
            i += 1
        if i < len(starts) and starts[i] < e:
            out.append((starts[i] - s) / 1e9)
    return out


def top_ops(trace: dict, n: int = 10) -> list[list]:
    """The device operations that took most time in the window:
    ``[name, seconds]``, summed by name."""
    lo, hi = window(trace)
    total: dict[str, int] = {}
    for s, e, name, _ in trace["device"]:
        t = max(0, min(e, hi) - max(s, lo))
        if t:
            total[name] = total.get(name, 0) + t
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def idle_gaps(trace: dict, n: int = 10) -> list[list]:
    """The longest idle gaps of chip 0 in the window, each named by the
    innermost host span that covers its middle (``between calls`` where
    none):
    ``[what the host was doing, seconds]``."""
    lo, hi = window(trace)
    merged = union(d[:2] for d in trace["device"] if d[3] == 0)
    gaps = []
    t = lo
    for s, e in merged:
        if s > t:
            gaps.append((min(s, hi) - t, t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((hi - t, t, hi))
    gaps = sorted((g for g in gaps if g[0] > 0), reverse=True)[:n]
    inner = [sp for sp in trace["spans"] if sp[0] != "window"]
    out = []
    for length, a, b in gaps:
        mid = (a + b) // 2
        holders = [sp for sp in inner if sp[1] <= mid < sp[2]]
        name = min(holders, key=lambda sp: sp[2] - sp[1])[0] if holders \
            else "between calls"
        out.append([name, length / 1e9])
    return out
