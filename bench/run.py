"""On-chip benchmark of the AMTHA mapping system.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on:
set-up (inputs from the seed, every shape the window uses warmed), a
measured window of ``--seconds``, then the comparison with the plain
reference that decides ``correct``. With ``--trace 0`` the result holds
the cell's end-to-end metrics; with ``--trace 1`` the window runs under
the JAX profiler and the result holds its per-layer metrics.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with a trace
``breakdown``), and last ``checks``: each number compared, beside its
limit. The same numbers are the last lines of standard error.

It exits non-zero and prints no result when JAX finds no TPU or fewer
chips than the cell asks for, or when the program is not next to the
benchmark. Compiled programs are kept in JAX's persistent cache in the
checkout (``repro.compile_cache``), so only a checkout's first run of a
cell compiles.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

from . import discover, program, trace as trace_lib, work  # noqa: E402

OUT_DIR = discover.ROOT / ".bench_out"


class Run:
    """What a workload kind sees of one run: the cell, the seed, the window's
    length, seeded generators, and host spans."""

    def __init__(self, cell: discover.Cell, seed: int, seconds: float,
                 tracing: bool):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.tracing = tracing
        self.host_spans: dict[str, list[float]] = {}
        self.info: dict = {}
        self._annotation = None
        if tracing:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation

    def rng(self, *tags: int) -> np.random.Generator:
        """A generator drawn from the run's seed and ``tags``."""
        return np.random.default_rng(
            np.random.SeedSequence([self.seed % 2**64, *tags]))

    def seed31(self, *tags: int) -> int:
        """A seed for the program, below 2**31, from the run's seed."""
        return int(self.rng(*tags).integers(0, 2**31 - 1))

    @contextlib.contextmanager
    def span(self, name: str):
        """Time a call into a layer on the host clock, and mark it on the
        trace when the run is traced."""
        ctx = self._annotation(trace_lib.SPAN_PREFIX + name) \
            if self._annotation else contextlib.nullcontext()
        with ctx:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.host_spans.setdefault(name, []).append(
                    time.perf_counter() - t0)


class Compiles:
    """Counts compilations, persistent-cache loads and traces while
    ``active``."""

    def __init__(self):
        import jax.monitoring as mon
        self.active = False
        self.counts = {"backend_compile_or_load": 0, "cache_loads": 0,
                       "traces": 0}

        def on_event(event, **_):
            if self.active and event == "/jax/compilation_cache/cache_hits":
                self.counts["cache_loads"] += 1

        def on_duration(event, duration, **_):
            if not self.active:
                return
            if event == "/jax/core/compile/backend_compile_duration":
                self.counts["backend_compile_or_load"] += 1
            elif event == "/jax/core/compile/jaxpr_trace_duration":
                self.counts["traces"] += 1

        mon.register_event_listener(on_event)
        mon.register_event_duration_secs_listener(on_duration)

    def summary(self) -> dict:
        c = self.counts
        return {"compiles": c["backend_compile_or_load"] - c["cache_loads"],
                "cache_loads": c["cache_loads"], "traces": c["traces"]}


_COMPILES: Compiles | None = None


def prepare(require_tpu: bool = True, chips: int = 1):
    """Import the program, turn on the compile cache, and return JAX's
    devices, or raise ``SystemExit`` with a message when the program is
    missing or the chips are not there."""
    global _COMPILES
    # the TPU runtime's logs stay in the checkout, like everything else
    # the benchmark writes
    os.environ.setdefault("TPU_LOG_DIR", str(OUT_DIR / "tpu_logs"))
    program.import_path()
    try:
        from repro.compile_cache import use_compile_cache
    except ImportError as e:
        raise SystemExit(f"bench: the program (src/repro) is not next to "
                         f"the benchmark ({e})")
    use_compile_cache()
    import jax
    # every program, however quick to compile, goes to the cache, so a
    # second run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX platform "
                         f"{devices[0].platform!r}); the benchmark does "
                         f"not run on the CPU")
    if require_tpu and len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX "
                         f"found {len(devices)}")
    if _COMPILES is None:
        _COMPILES = Compiles()
    return devices


def _profile_options():
    from jax.profiler import ProfileOptions
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    return opts


class Ctx:
    """What a per-layer reader sees."""

    def __init__(self, run: Run, trace: dict | None, work_done: dict,
                 peak: dict | None):
        self.host_spans = run.host_spans
        self.trace = trace
        self.work = work_done
        self.peak = peak
        self.notes: dict = {}


def _number(x):
    return None if x is None or not math.isfinite(x) else float(x)


def _finite(checks: dict) -> dict:
    """Checks with a number that JSON can carry: a reading that is not
    finite (no answer, or a NaN) becomes the largest float, which fails
    any limit."""
    return {k: {"value": float(v["value"]) if math.isfinite(v["value"])
                else sys.float_info.max, "limit": v["limit"]}
            for k, v in checks.items()}


def execute(cell: discover.Cell, seed: int, seconds: float, tracing: bool,
            *, t_start: float | None = None, control: bool = False) -> dict:
    """One run of ``cell``; returns the result object (and, with
    ``control``, the control's readings under ``control_checks``)."""
    import jax
    t_start = time.perf_counter() if t_start is None else t_start
    devices = jax.devices()
    dev = devices[0]
    peak = work.peaks(dev.device_kind) if dev.platform == "tpu" else None
    run = Run(cell, seed, seconds, tracing)
    kind = cell.kind
    state = kind.setup(run)
    trace_dir = OUT_DIR / f"trace-{cell.name}"
    if tracing:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=_profile_options())
    setup_s = time.perf_counter() - t_start
    _COMPILES.counts = dict.fromkeys(_COMPILES.counts, 0)
    _COMPILES.active = True
    try:
        with run.span("window"):
            out = kind.window(run, state)
    finally:
        _COMPILES.active = False
        if tracing:
            jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    e2e = dict(kind.metrics(run, state, out), setup_s=setup_s)
    result: dict = {"correct": False, "attempted": int(out["attempted"]),
                    "failed": int(out["failed"])}
    metrics = {}
    breakdown = None
    if tracing:
        tr = trace_lib.extract(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        # the reduced trace (device operations and the benchmark's spans)
        # stays for a reader of the run; the profile itself does not
        (OUT_DIR / f"{cell.name}-trace.json").write_text(json.dumps(tr))
        ctx = Ctx(run, tr, kind.work(run, state, out), peak)
        for m in cell.per_layer:
            v = _number(cell.readers[m["name"]].read(ctx))
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        run.info["readers"] = ctx.notes
        device["busy_s"] = trace_lib.busy_s(tr)
        device["window_s"] = trace_lib.window_s(tr)
        breakdown = {"device_ops": trace_lib.top_ops(tr),
                     "idle_gaps": trace_lib.idle_gaps(tr)}
    else:
        for m in cell.end_to_end:
            v = _number(e2e.get(m["name"]))
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    info = {"cell": cell.name, "seed": run.seed, "setup_s": setup_s,
            "window": _COMPILES.summary(), "host_spans_s": {
                k: {"n": len(v), "mean": sum(v) / len(v)}
                for k, v in run.host_spans.items()}, **run.info}
    print(json.dumps({"info": info}), flush=True)
    # the reference runs once the program's state is gone, so it sets no
    # memory peak and holds nothing of the program's
    kind.release(state, out)
    gc.collect()
    checks = _finite(kind.check(run, state, out))
    result["correct"] = all(v["value"] <= v["limit"] for v in checks.values())
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    if control:
        result["control_checks"] = _finite(kind.check(run, state, out,
                                                     control=True))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = discover.resolve(args.workload)
        prepare(require_tpu=True, chips=cell.chips)
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 1
    except (FileNotFoundError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    result = execute(cell, args.seed, args.seconds, bool(args.trace),
                     t_start=T_START)
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
