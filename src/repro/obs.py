"""Spans and counters of the scheduling hot paths, for operators.

The mapping search (``search/ga.py``, ``search/device.py``,
``search/local.py``) and the suite validator (``core/sim_engine.py``)
mark their phases with :class:`span` and count their work with
:func:`count`. Both are always on and cost a few microseconds, so they
sit at phase granularity: about six spans per search or suite call.

* ``span(name)`` (or ``@spanned(name)`` around a whole function)
  records its name, an id, the id of the enclosing span
  on the same thread (``parent``) and of the outermost one (``root``),
  and start and end on ``time.perf_counter_ns()``. A span left by an
  exception is closed and names the exception under ``error``. While
  JAX is loaded each span is also a ``jax.profiler.TraceAnnotation``,
  so under ``jax.profiler.trace(dir)`` it shows on the host plane, on
  the same clock as the device's operations.
* ``count(name, n)`` adds to a process-wide counter and to the
  ``counts`` of the root span open on this thread, so the counts of one
  call can be read apart from every other call.
* JIT events (``jax.monitoring``) become the counters ``jit.traces``,
  ``jit.trace_s``, ``jit.lower_s``, ``jit.compile_s`` (a persistent-cache
  load included) and ``jit.cache_loads``; each is also charged to the
  innermost open span, so a span's ``counts`` say which phase traced or
  compiled.

Closed spans are kept in a ring of the last :data:`RING`; ``dropped``
counts those that fell out. Each thread keeps its own counters and span
stack, so recording takes no lock. :func:`snapshot` returns the record and
:func:`reset` clears it. The module imports no JAX itself: it hooks
into JAX the first time a span opens after JAX is loaded, so the
NumPy-only paths stay free of it.
"""

from __future__ import annotations

import collections
import functools
import itertools
import sys
import threading
import time

#: about 2,300 suite calls of seven spans each, about 10 MB of records: a
#: minute of short suite calls stays whole
RING = 16384

_ring: collections.deque = collections.deque(maxlen=RING)
_threads: list = []         # every thread's _State, for snapshot and reset
_lock = threading.Lock()    # guards _threads and the JAX hook only
_local = threading.local()
_ids = itertools.count(1)
_annotation = None          # jax.profiler.TraceAnnotation once hooked

# jax.monitoring duration events -> (event counter or None, seconds counter)
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": ("jit.traces", "jit.trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": (None, "jit.lower_s"),
    "/jax/core/compile/backend_compile_duration": (None, "jit.compile_s"),
}


class _State:
    """One thread's open spans, counters and number of closed spans: only
    that thread writes them, so the hot path takes no lock."""

    __slots__ = ("stack", "counts", "closed")

    def __init__(self):
        self.stack: list = []
        self.counts: dict = {}
        self.closed = 0


def _state() -> _State:
    st = getattr(_local, "state", None)
    if st is None:
        st = _local.state = _State()
        with _lock:
            _threads.append(st)
    return st


def __getattr__(name: str):
    if name == "dropped":       # closed spans that fell out of the ring
        return max(0, sum(st.closed for st in _threads) - len(_ring))
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _add(counts: dict, name: str, n: float) -> None:
    counts[name] = counts.get(name, 0) + n


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name`` and to the open root span's."""
    st = _state()
    _add(st.counts, name, n)
    if st.stack:
        _add(st.stack[0]["counts"], name, n)


def _charge_jit(name: str, n: float) -> None:
    count(name, n)
    stack = _state().stack
    if len(stack) > 1:
        _add(stack[-1]["counts"], name, n)


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _charge_jit("jit.cache_loads", 1)


def _on_duration(event: str, duration: float, **_) -> None:
    names = _DURATIONS.get(event)
    if names is None:
        return
    if names[0] is not None:
        _charge_jit(names[0], 1)
    _charge_jit(names[1], duration)


def _hook_jax():
    """Register the JIT listeners and take the profiler's annotation, once
    JAX is loaded (a profiler session needs JAX, so nothing is missed)."""
    global _annotation
    if "jax" not in sys.modules:
        return None
    from jax import monitoring
    from jax.profiler import TraceAnnotation
    with _lock:
        if _annotation is None:
            monitoring.register_event_listener(_on_event)
            monitoring.register_event_duration_secs_listener(_on_duration)
            _annotation = TraceAnnotation
    return _annotation


class span:
    """``with span("ga.decode"): ...`` — one phase of a call."""

    __slots__ = ("rec", "ann", "st")

    def __init__(self, name: str):
        self.rec = {"name": name}

    def __enter__(self) -> "span":
        self.st = st = _state()
        stack = st.stack
        rec = self.rec
        rec["id"] = sid = next(_ids)
        if stack:
            rec["parent"], rec["root"] = stack[-1]["id"], stack[0]["id"]
        else:
            rec["parent"], rec["root"] = None, sid
        rec["error"] = None
        rec["counts"] = {}
        ann = _annotation or _hook_jax()
        self.ann = ann(rec["name"]) if ann is not None else None
        if self.ann is not None:
            self.ann.__enter__()
        stack.append(rec)
        rec["start_ns"] = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        rec = self.rec
        rec["end_ns"] = time.perf_counter_ns()
        st = self.st
        st.stack.pop()
        if exc_type is not None:
            rec["error"] = exc_type.__name__
        if self.ann is not None:
            self.ann.__exit__(exc_type, exc, tb)
        st.closed += 1
        _ring.append(rec)


def spanned(name: str):
    """Decorator: every call of the function in one span, the teardown of
    its frame included, so the span ends where the caller's clock does."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return call
    return wrap


def snapshot() -> dict:
    """``{"spans": [...], "counters": {...}}``: the closed spans still in
    the ring, oldest first by closing, and the process-wide counters."""
    spans = [dict(r, counts=dict(r["counts"])) for r in list(_ring)]
    counters: dict = {}
    for st in list(_threads):
        for k, v in list(st.counts.items()):
            _add(counters, k, v)
    return {"spans": spans, "counters": counters}


def reset() -> None:
    """Forget every closed span and counter (open spans still close)."""
    _ring.clear()
    for st in list(_threads):
        st.counts.clear()
        st.closed = 0
