"""Process-wide host spans of the serving path, with the compiles in them.

``with span("engine.tick"): ...`` does three things:

* opens ``jax.profiler.TraceAnnotation(name)``, so that a profiled run
  shows the span on the profiler's host plane, on the same clock as the
  device's events (a no-op when no profiler runs);
* keeps a thread-local stack of open spans, so each span knows its
  parent and its children's time;
* on exit, appends one :class:`Record` to a bounded ring (the oldest
  records drop first) and adds to per-name totals.

One ``jax.monitoring`` listener, registered once at import, sends each
compile-time event to the innermost open span of the thread that
compiles: trace, lowering and backend-compile seconds into
``compile_s`` (of nested traces, a jit traced inside another's trace,
the outermost alone), and each backend compile into ``compiles`` or,
when the persistent compilation cache served it, ``cache_loads``.  A
compile is counted in its innermost span only, so the sum of
``compile_s`` over all records counts each compile once.

The recorder is always on and process-wide, like JAX's own monitoring:
readers run after the code that made the spans has gone.  Spans sit at
request, engine-step and admission granularity (milliseconds to
seconds), never inside a jitted function, and their boundaries wait on
no device value.
"""
from __future__ import annotations

import collections
import functools
import itertools
import threading
import time
from typing import NamedTuple

import jax

RING_SIZE = 1 << 16

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_COMPILE_EVENTS = frozenset((
    _TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    _BACKEND_COMPILE,
))
# recorded inside the backend-compile event of a program the persistent
# cache served (whose duration already holds the retrieval)
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


class Record(NamedTuple):
    """One closed span.  ``index`` numbers spans in the order they
    opened; ``parent`` is the enclosing span's index (-1 at the top of
    its thread).  ``compile_s``, ``compiles`` and ``cache_loads`` count
    the span's own compiles, not its children's."""

    index: int
    name: str
    thread: str
    start_ns: int
    end_ns: int
    parent: int
    compile_s: float
    compiles: int
    cache_loads: int

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class span:
    """Context manager recording one span (see the module docstring).
    After exit, ``record`` holds its :class:`Record`."""

    __slots__ = ("name", "index", "parent", "start_ns", "child_ns",
                 "compile_s", "compiles", "cache_loads", "record",
                 "_traces", "_annotation")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        stack = _stack()
        self.index = next(_ids)
        self.parent = stack[-1].index if stack else -1
        self.child_ns = 0
        self.compile_s = 0.0
        self.compiles = self.cache_loads = 0
        self._traces: list[tuple[float, float]] = []  # (end, seconds)
        stack.append(self)
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        end_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        stack = _stack()
        stack.pop()
        dur_ns = end_ns - self.start_ns
        if stack:
            stack[-1].child_ns += dur_ns
        rec = self.record = Record(
            self.index, self.name, threading.current_thread().name,
            self.start_ns, end_ns, self.parent, self.compile_s,
            self.compiles, self.cache_loads)
        with _lock:
            _ring.append(rec)
            t = _totals.get(self.name)
            if t is None:
                t = _totals[self.name] = dict.fromkeys(
                    ("count", "total_s", "self_s", "compile_s", "compiles",
                     "max_s"), 0)
            t["count"] += 1
            t["total_s"] += rec.seconds
            t["self_s"] += (dur_ns - self.child_ns) * 1e-9
            t["compile_s"] += self.compile_s
            t["compiles"] += self.compiles
            t["max_s"] = max(t["max_s"], rec.seconds)
        return False


def traced(name: str):
    """Decorator: run each call of the function inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def records() -> list[Record]:
    """The ring's records, oldest first (at most ``RING_SIZE``)."""
    with _lock:
        return list(_ring)


def totals() -> dict[str, dict]:
    """Per span name since the last :func:`reset`: ``count``,
    ``total_s``, ``self_s`` (duration less children), ``compile_s``,
    ``compiles`` and ``max_s``."""
    with _lock:
        return {name: dict(t) for name, t in sorted(_totals.items())}


def reset() -> None:
    """Forget every closed span (spans open now still record)."""
    with _lock:
        _ring.clear()
        _totals.clear()


_lock = threading.Lock()
_ring: collections.deque[Record] = collections.deque(maxlen=RING_SIZE)
_totals: dict[str, dict] = {}
_ids = itertools.count()
_local = threading.local()


def _stack() -> list[span]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _on_duration(event: str, secs: float, **_) -> None:
    if event == _CACHE_RETRIEVAL:
        _local.cache_hit = True
        return
    if event not in _COMPILE_EVENTS:
        return
    hit = False
    if event == _BACKEND_COMPILE:
        hit = getattr(_local, "cache_hit", False)
        _local.cache_hit = False
    stack = _stack()
    if not stack:
        return
    top = stack[-1]
    if event == _TRACE:
        # a jit traced inside another's trace reports before it, and the
        # outer duration holds the inner one: replace what it contains
        now = time.perf_counter()
        kept, inner = [], 0.0
        for end, d in top._traces:
            if end > now - secs:
                inner += d
            else:
                kept.append((end, d))
        kept.append((now, secs))
        top._traces = kept
        secs = max(secs - inner, 0.0)
    top.compile_s += secs
    if event == _BACKEND_COMPILE:
        if hit:
            top.cache_loads += 1
        else:
            top.compiles += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)

__all__ = ["RING_SIZE", "Record", "records", "reset", "span", "totals",
           "traced"]
