"""The program's own tracing: host spans, device phase scopes and compile
counters.

``span(name)`` times a piece of host work twice over: as a profiler
``TraceAnnotation`` named ``bhfl/<name>``, so under ``jax.profiler`` it
lands on the host plane on the device trace's clock, and as a
``Span(name, parent, start_ns, end_ns)`` on ``time.perf_counter_ns`` in a
bounded in-memory buffer that ``spans()`` returns.  ``parent`` is the
innermost span open on the same thread when this one opened.

The device phases of a round are ``jax.named_scope`` scopes that the
engine opens at trace time (``TRAIN``, ``EDGE_AGG``, ``GLOBAL_AGG``,
``EVAL``): every HLO instruction beneath one carries its name in its
``op_name``, backward ops included, so a device trace attributes time to
phases by that name.  Scopes are metadata and change no arithmetic.

``counters()`` holds what JAX reports through ``jax.monitoring`` since the
module was imported (or since ``reset()``): backend compiles, persistent
cache hits and misses, and seconds of jaxpr tracing, MLIR lowering,
backend compile (cache retrieval included) and cache retrieval.  Each
counter maps the function JAX names ("" where it names none) to a number.
A function's tracing includes that of the jitted functions it calls, which
report their own tracing too.

Spans are opened around host work only, never per SGD step: every step of
a round runs inside one device call.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Iterator, NamedTuple, Optional

import jax

#: Phase scopes of one global round, opened in ``fl.engine._engine_body``.
TRAIN = "bhfl.train"
EDGE_AGG = "bhfl.edge_agg"
GLOBAL_AGG = "bhfl.global_agg"
EVAL = "bhfl.eval"
PHASES = (TRAIN, EDGE_AGG, GLOBAL_AGG, EVAL)

#: Prefix of the program's spans on the profiler's host plane.
SPAN_PREFIX = "bhfl/"
#: Spans kept; older ones are dropped first.
MAX_SPANS = 4096


class Span(NamedTuple):
    name: str
    parent: Optional[str]
    start_ns: int
    end_ns: int

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


_spans: collections.deque = collections.deque(maxlen=MAX_SPANS)
_open = threading.local()
_lock = threading.Lock()
_counts: dict[str, collections.Counter] = collections.defaultdict(
    collections.Counter)


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """Time the enclosed host work as span ``name``; it closes even when
    the body raises."""
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    parent = stack[-1] if stack else None
    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        stack.append(name)
        start = time.perf_counter_ns()
        try:
            yield
        finally:
            end = time.perf_counter_ns()
            stack.pop()
            _spans.append(Span(name, parent, start, end))


def spans() -> list[Span]:
    """The recorded spans, oldest first (closing order)."""
    return list(_spans)


def counters() -> dict[str, dict[str, float]]:
    """A snapshot of the compile counters: counter -> function -> value."""
    with _lock:
        return {k: dict(v) for k, v in _counts.items()}


def reset() -> None:
    """Clear the recorded spans and the compile counters."""
    _spans.clear()
    with _lock:
        _counts.clear()


# ------------------------------------------------------- compile counters
#: jax.monitoring event -> counter, for events that carry a duration.
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
}
#: jax.monitoring event -> counter, for events that are counted.
_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


def _add(counter: str, fun_name, value: float) -> None:
    with _lock:
        _counts[counter][str(fun_name or "")] += value


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    if event in _DURATIONS:
        _add(_DURATIONS[event], kw.get("fun_name"), duration_secs)
    if event == "/jax/core/compile/backend_compile_duration":
        _add("compiles", kw.get("fun_name"), 1)


def _on_event(event: str, **kw) -> None:
    if event in _EVENTS:
        _add(_EVENTS[event], kw.get("fun_name"), 1)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
