"""Low-overhead tracing spans with Chrome-trace/Perfetto export.

Usage::

    from repro.obs import tracing

    with tracing.span("compile.search", stages=3):
        ...

    @tracing.traced("serve.admit")
    def _admit(self): ...

Spans nest through a per-thread stack; each completed span records
``(name, start, end, depth, parent, args)`` into a bounded ring buffer on
the process-wide :data:`TRACER`.  Recording is append-only under a lock —
no I/O, no device syncs — and a disabled tracer short-circuits to a
no-op, so instrumented hot paths pay one attribute read when tracing is
off.  An enabled span is also a ``jax.profiler.TraceAnnotation`` of the
same name, so a profiler trace taken around it shows it on the host's
timeline beside the device's ops (one call into the profiler when none is
running).

A ``jax.monitoring`` listener, registered at import, records jax's own
compile stages of every jit on :data:`TRACER` (``jit.trace``,
``jit.lower``, and ``jit.compile`` or, when the persistent compilation
cache held the executable, ``jit.cache_load``; each with the ``fun`` jax
names) and counts the persistent cache's hits and misses
(``jit_cache_hits`` / ``jit_cache_misses`` in the metrics registry).
jax reports a stage once it is done, so these spans are recorded after
the fact, with start = end - duration, and never enclose a span opened
meanwhile.

:meth:`Tracer.to_chrome` renders the buffer as Chrome-trace JSON
(``"X"`` complete events, microsecond timestamps), which Perfetto and
``chrome://tracing`` load directly; ``tools/trace_export`` and
``serve.py --trace-out`` wrap it.

The clock is injectable (see ``obs.clock``) so ordering/nesting tests run
on a :class:`~repro.obs.clock.ManualClock` instead of sleeping.
"""
from __future__ import annotations

import json
import os
import threading
from collections import deque
from contextlib import contextmanager
from functools import wraps
from typing import Any, Dict, List, Optional

from jax import monitoring
from jax.profiler import TraceAnnotation

from . import metrics
from .clock import Clock, perf_clock


class Span:
    __slots__ = ("name", "start", "end", "depth", "parent", "tid", "args")

    def __init__(self, name: str, start: float, end: float, depth: int,
                 parent: Optional[str], tid: int,
                 args: Optional[Dict[str, Any]]):
        self.name = name
        self.start = start
        self.end = end
        self.depth = depth
        self.parent = parent
        self.tid = tid
        self.args = args or {}

    @property
    def duration(self) -> float:
        return self.end - self.start

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name, "start": self.start, "end": self.end,
            "depth": self.depth, "parent": self.parent, "tid": self.tid,
            "args": self.args,
        }


class _OpenSpan:
    __slots__ = ("name", "start", "depth", "parent", "args")

    def __init__(self, name, start, depth, parent, args):
        self.name = name
        self.start = start
        self.depth = depth
        self.parent = parent
        self.args = args


class Tracer:
    """Bounded in-process span recorder."""

    def __init__(self, clock: Optional[Clock] = None,
                 max_spans: int = 200_000):
        self._clock: Clock = clock or perf_clock
        self._spans: deque = deque(maxlen=max_spans)
        self._local = threading.local()
        self._lock = threading.Lock()
        self.enabled = True
        self._origin = self._clock()

    # -- clock -------------------------------------------------------------
    def set_clock(self, clock: Clock) -> None:
        """Swap the timestamp source (tests: a ManualClock).  Resets the
        trace origin so exported ``ts`` values start near zero."""
        self._clock = clock
        self._origin = clock()

    # -- recording ---------------------------------------------------------
    def _stack(self) -> List[_OpenSpan]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    @contextmanager
    def span(self, name: str, **args):
        if not self.enabled:
            yield None
            return
        stack = self._stack()
        parent = stack[-1].name if stack else None
        open_span = _OpenSpan(name, self._clock(), len(stack), parent,
                              args or None)
        stack.append(open_span)
        try:
            with TraceAnnotation(name):
                yield open_span
        finally:
            stack.pop()
            self._record(open_span, self._clock())

    def _record(self, open_span: _OpenSpan, end: float) -> None:
        sp = Span(open_span.name, open_span.start, end, open_span.depth,
                  open_span.parent, threading.get_ident(), open_span.args)
        with self._lock:
            self._spans.append(sp)

    def instant(self, name: str, **args) -> None:
        """Zero-duration marker event."""
        self.record_done(name, 0.0, **args)

    def record_done(self, name: str, duration: float, **args) -> None:
        """A span of ``duration`` seconds that ended now: work reported
        once it is done.  It is never on the stack, so it is nobody's
        parent."""
        if not self.enabled:
            return
        now = self._clock()
        stack = self._stack()
        parent = stack[-1].name if stack else None
        self._record(_OpenSpan(name, now - duration, len(stack), parent,
                               args or None), now)

    # -- inspection / export ----------------------------------------------
    def spans(self, name: Optional[str] = None) -> List[Span]:
        with self._lock:
            out = list(self._spans)
        if name is not None:
            out = [s for s in out if s.name == name]
        return sorted(out, key=lambda s: (s.start, s.depth))

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
        self._origin = self._clock()

    def to_chrome(self) -> Dict[str, Any]:
        """Chrome-trace JSON object (Perfetto/chrome://tracing loadable):
        one ``"X"`` complete event per span, µs timestamps relative to the
        tracer origin."""
        pid = os.getpid()
        events: List[Dict[str, Any]] = [{
            "ph": "M", "pid": pid, "name": "process_name",
            "args": {"name": "autochunk"},
        }]
        for s in self.spans():
            events.append({
                "ph": "X",
                "name": s.name,
                "cat": s.name.split(".", 1)[0],
                "ts": (s.start - self._origin) * 1e6,
                "dur": max(s.duration, 0.0) * 1e6,
                "pid": pid,
                "tid": s.tid,
                "args": s.args,
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f, indent=1)
            f.write("\n")


TRACER = Tracer()


class JitListener:
    """Records jax's compile stages on a tracer (see the module doc).

    jax reports, in this order and on the compiling thread: the jaxpr
    trace, the lowering to MLIR, then for a persistent-cache hit the
    cache's hit and its retrieval time, and last the backend compile,
    whose duration holds the retrieval.  A backend compile that follows a
    retrieval is therefore recorded as ``jit.cache_load``.
    """

    SPANS = {
        "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
        "/jax/core/compile/backend_compile_duration": "jit.compile",
    }
    RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
    COUNTERS = {
        "/jax/compilation_cache/cache_hits": "jit_cache_hits",
        "/jax/compilation_cache/cache_misses": "jit_cache_misses",
    }

    def __init__(self, tracer: Tracer):
        self.tracer = tracer
        registry = metrics.default_registry()
        self._counters = {event: registry.counter(name)
                          for event, name in self.COUNTERS.items()}
        self._local = threading.local()

    def on_duration(self, event: str, duration: float, **kwargs) -> None:
        if not self.tracer.enabled:
            return
        if event == self.RETRIEVAL:
            self._local.retrieval_s = duration
            return
        name = self.SPANS.get(event)
        if name is None:
            return
        args = {"fun": kwargs.get("fun_name", "")}
        if name == "jit.compile":
            retrieval = getattr(self._local, "retrieval_s", None)
            if retrieval is not None:
                self._local.retrieval_s = None
                name, args["retrieval_s"] = "jit.cache_load", retrieval
        self.tracer.record_done(name, duration, **args)

    def on_event(self, event: str, **kwargs) -> None:
        counter = self._counters.get(event)
        if counter is not None and self.tracer.enabled:
            counter.inc()

    def register(self) -> None:
        monitoring.register_event_duration_secs_listener(self.on_duration)
        monitoring.register_event_listener(self.on_event)


JitListener(TRACER).register()


def span(name: str, **args):
    """Context manager recording a span on the default tracer."""
    return TRACER.span(name, **args)


def traced(name: Optional[str] = None):
    """Decorator form of :func:`span`; defaults to the function name."""

    def deco(fn):
        span_name = name or fn.__name__

        @wraps(fn)
        def wrapper(*a, **kw):
            with TRACER.span(span_name):
                return fn(*a, **kw)

        return wrapper

    return deco


def set_enabled(on: bool) -> None:
    TRACER.enabled = bool(on)
