"""Spans and counters of the serving engine's host-side phases.

A :class:`Tracer` records named spans: each has a name, a start and an end
on ``time.perf_counter()``, the index of its parent span in
:attr:`Tracer.spans` (-1 at the top) and a small dict of attributes. Open
one with ``with tracer.span(name, **attrs):``; ``tracer.note(**attrs)`` adds
attributes to the innermost open span.

Every recorded span also enters ``jax.profiler.TraceAnnotation(name)`` with
its attributes, so that while JAX's profiler records, the same span sits on
the profile's host plane, on the device trace's clock: joins against device
events read that copy and never compute a clock offset.

While a span is open, JAX's compile events (``jax.monitoring``) land on the
innermost open span as attributes: ``build_s``, the seconds spent tracing,
lowering and compiling or loading programs (nested events counted once);
``compiles``, the backend compiles JAX reported, a load from the persistent
cache included; ``cache_loads``, the loads from that cache. The listeners
are registered once per process, when a tracer first records.

A disabled tracer, the default, keeps nothing in memory. While a JAX
profiler session records, its spans still go into the profile (attributes
and all); otherwise :meth:`Tracer.span` checks the profiler's state once
and returns one shared no-op context manager: no clock read, no
annotation, no list append. :data:`DEFAULT` is the tracer of an engine
given none.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
import weakref
from typing import Any, Dict, List, Optional

import jax

_BUILD_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration")
_COMPILE_EVENT = _BUILD_EVENTS[-1]
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_NOOP = contextlib.nullcontext()
_TRACERS: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_listening = False


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: Optional[float]                 # None while open
    parent: int                          # index in Tracer.spans, -1 at top
    attrs: Dict[str, Any]


class _Open:
    """One open span: its record, its annotation and its build intervals."""

    def __init__(self, tracer: "Tracer", name: str, attrs: dict,
                 keep: bool):
        self.tracer, self.name, self.keep = tracer, name, keep
        self.attrs = attrs
        self.index = -1                  # in tracer.spans, when kept
        self.noted: Dict[str, Any] = {}
        self.builds: List[tuple] = []    # (start, end) on perf_counter

    def __enter__(self):
        t = self.tracer
        if self.keep:
            parent = t._open[-1].index if t._open else -1
            self.index = len(t.spans)
            t.spans.append(Span(self.name, 0.0, None, parent, self.attrs))
        t._open.append(self)
        self.ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self.ann.__enter__()
        if self.keep:
            t.spans[self.index].start = time.perf_counter()
        return self

    def build(self, secs: float) -> None:
        b = time.perf_counter()
        a = b - secs
        while self.builds and self.builds[-1][0] >= a:
            self.builds.pop()            # nested in this one, ended earlier
        self.builds.append((a, b))

    def __exit__(self, *exc):
        end = time.perf_counter()
        t = self.tracer
        t._open.pop()
        if self.builds:
            self.noted["build_s"] = sum(b - a for a, b in self.builds)
        if self.noted:
            self.ann.set_metadata(**self.noted)
        self.ann.__exit__(*exc)
        if self.keep:
            s = t.spans[self.index]
            s.end = end
            s.attrs.update(self.noted)
        return False


class Tracer:
    """Host spans with attributes; see the module docstring."""

    def __init__(self, enabled: bool = False):
        self.enabled = False
        self.spans: List[Span] = []
        self._open: List[_Open] = []
        if enabled:
            self.enable()

    def enable(self) -> None:
        self.enabled = True
        _listen(self)

    def disable(self) -> None:
        self.enabled = False

    def span(self, name: str, **attrs):
        if self.enabled:
            return _Open(self, name, attrs, keep=True)
        if jax.profiler.TraceAnnotation.is_enabled():
            _listen(self)
            return _Open(self, name, attrs, keep=False)
        return _NOOP

    def note(self, **attrs) -> None:
        if self._open:
            self._open[-1].noted.update(attrs)


DEFAULT = Tracer()


def _on_duration(event: str, secs: float, **_) -> None:
    if event not in _BUILD_EVENTS:
        return
    for t in list(_TRACERS):
        if t._open:
            inner = t._open[-1]
            inner.build(secs)
            if event == _COMPILE_EVENT:
                inner.noted["compiles"] = inner.noted.get("compiles", 0) + 1


def _on_event(event: str, **_) -> None:
    if event != _CACHE_HIT_EVENT:
        return
    for t in list(_TRACERS):
        if t._open:
            n = t._open[-1].noted
            n["cache_loads"] = n.get("cache_loads", 0) + 1


def _listen(tracer: Tracer) -> None:
    global _listening
    _TRACERS.add(tracer)
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
