"""Span-based runtime tracing (DESIGN.md §11.2).

A :class:`Span` is one timed region of the serving stack —
``span("store.sync")`` around an epoch flip, ``span("repl.publish")``
around a replication round — with monotonic
(``time.perf_counter_ns``) start/duration stamps and parent/child
nesting carried by a ``contextvars`` token, so spans opened inside an
open span become its children automatically (including across the
driver's nested store → kernel call chains, and per *logical* context
in threaded servers).  A span opened inside one that carries a
``batch`` id carries the same id, so every span of one routed batch
(router → store → engine) names it.

Every completed span is appended to the owning :class:`Tracer`'s bounded
ring and emitted as a ``kind="span"`` event on the registry's
:class:`~repro.obs.export.TelemetrySink` JSONL log.  When a span opens,
the tracer also enters a ``jax.profiler.TraceAnnotation`` named scope,
so spans line up with XLA device traces in TensorBoard/perfetto: the
wall-clock span tree and the device timeline share names.  They share a
clock too: the tracer reads ``perf_counter_ns`` and the profiler's host
clock (``time.time_ns``, the clock ``jax.profiler`` stamps host events
with) back to back at its epoch, keeps the pair as :attr:`Tracer.clock`
and emits it as the sink's first event (``kind="clock"``), so a span's
profiler-clock start is ``clock["profiler_ns"] + start_us · 1000`` ns.

Determinism: span *structure* (names, nesting, order of completion) is a
pure function of the replayed control flow; only the timestamps are
wall-clock.  tests/test_obs.py pins the structure.
"""
from __future__ import annotations

import contextvars
import itertools
import threading
import time
from collections import deque
from dataclasses import dataclass, field

#: the open-span context (span id of the innermost open span, 0 = root)
_CURRENT: contextvars.ContextVar[int] = contextvars.ContextVar(
    "repro_obs_span", default=0)
#: the ``batch`` id of the innermost open span that carries one (0 = none)
_BATCH: contextvars.ContextVar[int] = contextvars.ContextVar(
    "repro_obs_batch", default=0)


def _profiler_scope(name: str):
    """A ``jax.profiler`` named scope, or None when jax is unavailable —
    tracing must never make telemetry a hard jax dependency."""
    try:
        from jax.profiler import TraceAnnotation
    except Exception:  # pragma: no cover - jax is present in this repo
        return None
    return TraceAnnotation(name)


@dataclass
class Span:
    """One completed (or open) trace region."""

    name: str
    id: int
    parent: int          # 0 = top-level
    depth: int
    start_us: float      # monotonic, relative to the tracer's epoch
    dur_us: float = 0.0
    attrs: dict = field(default_factory=dict)


class Tracer:
    """Bounded completed-span ring + the nesting machinery.

    ``span(name)`` is a context manager AND re-entrant: nested ``with``
    blocks chain parent ids.  The ring keeps the most recent
    ``max_spans`` completed spans (oldest dropped, ``dropped`` counts
    them) — telemetry must stay O(1) memory under million-event storms.
    """

    def __init__(self, *, max_spans: int = 4096, sink=None):
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._batches = itertools.count(1)
        self._epoch_ns = time.perf_counter_ns()
        #: the epoch on the profiler's host clock (see the module doc)
        self.clock = {"perf_ns": self._epoch_ns, "profiler_ns": time.time_ns()}
        self.max_spans = max_spans
        self.spans: deque[Span] = deque(maxlen=max_spans)
        self.dropped = 0
        self.sink = sink
        if sink is not None:
            sink.emit("clock", **self.clock)

    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self._epoch_ns) / 1e3

    def next_batch(self) -> int:
        """A fresh ``batch`` id for the spans of one routed batch."""
        return next(self._batches)

    def span(self, name: str, hist=None, **attrs) -> "_SpanContext":
        """A span context; ``hist`` (a Histogram) also observes the span's
        duration in µs when it closes."""
        return _SpanContext(self, name, attrs, hist)

    def _complete(self, span: Span) -> None:
        with self._lock:
            self.dropped += len(self.spans) == self.max_spans
            self.spans.append(span)
        if self.sink is not None:
            self.sink.emit("span", name=span.name, id=span.id,
                           parent=span.parent, depth=span.depth,
                           start_us=round(span.start_us, 3),
                           dur_us=round(span.dur_us, 3), **span.attrs)

    # -- reading ------------------------------------------------------------
    def completed(self, name: str | None = None) -> list[Span]:
        with self._lock:
            spans = list(self.spans)
        return spans if name is None else [s for s in spans if s.name == name]

    def children_of(self, span: Span) -> list[Span]:
        return [s for s in self.completed() if s.parent == span.id]

    def tree(self) -> list[tuple[int, str, float]]:
        """(depth, name, dur_us) rows in completion order — the compact
        text rendering quickstarts print."""
        return [(s.depth, s.name, s.dur_us) for s in self.completed()]


class _SpanContext:
    """The ``with tracer.span("..."):`` guard."""

    __slots__ = ("_tracer", "_name", "_attrs", "_hist", "_span", "_token",
                 "_depth_token", "_batch_token", "_scope")

    def __init__(self, tracer: Tracer, name: str, attrs: dict, hist=None):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs
        self._hist = hist
        self._span: Span | None = None
        self._token = None
        self._scope = None

    def __enter__(self) -> Span:
        t = self._tracer
        parent = _CURRENT.get()
        attrs = self._attrs
        if "batch" not in attrs and _BATCH.get():
            attrs = {**attrs, "batch": _BATCH.get()}
        span = Span(name=self._name, id=next(t._ids), parent=parent,
                    depth=0, start_us=t._now_us(), attrs=attrs)
        # depth = chain length to the root; the parent is still open (not
        # in the completed ring), so it rides its own contextvar.
        span.depth = _DEPTH.get() + 1
        self._span = span
        self._token = _CURRENT.set(span.id)
        self._depth_token = _DEPTH.set(span.depth)
        self._batch_token = _BATCH.set(attrs.get("batch", 0))
        self._scope = _profiler_scope(self._name)
        if self._scope is not None:
            self._scope.__enter__()
        return span

    def __exit__(self, *exc) -> None:
        if self._scope is not None:
            self._scope.__exit__(*exc)
        span = self._span
        span.dur_us = self._tracer._now_us() - span.start_us
        _CURRENT.reset(self._token)
        _DEPTH.reset(self._depth_token)
        _BATCH.reset(self._batch_token)
        if self._hist is not None:
            self._hist.observe(span.dur_us)
        self._tracer._complete(span)


_DEPTH: contextvars.ContextVar[int] = contextvars.ContextVar(
    "repro_obs_depth", default=0)


class _NullSpan:
    name = ""
    id = 0
    parent = 0
    depth = 0
    start_us = 0.0
    dur_us = 0.0
    attrs: dict = {}


_NULL_SPAN = _NullSpan()


class _NullSpanContext:
    __slots__ = ()

    def __enter__(self):
        return _NULL_SPAN

    def __exit__(self, *exc) -> None:
        pass


_NULL_SPAN_CTX = _NullSpanContext()


class NullTracer:
    """No-op tracer: ``span()`` returns a shared do-nothing context."""

    max_spans = 0
    spans: list = []
    dropped = 0
    sink = None
    clock: dict = {}

    def next_batch(self) -> int:
        return 0

    def span(self, name: str, hist=None, **attrs) -> _NullSpanContext:
        return _NULL_SPAN_CTX

    def completed(self, name: str | None = None) -> list:
        return []

    def children_of(self, span) -> list:
        return []

    def tree(self) -> list:
        return []
