"""Core tracing primitives: spans, instants, counter samples.

A :class:`Tracer` is a thread-safe, bounded event sink with a monotonic
clock (:mod:`repro.obs.clock`).  Spans nest per thread: each thread keeps
its own open-span stack, so a span started on thread A never becomes the
parent of one started on thread B.  All recorded events carry the native
thread id and are exported on separate tracks.

The tracer never allocates past ``max_events`` retained events — beyond
that, finished events are dropped and counted (``dropped``), keeping
obs-on cost bounded on long runs.

:class:`SpanScope` records one span into a tracer and, while a JAX
profiler session collects, into the profiler's trace as a
``TraceAnnotation`` on the profiler's clock.
"""

from __future__ import annotations

import itertools
import threading
from contextlib import contextmanager
from dataclasses import dataclass, field
from types import TracebackType
from typing import Any, Callable, Iterator

from jax.profiler import TraceAnnotation

from repro.obs.clock import now
from repro.obs.metrics import MetricsRegistry

__all__ = ["Span", "Instant", "Sample", "Tracer", "SpanScope", "profiling"]


#: whether a JAX profiler session is collecting host events now
profiling: Callable[[], bool] = TraceAnnotation.is_enabled


@dataclass(eq=False)
class Span:
    """A named duration with structured attributes.

    ``sid``/``parent`` express nesting; ``tid`` is the recording thread.
    ``attrs`` may be updated until export (handy for filling in results
    computed inside the span).
    """

    name: str
    cat: str
    start: float
    end: float
    sid: int
    parent: int | None
    tid: int
    attrs: dict[str, Any] = field(default_factory=dict)

    @property
    def duration(self) -> float:
        return self.end - self.start


@dataclass(eq=False)
class Instant:
    """A point-in-time event (request lifecycle edges, alloc/free, ...)."""

    name: str
    cat: str
    ts: float
    tid: int
    attrs: dict[str, Any] = field(default_factory=dict)


@dataclass(eq=False)
class Sample:
    """One point on a counter track (gauge value over time)."""

    name: str
    ts: float
    value: float


class _ThreadState(threading.local):
    """Per-thread open-span stack."""

    def __init__(self) -> None:
        self.stack: list[Span] = []


class Tracer:
    """Thread-safe bounded sink for spans, instants, and samples."""

    def __init__(self, max_events: int = 200_000) -> None:
        self.max_events = max_events
        self.epoch = now()
        self.spans: list[Span] = []
        self.instants: list[Instant] = []
        self.samples: list[Sample] = []
        self.dropped = 0
        self.thread_names: dict[int, str] = {}
        self.metrics = MetricsRegistry(self)
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        self._tls = _ThreadState()

    # ------------------------------------------------------------- events

    def begin(self, name: str, cat: str = "", **attrs: Any) -> Span:
        """Open a span; it becomes the parent of spans opened after it
        on the same thread until :meth:`finish`."""
        stack = self._tls.stack
        parent = stack[-1].sid if stack else None
        sp = Span(name=name, cat=cat, start=now(), end=0.0,
                  sid=next(self._ids), parent=parent, tid=self._tid(),
                  attrs=dict(attrs))
        stack.append(sp)
        return sp

    def finish(self, sp: Span) -> None:
        sp.end = now()
        stack = self._tls.stack
        if stack and stack[-1] is sp:
            stack.pop()
        else:  # mis-nested finish: unwind down to (and including) sp
            for i, open_sp in enumerate(stack):
                if open_sp is sp:
                    del stack[i:]
                    break
        with self._lock:
            if self._n_events() < self.max_events:
                self.spans.append(sp)
            else:
                self.dropped += 1

    @contextmanager
    def span(self, name: str, cat: str = "", **attrs: Any) -> Iterator[Span]:
        sp = self.begin(name, cat, **attrs)
        try:
            yield sp
        finally:
            self.finish(sp)

    def instant(self, name: str, cat: str = "", ts: float | None = None,
                **attrs: Any) -> None:
        """Record a point event.  Pass ``ts`` (from :func:`repro.obs.now`)
        to stamp it with a moment measured by the caller — the serving
        engine does this so trace timestamps and benchmark-side latency
        math read the very same clock sample."""
        ev = Instant(name=name, cat=cat, ts=now() if ts is None else ts,
                     tid=self._tid(), attrs=dict(attrs))
        with self._lock:
            if self._n_events() < self.max_events:
                self.instants.append(ev)
            else:
                self.dropped += 1

    def sample(self, name: str, value: float,
               ts: float | None = None) -> None:
        ev = Sample(name=name, ts=now() if ts is None else ts,
                    value=float(value))
        with self._lock:
            if self._n_events() < self.max_events:
                self.samples.append(ev)
            else:
                self.dropped += 1

    # ------------------------------------------------------------ helpers

    def _n_events(self) -> int:
        return len(self.spans) + len(self.instants) + len(self.samples)

    def _tid(self) -> int:
        tid = threading.get_ident()
        if tid not in self.thread_names:
            with self._lock:
                self.thread_names[tid] = threading.current_thread().name
        return tid

    def describe(self) -> dict[str, Any]:
        with self._lock:
            return {
                "spans": len(self.spans),
                "instants": len(self.instants),
                "samples": len(self.samples),
                "dropped": self.dropped,
                "threads": len(self.thread_names),
            }


class SpanScope:
    """One span on two independent sinks: ``tracer`` when it is not
    ``None``, and a ``TraceAnnotation`` when a profiler session collects
    at entry.  Yields the tracer's :class:`Span`, or without a tracer
    itself; either way the yielded ``attrs`` may be added to until the
    span ends, and the profiler receives them all then."""

    __slots__ = ("tracer", "name", "cat", "attrs", "_span", "_note")

    def __init__(self, tracer: Tracer | None, name: str, cat: str,
                 attrs: dict[str, Any]) -> None:
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self._span: Span | None = None
        self._note: TraceAnnotation | None = None

    def __enter__(self) -> Span | SpanScope:
        if self.tracer is not None:
            self._span = self.tracer.begin(self.name, self.cat, **self.attrs)
            self.attrs = self._span.attrs
        if profiling():
            self._note = TraceAnnotation(self.name)
            self._note.__enter__()
        return self if self._span is None else self._span

    def __exit__(self, typ: type[BaseException] | None,
                 exc: BaseException | None,
                 tb: TracebackType | None) -> None:
        if self._note is not None:
            if self.attrs:
                self._note.set_metadata(**self.attrs)
            self._note.__exit__(typ, exc, tb)
        if self._span is not None:
            assert self.tracer is not None
            self.tracer.finish(self._span)
