"""``repro.obs`` — unified tracing + metrics across compiler, serving, memory.

One event model for the whole stack: monotonic-clock :class:`Span` trees,
instant events, and a :class:`MetricsRegistry` of counters / gauges /
histograms, recorded into a session-scoped :class:`Tracer` and exported
as Chrome-trace-event JSON loadable in Perfetto / ``chrome://tracing``.

Gating: observability is **off by default**.  Enable per session::

    with repro.session(obs=True):          # or obs={"max_events": 50_000}
        ...

Instrumentation sites call :func:`get_tracer`, which returns ``None``
unless the ambient session's :class:`ObservabilityPolicy` is enabled —
the off path is a single attribute check.  Sessions derived from an
enabled one (nested ``repro.session(...)``) share the same tracer, so
compiler, serving, and memory events land in one stream.

Spans have a second sink: whenever a JAX profiler session is collecting
(``jax.profiler.start_trace``), :func:`span` also writes a
``jax.profiler.TraceAnnotation`` of the same name and attributes, with
observability on or off, so the profiler's trace can put device idle
time down to the host work that was running.

Summarize a trace offline::

    python -m repro.obs summarize trace.json
"""

from __future__ import annotations

from contextlib import AbstractContextManager, nullcontext
from typing import Any

from repro.obs.clock import now
from repro.obs.export import save_trace, to_chrome_trace, validate_chrome_trace
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import (Instant, Sample, Span, SpanScope, Tracer,
                             profiling)

__all__ = [
    "now",
    "get_tracer",
    "span",
    "instant",
    "Span",
    "Instant",
    "Sample",
    "Tracer",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "to_chrome_trace",
    "save_trace",
    "validate_chrome_trace",
]


#: default of :func:`span`'s ``tracer``: the ambient session's tracer
AMBIENT: Any = object()
_OFF: nullcontext[None] = nullcontext(None)


def get_tracer(sess: Any | None = None) -> Tracer | None:
    """The tracer of ``sess`` (default: the ambient session), or ``None``
    when its observability policy is disabled or absent."""
    if sess is None:
        from repro.runtime import current_session
        sess = current_session()
    policy = getattr(sess, "obs", None)
    if policy is None:
        return None
    tracer = policy.tracer()
    return tracer if isinstance(tracer, Tracer) else None


def span(name: str, cat: str = "", tracer: Any = AMBIENT, **attrs: Any
         ) -> AbstractContextManager[Span | SpanScope | None]:
    """Context manager for one span on both sinks: ``tracer`` (default:
    the ambient session's, ``None``: none) and, while a profiler session
    collects, a ``TraceAnnotation`` with the same name and attributes.

    Attribute values are ``int``, ``float``, ``str`` or ``bool``.  The
    span yields the tracer's :class:`Span` (or, with the profiler alone,
    the :class:`SpanScope`), whose ``attrs`` may be added to until it
    ends — both sinks receive them — or ``None`` when neither sink is
    on.  The off path is one check, so an attribute that costs more
    than a ``len()`` is set through the yielded object, under ``if sp
    is not None``."""
    if tracer is AMBIENT:
        tracer = get_tracer()
    if tracer is None and not profiling():
        return _OFF
    return SpanScope(tracer, name, cat, attrs)


def instant(name: str, cat: str = "", ts: float | None = None,
            **attrs: Any) -> None:
    """Record an instant event on the ambient session's tracer, if any."""
    tracer = get_tracer()
    if tracer is not None:
        tracer.instant(name, cat, ts=ts, **attrs)
