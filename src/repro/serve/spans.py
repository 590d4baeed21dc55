"""Host spans and counters of the serving engine, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: while the profiler runs it is
written into the same trace as the device's operations, on the same clock,
with its counters as the event's arguments.  While nothing traces, ``span``
hands back one shared no-op and records nothing.  A counter given as a
callable is called only while the profiler runs, so a counter that costs
more than arithmetic on host values (a device-to-host fetch, a string
join, a clock read) costs nothing when off.  The profiler is the only
switch: whoever starts it (``jax.profiler.start_trace``) turns the spans on.
"""
from __future__ import annotations

import jax


def recording() -> bool:
    """Whether the profiler runs, so that a span opened now is recorded."""
    return jax.profiler.TraceAnnotation.is_enabled()


def _values(counters: dict) -> dict:
    return {k: v() if callable(v) else v for k, v in counters.items()}


class _Span(jax.profiler.TraceAnnotation):
    def set(self, **counters):
        """Add counters known only after the span opened."""
        self.set_metadata(**_values(counters))


class _Off:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **counters):
        pass


_OFF = _Off()


def span(name: str, **counters):
    """``with span("serve.x", n=3, cost=lambda: fetch()) as sp: ...;
    sp.set(more=...)`` — a recorded span while the profiler runs, else a
    no-op that calls none of the counters.  Counter values are ints, floats
    or strings (a string holds no comma: the profiler splits on them)."""
    if not recording():
        return _OFF
    return _Span(name, **_values(counters))
