"""Structured span tracer on the profiler's clock, with Chrome-trace /
Perfetto export.

Spans are host-Python timing records around host-side control flow:
per-request lifetimes and per-engine-step phases in the serve engine.
Every ``span(name, **attrs)`` is also a ``jax.profiler.TraceAnnotation`` of
the same name, whether or not tracing is enabled: the profiler keeps it
only while a profiler session runs (``jax.profiler.trace``), and then it
sits on the same timeline as the device planes' ``XLA Ops``, with
``attrs`` as the event's stats.  With no session an annotation costs about
a microsecond and records nothing.  jax is imported on the first span, so
this module stays light to import.

``enable()`` turns on the in-memory Chrome buffer besides: with it off (the
default) a span writes no buffer event and reads no clock of its own.
Opening a span inside a jitted function's *trace* records the (one-time)
trace cost, never a per-call device sync, so enabling tracing cannot change
any computed value (pinned by the serve token-identity test).

Export is the Chrome trace-event JSON format (``chrome://tracing`` /
Perfetto ``ui.perfetto.dev``): synchronous spans as complete events
(``ph: "X"``, microsecond ``ts``/``dur``), request lifetimes as async
begin/end pairs (``ph: "b"``/``"e"`` with an ``id``) so overlapping
requests render as separate tracks.  Nesting depth flows through a
contextvar, so spans opened across threads don't corrupt each other's
parent chain.
"""
from __future__ import annotations

import contextvars
import json
import os
import threading
import time
from typing import Dict, List, Optional

__all__ = ["enable", "disable", "enabled", "span", "instant",
           "begin_async", "end_async", "events", "clear", "chrome_trace",
           "export_chrome"]

_lock = threading.Lock()
_enabled = False
_events: List[dict] = []
# Monotonic epoch for the whole process: Chrome-trace ts values are relative
# microseconds, so one shared origin keeps every span on one timeline.
_EPOCH_NS = time.perf_counter_ns()

_span_path: contextvars.ContextVar = contextvars.ContextVar(
    "obs_span_path", default=())


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def _now_us() -> float:
    return (time.perf_counter_ns() - _EPOCH_NS) / 1e3


_Annotation = None


def _annotation(name: str, attrs: Dict[str, object]):
    """A profiler annotation (``TraceAnnotation`` with a no-op ``set``);
    jax is imported on the first call."""
    global _Annotation
    if _Annotation is None:
        from jax.profiler import TraceAnnotation

        class _ProfilerSpan(TraceAnnotation):
            """The whole span while the Chrome buffer is off."""

            def set(self, **attrs) -> None:
                pass

        _Annotation = _ProfilerSpan
    return _Annotation(name, **attrs)


class _Span:
    __slots__ = ("name", "args", "_t0", "_token", "_ann")

    def __init__(self, name: str, args: Dict[str, object]):
        self.name = name
        self.args = args
        self._t0 = 0.0
        self._token = None
        self._ann = _annotation(name, args)

    def __enter__(self):
        self._ann.__enter__()
        path = _span_path.get()
        self.args["depth"] = len(path)
        if path:
            self.args["parent"] = path[-1]
        self._token = _span_path.set(path + (self.name,))
        self._t0 = _now_us()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = _now_us()
        _span_path.reset(self._token)
        event = {
            "name": self.name,
            "ph": "X",
            "ts": self._t0,
            "dur": t1 - self._t0,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "cat": "repro",
            "args": self.args,
        }
        with _lock:
            _events.append(event)
        self._ann.__exit__(exc_type, exc, tb)
        return False

    def set(self, **attrs) -> None:
        """Attach attributes discovered mid-span (e.g. chosen lane width)
        to the Chrome event; the profiler keeps those given at open."""
        self.args.update(attrs)


def span(name: str, **attrs):
    """Context manager timing a host-side region, on the profiler's
    timeline always and in the Chrome buffer when enabled.

    ``with trace.span("serve.prefill", rid=3) as sp: ... sp.set(lanes=4)``
    """
    if not _enabled:
        return _annotation(name, attrs)
    return _Span(name, dict(attrs))


def instant(name: str, **attrs) -> None:
    """Zero-duration marker event (e.g. request finished, fallback taken)."""
    if not _enabled:
        return
    event = {"name": name, "ph": "i", "ts": _now_us(), "pid": os.getpid(),
             "tid": threading.get_ident(), "s": "t", "cat": "repro",
             "args": dict(attrs)}
    with _lock:
        _events.append(event)


def begin_async(name: str, async_id, **attrs) -> None:
    """Open an async span (request lifetime) — pairs with :func:`end_async`
    by (name, id); overlapping ids render as parallel tracks."""
    if not _enabled:
        return
    event = {"name": name, "ph": "b", "id": str(async_id), "ts": _now_us(),
             "pid": os.getpid(), "tid": threading.get_ident(),
             "cat": "repro", "args": dict(attrs)}
    with _lock:
        _events.append(event)


def end_async(name: str, async_id, **attrs) -> None:
    if not _enabled:
        return
    event = {"name": name, "ph": "e", "id": str(async_id), "ts": _now_us(),
             "pid": os.getpid(), "tid": threading.get_ident(),
             "cat": "repro", "args": dict(attrs)}
    with _lock:
        _events.append(event)


def events() -> List[dict]:
    with _lock:
        return list(_events)


def clear() -> None:
    with _lock:
        _events.clear()


def chrome_trace() -> Dict[str, list]:
    """The buffered events as a Chrome trace-event JSON object."""
    return {"traceEvents": events(), "displayTimeUnit": "ms"}


def export_chrome(path: str) -> None:
    with open(path, "w") as f:
        json.dump(chrome_trace(), f)
        f.write("\n")
