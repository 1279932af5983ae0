"""Host-side span tracing: nested spans to the profiler and a JSONL log.

``span("name", attr=...)`` wraps the host-side phases of a run — training
chunk, eval, snapshot publish (``publish.encode``, ``publish.install``),
checkpoint save/restore, serving batch — and does two things:

  * it always opens a ``jax.profiler.TraceAnnotation`` of the same name,
    so whenever a profiler session is running (``jax.profiler.trace``,
    ``ObsConfig.profile_dir``) the span lands on the ``/host:CPU`` plane
    of the ``.xplane.pb``, on the device ops' clock. With no profiler
    running that is one annotation enter and exit, about a microsecond;
  * with a :class:`Tracer` installed it also records one event per span
    with monotonic timestamps, duration, nesting depth and parent name.

The module-level :func:`span` dispatches to the *installed* tracer; the
default :class:`NullTracer` hands back the bare annotation, so nothing is
formatted or written. :func:`recording` tells the loop whether a tracer
records durations, so a span that should time device work can sync
before it closes (``train_chunk``) only when someone reads the number.

Span events (one JSON object per line)::

    {"type": "span", "name": "train_chunk", "ts": 12.031, "dur": 0.482,
     "depth": 0, "parent": null,
     "attrs": {"start": 0, "end": 25, "backend": "scan"}}

``ts`` is seconds on the monotonic clock relative to tracer creation.
Nesting is tracked per thread, so concurrent serving threads produce
well-formed (if interleaved) span streams.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

SPAN_REQUIRED_KEYS = ("type", "name", "ts", "dur", "depth")


class NullTracer:
    """The default: ``span`` is the profiler annotation alone; no event is
    recorded."""

    def span(self, name: str, **attrs) -> Any:
        return TraceAnnotation(name)

    def close(self) -> None:
        pass


class _Span:
    __slots__ = ("tracer", "name", "attrs", "t0", "depth", "parent",
                 "annotation")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self.annotation = TraceAnnotation(name)

    def __enter__(self):
        stack = self.tracer._stack()
        self.depth = len(stack)
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self.annotation.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        dur = time.monotonic() - self.t0
        self.annotation.__exit__(None, None, None)
        self.tracer._stack().pop()
        event = {
            "type": "span",
            "name": self.name,
            "ts": round(self.t0 - self.tracer.t0, 6),
            "dur": round(dur, 6),
            "depth": self.depth,
            "parent": self.parent,
        }
        if self.attrs:
            event["attrs"] = self.attrs
        self.tracer._write(event)
        return False


class Tracer:
    """Collect span events; persist to ``path`` (JSONL) or ``.events``."""

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self.t0 = time.monotonic()
        self.events: List[Dict[str, Any]] = []
        self._fh = None
        self._lock = threading.Lock()
        self._local = threading.local()

    def _stack(self) -> List[str]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, **attrs) -> _Span:
        return _Span(self, name, attrs)

    def _write(self, event: Dict[str, Any]) -> None:
        with self._lock:
            if self.path is None:
                self.events.append(event)
                return
            if self._fh is None:
                os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
                self._fh = open(self.path, "w")
            self._fh.write(json.dumps(event, default=float) + "\n")
            self._fh.flush()

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None


_active: Any = NullTracer()


def install_tracer(tracer: Optional[Any]) -> Any:
    """Install the process-global tracer (None reverts to the no-op).

    Returns the previously installed tracer so callers can restore it.
    """
    global _active
    previous = _active
    _active = tracer if tracer is not None else NullTracer()
    return previous


def active_tracer() -> Any:
    return _active


def span(name: str, **attrs) -> Any:
    """A span context on the installed tracer: a profiler annotation, and a
    recorded event when a :class:`Tracer` is installed."""
    return _active.span(name, **attrs)


def recording() -> bool:
    """Whether the installed tracer records span durations."""
    return not isinstance(_active, NullTracer)


def validate_span_event(event: Any) -> List[str]:
    """Schema errors for one span event dict ([] = valid)."""
    errors: List[str] = []
    if not isinstance(event, dict):
        return [f"span event must be a dict, got {type(event).__name__}"]
    for key in SPAN_REQUIRED_KEYS:
        if key not in event:
            errors.append(f"span event missing key {key!r}")
    if errors:
        return errors
    if event["type"] != "span":
        errors.append(f"span event type must be 'span', got "
                      f"{event['type']!r}")
    if not isinstance(event["name"], str) or not event["name"]:
        errors.append("span name must be a non-empty string")
    for key in ("ts", "dur"):
        v = event[key]
        if not isinstance(v, (int, float)) or isinstance(v, bool) or v < 0:
            errors.append(f"span {key} must be a non-negative number, "
                          f"got {v!r}")
    d = event["depth"]
    if not isinstance(d, int) or isinstance(d, bool) or d < 0:
        errors.append(f"span depth must be a non-negative int, got {d!r}")
    parent = event.get("parent")
    if parent is not None and not isinstance(parent, str):
        errors.append(f"span parent must be null or a string, got {parent!r}")
    return errors
