"""Timed steps of the program's own work, recorded as spans.

``with step(sink, "begin.stage") as st:`` times the block on
``time.monotonic()`` and adds it as a span named ``begin.stage`` to
``sink`` (an ``obs.trace.SpanSink``, flushed to the tracer by whoever
made it); ``st.sink`` is where the block's own sub-steps record, so they
carry this span as their parent.  While the step runs it is also a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace
shows it on the host thread next to the device's ops.

With ``sink`` None a step records nothing and makes no span: it is one
shared do-nothing object whose ``sink`` is None in turn, so an untraced
call passes None all the way down.

No jax import here: a process that never imported jax (a fabric shard
server) cannot be profiled by jax, and gets the spans alone.
"""
from __future__ import annotations

import sys
import time
from typing import Optional

from repro.obs.trace import SpanSink


class _Step:
    __slots__ = ("_parent", "name", "tags", "span_id", "t0", "t1",
                 "_annotation")

    def __init__(self, parent: SpanSink, name: str, tags: dict):
        self._parent = parent
        self.name = name
        self.tags = tags
        self.t0 = self.t1 = 0.0
        self._annotation = None

    @property
    def sink(self) -> SpanSink:
        return self._parent.under(self.span_id)

    def __enter__(self) -> "_Step":
        self.span_id = self._parent.span_id()
        jax = sys.modules.get("jax")
        if jax is not None:
            self._annotation = jax.profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        self.t0 = time.monotonic()
        return self

    def tag(self, **tags) -> None:
        self.tags.update(tags)

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.t1 = time.monotonic()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self.tags["error"] = exc_type.__name__
        self._parent.add(self.name, self.t0, self.t1, self.span_id,
                         self.tags or None)
        return False


class _NoStep:
    __slots__ = ()
    sink = None

    def __enter__(self) -> "_NoStep":
        return self

    def tag(self, **tags) -> None:
        pass

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NO_STEP = _NoStep()


def step(sink: Optional[SpanSink], name: str, **tags):
    """A context manager that records the block as the span ``name`` in
    ``sink``, tagged ``tags`` and whatever the block adds with ``.tag``;
    or does nothing when ``sink`` is None."""
    if sink is None:
        return _NO_STEP
    return _Step(sink, name, tags)
