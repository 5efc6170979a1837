"""In-program spans and counters, recorded while a JAX profiler runs.

The serving front end and the design sweep mark each layer boundary of
their hot paths with ``span(name, **attrs)`` and count work with
``count(name, n)``.  Both record only while a JAX profiler trace is
active (``jax.profiler.trace`` or ``start_trace``): outside one, a call is
a single check and records nothing, so an untraced run measures the
program as it is.  Inside one, each span

* opens a ``jax.profiler.TraceAnnotation`` of the same name (its attrs as
  the event's metadata), so it lands in the profiler's ``.xplane.pb`` on
  the host plane, on the clock of the device trace;
* appends ``(id, parent, name, start_ns, end_ns, attrs)`` to the
  recorder's list, ``parent`` being the span open around it on the same
  thread (0 for a root).

``snapshot()`` returns what was recorded, with per-name count, total and
self time (a span's duration less the part its child spans cover);
``reset()`` clears it.  The list grows for as long as the profiler runs,
so a long profiled run should ``reset()`` between reads.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation


class SpanRecord(NamedTuple):
    id: int
    parent: int  # 0 for a root span
    name: str
    start_ns: int  # time.perf_counter_ns()
    end_ns: int
    attrs: dict


@dataclasses.dataclass(frozen=True)
class Snapshot:
    """What the recorder holds: every span, the counters, and per span
    name the number of spans, their total and their self seconds."""

    spans: tuple
    counters: dict
    count: dict
    total_s: dict
    self_s: dict

    @classmethod
    def of(cls, spans, counters=None) -> "Snapshot":
        spans = tuple(spans)
        # ns of each span that its children cover: children of one span
        # run on its thread, one after another, inside it
        covered: dict = {}
        for s in spans:
            if s.parent:
                dur = s.end_ns - s.start_ns
                covered[s.parent] = covered.get(s.parent, 0) + dur
        count: dict = {}
        total: dict = {}
        self_: dict = {}
        for s in spans:
            dur = s.end_ns - s.start_ns
            own = dur - covered.get(s.id, 0)
            count[s.name] = count.get(s.name, 0) + 1
            total[s.name] = total.get(s.name, 0.0) + dur * 1e-9
            self_[s.name] = self_.get(s.name, 0.0) + own * 1e-9
        return cls(spans, dict(counters or {}), count, total, self_)


def enabled() -> bool:
    """True while a JAX profiler trace records host events."""
    return TraceAnnotation.is_enabled()


class _Span:
    __slots__ = (
        "_rec", "_name", "_attrs", "_id", "_parent", "_start", "_note"
    )

    def __init__(self, rec: "Recorder", name: str, attrs: dict):
        self._rec = rec
        self._name = name
        self._attrs = attrs

    def __enter__(self):
        stack = self._rec._stack()
        self._parent = stack[-1] if stack else 0
        self._id = next(self._rec._ids)
        stack.append(self._id)
        self._note = TraceAnnotation(self._name, **self._attrs)
        self._note.__enter__()
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self._note.__exit__(*exc)
        self._rec._stack().pop()
        self._rec._spans.append(SpanRecord(
            self._id, self._parent, self._name, self._start, end, self._attrs
        ))
        return False


_OFF = contextlib.nullcontext()


class Recorder:
    """Spans and counters of one process; the module functions record into
    ``RECORDER``."""

    def __init__(self):
        self._spans: list = []
        self._counters: dict = {}
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        self._local = threading.local()

    def _stack(self) -> list:
        """Ids of the spans open on this thread, innermost last."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def add(self, name: str, n: int) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def snapshot(self) -> Snapshot:
        with self._lock:
            counters = dict(self._counters)
        return Snapshot.of(list(self._spans), counters)

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self._counters.clear()


RECORDER = Recorder()


def span(name: str, **attrs):
    """Context manager timing one span (recorded only while profiling)."""
    if not enabled():
        return _OFF
    return _Span(RECORDER, name, attrs)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` (only while profiling)."""
    if enabled():
        RECORDER.add(name, n)


def snapshot() -> Snapshot:
    return RECORDER.snapshot()


def reset() -> None:
    RECORDER.reset()
