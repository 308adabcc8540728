"""RecordEvent and host-span collection (reference: profiler/utils.py:40).

TPU-first design: a ``RecordEvent`` does two things at once —
  1. appends a wall-clock span to the in-process span buffer (used for the
     framework-side summary table and chrome-trace export), and
  2. opens a ``jax.profiler.TraceAnnotation`` so the same name shows up in
     the XLA device trace under any open profiler session (the Paddle-API
     ``Profiler`` or a plain ``jax.profiler.start_trace``).

Device-side op timing belongs to XLA's own profiler (captured via
``jax.profiler.start_trace``); the framework does not attempt to re-time
individual ops on host, which would fence the async dispatch queue.
"""

from __future__ import annotations

import threading
import time
import timeit
from contextlib import ContextDecorator

import jax


class TracerEventType:
    """Event categories (reference: paddle/fluid/platform/profiler/trace_event.h)."""
    Operator = "Operator"
    Dataloader = "Dataloader"
    ProfileStep = "ProfileStep"
    Forward = "Forward"
    Backward = "Backward"
    Optimization = "Optimization"
    Communication = "Communication"
    PythonOp = "PythonOp"
    UserDefined = "UserDefined"


class _SpanBuffer:
    """Thread-safe buffer of completed host spans: ``(name, event_type,
    start, end, tid, start_epoch_ns)``, start and end on
    ``timeit.default_timer`` (the Profiler's summary and export) and
    the start on the Unix epoch as well (the event ring's chrome
    export, which runs on it)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._spans = []
        self.enabled = False

    def add(self, name, event_type, start, end, tid, start_epoch_ns):
        with self._lock:
            self._spans.append((name, event_type, start, end, tid,
                                start_epoch_ns))

    def drain(self):
        with self._lock:
            spans, self._spans = self._spans, []
        return spans

    def clear(self):
        with self._lock:
            self._spans = []


_buffer = _SpanBuffer()


def in_profiler_mode():
    return _buffer.enabled


def _enable_collection():
    _buffer.enabled = True


def _disable_collection():
    _buffer.enabled = False


def _drain_spans():
    return _buffer.drain()


def _peek_spans():
    """Non-destructive view of the buffered spans — the observability
    event ring merges them into its chrome-trace export without
    stealing them from the profiler's own summary/export."""
    with _buffer._lock:
        return list(_buffer._spans)


class RecordEvent(ContextDecorator):
    """User-facing interval annotation (reference: profiler/utils.py:40).

    Usage::

        with paddle.profiler.RecordEvent("attention"):
            out = model(x)

    or via ``begin()`` / ``end()``, or as a decorator.  The
    ``TraceAnnotation`` is opened always: it costs a flag check while no
    profiler session is open, and under ANY open session (a plain
    ``jax.profiler.start_trace`` included) the span lands on the host
    plane of the trace, on the device ops' clock, with ``attrs`` as its
    keyword arguments.  Only the span-buffer entry waits for the
    Paddle-API ``Profiler`` to collect.
    """

    def __init__(self, name, event_type=TracerEventType.PythonOp, **attrs):
        self.name = name
        self.event_type = event_type
        self.attrs = attrs
        self._start = None
        self._start_epoch_ns = 0
        self._ann = None

    def _recreate_cm(self):
        # as a decorator one instance would serve every call, and the
        # decorated function may run on several threads at once
        return type(self)(self.name, self.event_type, **self.attrs)

    def begin(self):
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        if _buffer.enabled:
            self._start = timeit.default_timer()
            self._start_epoch_ns = time.time_ns()

    def annotate(self, **attrs):
        """Attributes that are known only while the span is open (what a
        set-up found): call between ``begin()`` and ``end()``."""
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)

    def end(self):
        if self._start is not None:
            _buffer.add(self.name, self.event_type, self._start,
                        timeit.default_timer(), threading.get_ident(),
                        self._start_epoch_ns)
            self._start = None
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        self.end()
        return False


def wrap_optimizers():
    """Reference wraps optimizer.step in a RecordEvent; our op-dispatch layer
    already annotates whole jitted steps, so this is a documented no-op."""
    return None


def load_profiler_result(filename):
    """Load a chrome-trace JSON previously written by export_chrome_tracing."""
    import json
    with open(filename) as f:
        return json.load(f)
