"""Bounded structured-event ring buffer + chrome-trace export.

The metrics registry answers "how much / how fast"; this ring answers
"what happened, in what order" — admission, preemption, watchdog
timeouts, bench backend-init attempts — without unbounded growth
(serving runs for days; the ring keeps the last ``capacity`` events
and drops the oldest).

Events are plain dicts (JSON lines on export).  Timestamps carry BOTH
clocks, read back to back by :func:`stamp`: ``ts`` is
``time.monotonic()`` (durations; the tracer's phase clocks and the
compile log run on it) and ``epoch_ns`` is Unix-epoch nanoseconds (the
clock of a profiler session's ``.xplane.pb`` — its ``profile_start_time``
— so a ring event can be laid beside device ops and ``RecordEvent``
spans; the chrome export runs on it); ``wall`` is the same instant in
seconds, for humans.  An event that carries ``dur_s`` is a span that
ENDED at its stamp.  ``seq`` increments per event so a tailer
(tools/metrics_dump.py) can poll ``/events?since=<seq>`` without
duplicates.

``span()`` opens a profiler ``RecordEvent`` carrying the span's fields
(it shows up in the profiler summary/chrome export, and on the host
plane of the XLA trace under any open profiler session) and additionally
emits a ring event with the measured duration — one annotation, three
sinks.  The engine's ``engine.admit`` goes through it: one ring event
per admission wave.
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

__all__ = ["EventRing", "default_ring", "stamp"]


def stamp() -> Tuple[float, int]:
    """``(time.monotonic() seconds, Unix-epoch nanoseconds)`` of one
    instant: what every ring event, compile record and set-up span is
    stamped with, so that none of them needs an offset between clocks
    sampled at some other time."""
    return time.monotonic(), time.time_ns()


# RecordEvent/TracerEventType resolved ONCE at first use: re-running
# the import statement inside every span __enter__ put an
# import-machinery round-trip on the hot span path (pinned by
# tests/test_observability.py::test_ring_span_no_import_in_hot_path)
_PROFILER_SPAN_TYPES = None


def _record_event_types():
    global _PROFILER_SPAN_TYPES
    if _PROFILER_SPAN_TYPES is None:
        from ..profiler.utils import RecordEvent, TracerEventType
        _PROFILER_SPAN_TYPES = (RecordEvent, TracerEventType)
    return _PROFILER_SPAN_TYPES


class _RingSpan:
    """Context manager: profiler RecordEvent + ring event on exit."""

    def __init__(self, ring: "EventRing", name: str, fields: dict):
        self._ring = ring
        self._name = name
        self._fields = fields
        self._rec = None
        self._t0 = 0.0

    def __enter__(self):
        RecordEvent, TracerEventType = _record_event_types()
        self._rec = RecordEvent(self._name,
                                TracerEventType.UserDefined,
                                **self._fields)
        self._rec.begin()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb):
        at = stamp()
        if self._rec is not None:
            self._rec.end()
        self._ring.emit(self._name, at=at, dur_s=at[0] - self._t0,
                        **self._fields)
        return False


class EventRing:
    """Thread-safe bounded ring of structured events."""

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=capacity)
        self._seq = 0
        self._dropped = 0         # events pushed out of the ring

    def emit(self, name: str, at: Optional[Tuple[float, int]] = None,
             **fields) -> dict:
        """Append one event, stamped now — or ``at`` a :func:`stamp`
        its caller took (a span reported after it closed: the compile
        log's records, the package's import)."""
        ts, epoch_ns = at if at is not None else stamp()
        ev = {"name": name, "ts": ts, "epoch_ns": epoch_ns,
              "wall": epoch_ns * 1e-9,
              "tid": threading.get_ident()}
        ev.update(fields)
        with self._lock:
            self._seq += 1
            ev["seq"] = self._seq
            if len(self._events) == self.capacity:
                self._dropped += 1
            self._events.append(ev)
        return ev

    @property
    def dropped(self) -> int:
        """Events pushed out of the ring — read from scrape threads
        (``/stats``) while the engine thread emits, so the counter
        lives behind the lock like the ring itself."""
        with self._lock:
            return self._dropped

    def span(self, name: str, **fields) -> _RingSpan:
        return _RingSpan(self, name, fields)

    def recent(self, n: Optional[int] = None,
               since: int = 0) -> List[dict]:
        """Last ``n`` events (all by default), optionally only those
        with ``seq > since`` (the tail-follow protocol)."""
        return self.recent_with_gap(n=n, since=since)[0]

    def recent_with_gap(self, n: Optional[int] = None,
                        since: int = 0):
        """``(events, gap)``: the tail-follow batch plus the number
        of events that fell off the ring BETWEEN ``since`` and the
        oldest retained event.  Without the gap figure a follower
        polling ``/events?since=`` across a ring wrap silently skips
        the lost events and reads a burst as a quiet stream — the
        ``dropped`` delta makes the loss visible
        (tools/metrics_dump.py prints a ``[gap: N events lost]``
        marker)."""
        with self._lock:
            evs = list(self._events)
            seq = self._seq
        gap = 0
        if since:
            # seq of the oldest event still in the ring; an empty
            # ring means everything up to seq is gone
            oldest = evs[0]["seq"] if evs else seq + 1
            if since + 1 < oldest:
                gap = oldest - since - 1
            evs = [e for e in evs if e["seq"] > since]
        if n is not None:
            evs = evs[-n:] if n > 0 else []   # n=0 is "none", not all
        return evs, gap

    def drain(self) -> List[dict]:
        with self._lock:
            evs = list(self._events)
            self._events.clear()
        return evs

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def to_jsonl(self, n: Optional[int] = None) -> str:
        return "\n".join(json.dumps(e) for e in self.recent(n))

    def chrome_events(self,
                      include_profiler_spans: bool = True) -> List[dict]:
        """The ring (and optionally the profiler's buffered host
        spans) as chrome trace-event dicts — the building block
        :meth:`export_chrome_trace` writes out and the per-trace
        Perfetto export (observability/tracing.py) merges onto.
        ``ts`` is Unix-epoch microseconds, each event's own stamp."""
        import os
        pid = os.getpid()
        trace_events = []
        for ev in self.recent():
            args = {k: v for k, v in ev.items()
                    if k not in ("name", "ts", "epoch_ns", "tid", "wall",
                                 "seq", "dur_s")
                    and isinstance(v, (str, int, float, bool,
                                       type(None)))}
            end_us = ev["epoch_ns"] * 1e-3
            if "dur_s" in ev:
                trace_events.append({
                    "name": ev["name"], "ph": "X", "cat": "event",
                    "ts": end_us - ev["dur_s"] * 1e6,
                    "dur": ev["dur_s"] * 1e6,
                    "pid": pid, "tid": ev["tid"], "args": args})
            else:
                trace_events.append({
                    "name": ev["name"], "ph": "i", "cat": "event",
                    "ts": end_us, "s": "t",
                    "pid": pid, "tid": ev["tid"], "args": args})
        if include_profiler_spans:
            try:
                from ..profiler.utils import _peek_spans
                for name, etype, start, end, tid, epoch_ns \
                        in _peek_spans():
                    trace_events.append({
                        "name": name, "ph": "X", "cat": etype,
                        "ts": epoch_ns * 1e-3,
                        "dur": (end - start) * 1e6,
                        "pid": pid, "tid": tid})
            except Exception:
                pass              # profiler unavailable: events only
        return trace_events

    def export_chrome_trace(self, path: str,
                            include_profiler_spans: bool = True
                            ) -> str:
        """Write a chrome trace: ring events as instants (spans when
        they carry ``dur_s``) merged with the profiler's currently
        buffered host spans — engine events and ``RecordEvent`` spans
        on one timeline (open in Perfetto / chrome://tracing)."""
        trace = {"traceEvents":
                 self.chrome_events(include_profiler_spans),
                 "displayTimeUnit": "ms"}
        import os.path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(trace, f)
        return path


_default_ring = EventRing()


def default_ring() -> EventRing:
    """The process-wide ring servers and the bench emit into."""
    return _default_ring
