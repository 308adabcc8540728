"""The program's own log of what it traced, lowered, compiled or loaded
from the persistent cache: which program, when, for how long.

One process-wide listener on ``jax.monitoring`` (:func:`enable`, called
once when ``paddle_tpu`` is imported, before anything the package or its
caller compiles) keeps a bounded list of records, one per phase of a
program's compile:

* ``compile.trace`` — ``/jax/core/compile/jaxpr_trace_duration``: the
  Python body run under tracers.  Paid on EVERY run, cache hit or not:
  the persistent cache's key is made from the lowered module.
* ``compile.lower`` — ``.../jaxpr_to_mlir_module_duration``: jaxpr to
  StableHLO.  Paid on every run as well.
* ``compile.backend`` — ``.../backend_compile_duration``: XLA's compile,
  or on a persistent-cache hit the retrieval in its place.

A record is a plain dict: ``id``, ``name`` (one of the three), ``program``
(JAX's ``fun_name`` normalised: the trace event says ``step``, the other
two ``jit(step)``; ``raw`` keeps what arrived), ``tid``, ``start`` / ``end``
on ``time.monotonic()`` and ``start_epoch_ns`` / ``end_epoch_ns`` on the
Unix epoch (JAX hands the listener epoch seconds; the monotonic pair is
set from one :func:`~.events.stamp` taken as the event arrives),
``dur_s``, ``self_s``, ``cause`` (the id of the trace record that opened
this program's compile: the three records of one program share it) and
``parent``.  **Trace records nest**: a jitted function called inside
``step`` is traced inside ``step``'s span and reports a span of its own,
so such a record names its ``parent`` trace and every total sums SELF
time — a span less what its children cover —, never the raw durations.
A trace that opens INSIDE A LOWERING (a lowering rule that calls a jitted
function) is the lowering's own time and makes no record.
A backend record also carries ``cache``: ``hit`` (the record's duration
is then the retrieval; ``retrieval_s`` and ``saved_s`` are the cache's
own figures, the second as it comes — negative for a tiny program),
``miss`` (compiled, and the entry written) or ``off`` (the cache was not
asked, or held the entry back under its thresholds).  The cache's events
carry no name; they fire on the compiling thread before that program's
backend span closes, and go to the next backend record of that thread.

The three records of a program that went on to a lowering are also events
of ``default_ring()`` — ``GET /events`` and the chrome export show
compiles on the engine's timeline; a trace that ends in jit's own cache,
and the nested ones, stay in the log alone — and six counters read the lifetime sums at
scrape time (:func:`bind`; ``EngineMetrics`` binds its registry, so
``GET /metrics`` / ``/stats`` of every server carry them).

Always on: no flag, no environment variable, no config key — a compile
that happened before tracing was asked for is exactly the one that is
wanted.  The cost is per compile event (a few dict writes under a lock),
never per step.  Stdlib only but for ``jax.monitoring`` in
:func:`enable`.
"""

from __future__ import annotations

import itertools
import json
import threading
from collections import deque
from typing import Dict, List, Optional

from .events import EventRing, default_ring, stamp
from .metrics import MetricsRegistry, default_registry

__all__ = ["CompileLog", "default_log", "enable", "bind", "records",
           "totals", "by_program", "to_jsonl", "TRACE", "LOWER",
           "BACKEND", "CAPACITY"]

TRACE, LOWER, BACKEND = "compile.trace", "compile.lower", "compile.backend"
_PHASE = {"/jax/core/compile/jaxpr_trace_duration": TRACE,
          "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
          "/jax/core/compile/backend_compile_duration": BACKEND}
_CACHE_STATE = {"/jax/compilation_cache/cache_hits": "hit",
                "/jax/compilation_cache/cache_misses": "miss"}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s"}
_SUM_OF = {TRACE: "trace_s", LOWER: "lower_s", BACKEND: "backend_s"}
# a run of a benchmark cell: 360 records of the dense cell's 120 programs
# and 1,150 traces nested in its step's (PR 54, on the chip); the expert
# toy on the CPU 1,060 and 8,250.  ~20 MB at the worst; a server compiles
# per layout
CAPACITY = 32768
_CAUSES_KEPT = 256

# instrument -> the key of ``totals()`` it reads
INSTRUMENTS = {
    "paddle_tpu_compile_programs_total":
        ("programs", "programs built by XLA or loaded from the "
                     "persistent cache (backend compiles)"),
    "paddle_tpu_compile_trace_seconds_total":
        ("trace_s", "self seconds of tracing (paid on every run)"),
    "paddle_tpu_compile_lower_seconds_total":
        ("lower_s", "seconds of lowering to StableHLO (paid on every "
                    "run)"),
    "paddle_tpu_compile_backend_seconds_total":
        ("backend_s", "seconds of backend compiles and cache "
                      "retrievals"),
    "paddle_tpu_compile_cache_hits_total":
        ("hits", "backend compiles served by the persistent cache"),
    "paddle_tpu_compile_cache_misses_total":
        ("misses", "backend compiles written to the persistent cache"),
}


def program_of(fun_name: str) -> str:
    """``jit(step)`` / ``pmap(step)`` -> ``step``."""
    head, paren, rest = fun_name.partition("(")
    if paren and rest.endswith(")") and head.isidentifier():
        return rest[:-1]
    return fun_name


def _zero_sums() -> dict:
    return {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "programs": 0, "hits": 0, "misses": 0}


class CompileLog:
    """The bounded list and its lifetime sums.  The four ``on_*`` methods
    are ``jax.monitoring`` listeners; they never raise into a compile
    (``faults`` counts what they swallowed)."""

    def __init__(self, capacity: int = CAPACITY,
                 ring: Optional[EventRing] = None):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self._ring = ring
        self._lock = threading.Lock()
        self._records: deque = deque(maxlen=capacity)
        self._ids = itertools.count(1)
        self._sums = _zero_sums()
        self._dropped = 0
        self.faults = 0
        self._local = threading.local()

    # -- per-thread state: open traces, the last cause, the cache's word --
    def _thread(self):
        st = self._local
        if not hasattr(st, "open"):
            st.open = []            # [id, seconds its children cover]
            st.lowering = 0         # lowerings open on this thread
            st.causes = {}      # program -> [its last top trace, in ring?]
            st.cache = {}
        return st

    # -- listeners --------------------------------------------------------
    def on_scalar(self, event, value, **kw):
        """``log_elapsed_time.__enter__`` reports a span's start: a trace
        that opens while another is open on this thread is its child, and
        one that opens INSIDE A LOWERING (a lowering rule that calls a
        jitted function: threefry's reports thousands a run, each a few
        microseconds) is the lowering's own time and makes no record."""
        phase = _PHASE.get(event)
        if phase == TRACE:
            st = self._thread()
            st.open.append(None if st.lowering or (st.open and
                                                   st.open[-1] is None)
                           else [next(self._ids), 0.0])
        elif phase == LOWER:
            self._thread().lowering += 1

    def on_event(self, event, **kw):
        state = _CACHE_STATE.get(event)
        if state is not None:
            self._thread().cache["cache"] = state

    def on_duration(self, event, secs, **kw):
        key = _CACHE_SECONDS.get(event)
        if key is not None:
            self._thread().cache[key] = float(secs)

    def on_span(self, event, start, end, fun_name="", **kw):
        name = _PHASE.get(event)
        if name is None:
            return
        try:
            self._record(name, str(fun_name), float(start), float(end))
        except Exception:               # never into the compile
            self.faults += 1

    def _record(self, name, raw, start, end):
        now_mono, now_epoch_ns = stamp()
        dur = max(end - start, 0.0)
        end_mono = now_mono - (now_epoch_ns * 1e-9 - end)
        st = self._thread()
        program = program_of(raw)
        rec = {"name": name, "program": program, "raw": raw,
               "tid": threading.get_ident(),
               "start": end_mono - dur, "end": end_mono,
               "start_epoch_ns": int(start * 1e9),
               "end_epoch_ns": int(end * 1e9),
               "dur_s": dur, "self_s": dur, "parent": None}
        if name == LOWER:
            st.lowering = max(st.lowering - 1, 0)
        if name == TRACE:
            mine = st.open.pop() if st.open else [next(self._ids), 0.0]
            if mine is None:
                return                      # inside a lowering
            rec["id"] = mine[0]
            rec["self_s"] = max(dur - mine[1], 0.0)
            if st.open:
                st.open[-1][1] += dur
                rec["parent"] = st.open[-1][0]
                rec["cause"] = st.open[0][0]
            else:
                rec["cause"] = mine[0]
                # another program may be traced between a program's
                # trace and its lowering: the cause is kept by name, the
                # newest _CAUSES_KEPT names a thread
                st.causes.pop(program, None)
                st.causes[program] = [rec, False]   # not in the ring yet
                if len(st.causes) > _CAUSES_KEPT:
                    del st.causes[next(iter(st.causes))]
        else:
            rec["id"] = next(self._ids)
            cause = st.causes.get(program)
            rec["cause"] = cause[0]["id"] if cause else None
            if name == BACKEND:
                rec["cache"] = st.cache.pop("cache", "off")
                rec.update(st.cache)
                st.cache = {}
        with self._lock:
            if len(self._records) == self.capacity:
                self._dropped += 1
            self._records.append(rec)
            _add(self._sums, rec)
        # the ring gets a program's three records: its trace once it is
        # known to have gone on to a lowering — a trace that ends in jit's
        # own cache, as most do, stays in the log alone
        if name != TRACE:
            if cause is not None and not cause[1]:
                cause[1] = True
                self._to_ring(cause[0])
            self._to_ring(rec)

    def _to_ring(self, rec):
        ring = self._ring if self._ring is not None else default_ring()
        ring.emit(rec["name"], at=(rec["end"], rec["end_epoch_ns"]),
                  **{k: rec[k] for k in ("program", "dur_s", "cause",
                                         "cache", "saved_s") if k in rec})

    # -- reads ------------------------------------------------------------
    def records(self, until_epoch_s: Optional[float] = None,
                since_epoch_s: Optional[float] = None) -> List[dict]:
        """Copies of the retained records, oldest first: those that
        ENDED by ``until_epoch_s`` and STARTED at or after
        ``since_epoch_s``, where given."""
        with self._lock:
            recs = [dict(r) for r in self._records]
        if until_epoch_s is not None:
            recs = [r for r in recs
                    if r["end_epoch_ns"] <= until_epoch_s * 1e9]
        if since_epoch_s is not None:
            recs = [r for r in recs
                    if r["start_epoch_ns"] >= since_epoch_s * 1e9]
        return recs

    def totals(self, until_epoch_s: Optional[float] = None,
               since_epoch_s: Optional[float] = None) -> dict:
        """Self seconds by phase, ``programs`` (backend records),
        ``hits``, ``misses``.  With no bound the process's lifetime sums,
        which the counters read (what fell out of the list included);
        with one, of the retained records inside it."""
        if until_epoch_s is None and since_epoch_s is None:
            with self._lock:
                return dict(self._sums, dropped=self._dropped,
                            faults=self.faults)
        out = _zero_sums()
        for r in self.records(until_epoch_s, since_epoch_s):
            _add(out, r)
        return out

    def by_program(self, top: Optional[int] = None,
                   until_epoch_s: Optional[float] = None,
                   since_epoch_s: Optional[float] = None) -> List[dict]:
        """The retained records summed by program, the largest first.  A
        nested trace counts for the program whose trace it lies in."""
        recs = self.records(until_epoch_s, since_epoch_s)
        owner = {r["id"]: r["program"] for r in recs
                 if r["name"] == TRACE and r["parent"] is None}
        out: Dict[str, dict] = {}
        for r in recs:
            prog = owner.get(r["cause"], r["program"])
            row = out.setdefault(prog, dict(_zero_sums(), program=prog))
            _add(row, r)
        rows = sorted(out.values(), key=lambda row: -total_s(row))
        return rows if top is None else rows[:top]

    def to_jsonl(self) -> str:
        return "\n".join(json.dumps(r) for r in self.records())

    def bind(self, registry: MetricsRegistry) -> None:
        """The six ``paddle_tpu_compile_*`` counters in ``registry``,
        each reading this log's lifetime sums when it is scraped."""
        for name, (key, text) in INSTRUMENTS.items():
            registry.counter(name, text).set_function(
                lambda key=key: self.totals()[key])


def _add(sums: dict, rec: dict) -> None:
    sums[_SUM_OF[rec["name"]]] += rec["self_s"]
    if rec["name"] == BACKEND:
        sums["programs"] += 1
        sums["hits"] += rec["cache"] == "hit"
        sums["misses"] += rec["cache"] == "miss"


def total_s(row: dict) -> float:
    """All three phases of one row of ``totals`` / ``by_program``."""
    return row["trace_s"] + row["lower_s"] + row["backend_s"]


_default = CompileLog()
_enabled = False
_enable_lock = threading.Lock()


def default_log() -> CompileLog:
    """The process-wide log :func:`enable` feeds."""
    return _default


def enable() -> CompileLog:
    """Register the default log's listeners with ``jax.monitoring`` and
    bind ``default_registry()``.  Idempotent: a second call adds nothing,
    each event is recorded once."""
    global _enabled
    with _enable_lock:
        if not _enabled:
            import jax.monitoring as mon
            mon.register_scalar_listener(_default.on_scalar)
            mon.register_event_listener(_default.on_event)
            mon.register_event_duration_secs_listener(_default.on_duration)
            mon.register_event_time_span_listener(_default.on_span)
            _default.bind(default_registry())
            _enabled = True
    return _default


def bind(registry: MetricsRegistry) -> None:
    _default.bind(registry)


def records(until_epoch_s: Optional[float] = None,
            since_epoch_s: Optional[float] = None) -> List[dict]:
    return _default.records(until_epoch_s, since_epoch_s)


def totals(until_epoch_s: Optional[float] = None,
           since_epoch_s: Optional[float] = None) -> dict:
    return _default.totals(until_epoch_s, since_epoch_s)


def by_program(top: Optional[int] = None,
               until_epoch_s: Optional[float] = None,
               since_epoch_s: Optional[float] = None) -> List[dict]:
    return _default.by_program(top, until_epoch_s, since_epoch_s)


def to_jsonl() -> str:
    return _default.to_jsonl()
