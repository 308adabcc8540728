"""paddle_tpu.observability — unified metrics + tracing for the
serving/training stack.

Three pieces (see docs/OBSERVABILITY.md for the metric catalogue and
scrape/export recipes):

* :mod:`.metrics` — thread-safe process-wide registry of
  Counter/Gauge/Histogram instruments, Prometheus text exposition
  (``GET /metrics`` on the servers) and a JSON snapshot API
  (``GET /stats``).
* :mod:`.events` — bounded structured-event ring buffer (JSON lines)
  with chrome-trace export that merges the profiler's RecordEvent
  spans onto the same timeline.
* :mod:`.engine_metrics` — the instrument bundle the
  continuous-batching serving stack records into (single source of
  truth for the metric catalogue).
* :mod:`.compile_log` — the program's own log of what it traced,
  lowered, compiled or loaded from the persistent cache, by program
  name and on both clocks (a ``jax.monitoring`` listener registered
  when ``paddle_tpu`` is imported).
* :mod:`.tracing` — end-to-end per-request distributed tracing:
  trace-context propagation across router/engine/handoff/failover
  boundaries, retirement-time span materialization from per-request
  phase clocks, and a bounded tail-sampling :class:`TraceStore`
  served at ``GET /trace/<rid>`` / ``GET /traces``.

Everything is stdlib-only and host-side: instrumentation adds zero
jitted programs and never forces a device sync — values are recorded
from numbers the engine already materializes on host.
"""

from .events import EventRing, default_ring, stamp     # noqa: F401
from .metrics import (Counter, Gauge, Histogram,       # noqa: F401
                      MetricsRegistry, default_registry)
from .engine_metrics import (EngineMetrics,            # noqa: F401
                             bind_engine_gauges)
from .fleet_metrics import FleetMetrics                # noqa: F401
from .disagg_metrics import DisaggMetrics              # noqa: F401
from .transport_metrics import TransportMetrics        # noqa: F401
from . import compile_log                              # noqa: F401
from .tracing import (PHASES, TraceContext, Tracer,    # noqa: F401
                      TraceStore, advance_phase, default_tracer,
                      finalize_request_trace, phase_clocks)

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "default_registry", "EventRing", "default_ring", "stamp",
           "compile_log",
           "EngineMetrics", "bind_engine_gauges", "FleetMetrics",
           "DisaggMetrics", "TransportMetrics", "PHASES",
           "TraceContext", "Tracer",
           "TraceStore", "advance_phase", "default_tracer",
           "finalize_request_trace", "phase_clocks"]
