"""How late the load generator sent: sent minus due, on its own clock.
A starved generator must not be read as a fast server."""

from benchmark.stats import percentile


def read(trace, counters, spans, cell):
    late = spans.get("late_ms")
    return percentile(late, 95) if late else None
