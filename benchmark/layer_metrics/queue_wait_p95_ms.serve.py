"""Time a request waited for admission: the ``queued`` phase clock the
program keeps per request (``observability/tracing.phase_clocks``),
read from the server's trace store for every request due in the
window."""

from benchmark.stats import percentile


def read(trace, counters, spans, cell):
    waits = spans.get("queue_wait_s")
    return percentile(waits, 95) * 1e3 if waits else None
