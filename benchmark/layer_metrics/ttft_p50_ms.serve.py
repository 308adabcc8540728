"""Time to first token, 50th percentile over the requests due in the
window: first streamed token minus the time the request was DUE (not
sent), on the load generator's clock.  Not an end-to-end metric yet:
the admission lane compiles new eager programs for wave layouts it has
not met (0.9 s each on the v5e, PERF.md section 6), so this tail swings
with what the compile cache happens to hold."""

from benchmark.stats import percentile


def read(trace, counters, spans, cell):
    ttft = spans.get("ttft_ms")
    return percentile(ttft, 50) if ttft else None
