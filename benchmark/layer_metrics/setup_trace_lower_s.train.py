"""Seconds of a set-up spent TRACING and LOWERING, over all programs
before the window: what a warm run pays again whatever the persistent
cache holds, since the cache's key is made from the lowered module.
Self time of the compile log's ``compile.trace`` records (a nested trace
is not counted twice) plus its ``compile.lower`` records."""

from benchmark.layer_metrics import _setup_log


def read(trace, counters, spans, cell):
    found = _setup_log.before_the_window(trace, counters, cell)
    if found is None:
        return None
    log, until = found
    t = log.totals(until_epoch_s=until)
    return t["trace_s"] + t["lower_s"] if t["programs"] else None
