"""Of the backend compiles before the window, the share that MISSED the
persistent cache (compiled, and wrote their entry): 0 on a warm run,
near 100 on a first one — what tells ``first_setup_s`` from ``setup_s``
in a result line.  The compile log's ``misses`` over its ``programs``."""

from benchmark.layer_metrics import _setup_log


def read(trace, counters, spans, cell):
    found = _setup_log.before_the_window(trace, counters, cell)
    if found is None:
        return None
    log, until = found
    t = log.totals(until_epoch_s=until)
    return 100.0 * t["misses"] / t["programs"] if t["programs"] else None
