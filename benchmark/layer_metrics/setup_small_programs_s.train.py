"""Seconds of a set-up that went into every program BUT the train
step's, all three phases: the eager one-op programs of ``make_params``
and ``init_adafactor_state``, the harness's ``leaf_maker``, the norms of
the followed steps.  The compile log's sum before the window less the
step's row (``setup_step_compile_s.train``)."""

from benchmark.layer_metrics import _setup_log


def read(trace, counters, spans, cell):
    found = _setup_log.before_the_window(trace, counters, cell)
    if found is None:
        return None
    log, until = found
    step, rows = _setup_log.step_row(log, until)
    if step is None:
        return None
    return sum(log.total_s(r) for r in rows if r is not step)
