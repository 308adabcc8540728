"""Seconds of a set-up that went into the TRAIN STEP's program: its
trace (the nested traces of the jitted functions it calls included), its
lowering, and its backend compile — or, on a warm cache, the retrieval
in the compile's place.  Read from the program's own compile log
(``paddle_tpu.observability.compile_log``), records that ended before
the traced slice; the step is the program whose normalised name is
``step`` (``make_train_step`` jits a function of that name: the cells'
logs say ``step`` / ``jit(step)``), else the largest by seconds."""

from benchmark.layer_metrics import _setup_log


def read(trace, counters, spans, cell):
    found = _setup_log.before_the_window(trace, counters, cell)
    if found is None:
        return None
    log, until = found
    step, _ = _setup_log.step_row(log, until)
    return log.total_s(step) if step else None
