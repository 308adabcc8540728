"""Median device duration of the train-step executable (``jit_step`` is
the name ``make_train_step``'s jitted function gives its XLA module)."""


def read(trace, counters, spans, cell):
    return trace.module_median_ms("jit_step") if trace else None
