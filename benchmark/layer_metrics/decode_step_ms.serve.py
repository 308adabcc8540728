"""Median device duration of the decode-step executable: ``jit_step``
is the XLA module name of the jitted ``step`` that
``models/paged_decode.make_paged_decode_step`` (and its TP form)
returns; no other program of a serving process has that name."""


def read(trace, counters, spans, cell):
    return trace.module_median_ms("jit_step") if trace else None
