"""Device self time a step under the scope ``ssm_scan``: the scan
kernels and whatever XLA leaves around them (softplus, the running sums,
the chunks' end states, the recurrence across chunks, the skip), every
pass.  Nothing where the program names no such scope."""

from benchmark import xplane_meta


def read(trace, counters, spans, cell):
    mt = xplane_meta.of_cell(cell, trace)
    return mt.scope_ms_per("ssm_scan", "jit_step") if mt else None
