"""Device self time a step under the scope ``loss_head``: the final
norm, the chunked 92k head and the loss, forward and backward."""

from benchmark import xplane_meta


def read(trace, counters, spans, cell):
    mt = xplane_meta.of_cell(cell, trace)
    return mt.scope_ms_per("loss_head", "jit_step") if mt else None
