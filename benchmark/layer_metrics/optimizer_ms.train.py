"""Device self time a step under the scope ``optimizer``: the
adafactor / adamw update, weight decay and the casts back."""

from benchmark import xplane_meta


def read(trace, counters, spans, cell):
    mt = xplane_meta.of_cell(cell, trace)
    return mt.scope_ms_per("optimizer", "jit_step") if mt else None
