"""Device self time a decode step under the scope ``pool_carry``: what
the layer scan itself does to move the page pools through (slices,
updates and copies outside any block)."""

from benchmark import xplane_meta


def read(trace, counters, spans, cell):
    mt = xplane_meta.of_cell(cell, trace)
    return mt.scope_ms_per("pool_carry", "jit_step") if mt else None
