"""Share of the device's self time whose JAX op path falls under none
of the scopes of the vocabulary (``xplane_meta.SCOPES``): the counter
that says the naming is still whole after a refactor.  Nothing where
the program names no scope at all."""

from benchmark import xplane_meta


def read(trace, counters, spans, cell):
    mt = xplane_meta.of_cell(cell, trace)
    by = mt.self_time_by("scope") if mt else {}
    if set(by) <= {xplane_meta.UNSCOPED}:
        return None
    return 100.0 * by.get(xplane_meta.UNSCOPED, 0.0) / sum(by.values())
