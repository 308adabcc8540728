"""Device self time a step under ``moe_route``, ``moe_dispatch`` and
``moe_combine``: the router, the sort of the (token, pick) pairs by
expert, the gather of the rows into the sorted buffer and of the rows
back to their tokens, every pass — what routing WITHOUT DROPPING costs
around the grouped products (the buffer is sized for every pair of
every token, whatever the load).  Nothing where the program names none
of the three."""

from benchmark import xplane_meta

SCOPES = ("moe_route", "moe_dispatch", "moe_combine")


def read(trace, counters, spans, cell):
    mt = xplane_meta.of_cell(cell, trace)
    runs = mt.executions("jit_step") if mt else 0
    by = mt.self_time_by("scope") if mt else {}
    busy = sum(by.get(s, 0.0) for s in SCOPES)
    if not busy or not runs:
        return None
    return busy * 1e3 / runs
