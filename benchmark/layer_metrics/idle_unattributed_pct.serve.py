"""Share of the device's idle time inside the traced slice that no
program span covers (``xplane_meta.idle_by_span``)."""

from benchmark import xplane_meta


def read(trace, counters, spans, cell):
    mt = xplane_meta.of_cell(cell, trace)
    if mt is None or not mt.spans():
        return None
    idle = mt.idle_by_span(trace.lo, trace.hi)
    total = sum(idle.values())
    if not total:
        return None
    return 100.0 * idle.get(xplane_meta.UNATTRIBUTED, 0.0) / total
