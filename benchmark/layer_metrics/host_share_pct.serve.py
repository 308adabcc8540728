"""The host's share of a serving step: 1 - the device's busy time
inside the ``engine.step`` spans over their total length."""

from benchmark import xplane_meta


def read(trace, counters, spans, cell):
    mt = xplane_meta.of_cell(cell, trace)
    steps = mt.spans(("engine.step",)) if mt else []
    total = sum(h.end_s - h.start_s for h in steps)
    if not total:
        return None
    busy = sum(mt.device.busy_s(h.start_s, h.end_s) for h in steps)
    return 100.0 * (1.0 - busy / total)
