"""Share of the traced slice in which no operation ran on the device
(``xplane.Trace.idle_pct``)."""


def read(trace, counters, spans, cell):
    return trace.idle_pct() if trace else None
