"""Share of the device's self time in the backward pass proper: ops
whose JAX op path holds ``transpose(jvp(`` and not
``rematted_computation`` (that is ``recompute_pct.train``)."""

from benchmark import xplane_meta


def read(trace, counters, spans, cell):
    mt = xplane_meta.of_cell(cell, trace)
    by = mt.self_time_by("phase") if mt else {}
    total = sum(by.values())
    return 100.0 * by.get("backward", 0.0) / total if total else None
