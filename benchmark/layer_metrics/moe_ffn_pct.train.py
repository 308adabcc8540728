"""Share of the step's device self time under the five scopes of the
expert layer (``moe_route``, ``moe_dispatch``, ``moe_experts``,
``moe_combine``, ``moe_shared``), every pass: forward, recompute and
backward.  Nothing where the program names none of them (a family
without routed experts, a program from before them)."""

from benchmark import xplane_meta


def read(trace, counters, spans, cell):
    scopes = getattr(cell.family, "MOE_SCOPES", ())
    mt = xplane_meta.of_cell(cell, trace)
    by = mt.self_time_by("scope") if mt else {}
    moe = sum(by.get(s, 0.0) for s in scopes)
    if not moe:
        return None
    return 100.0 * moe / sum(by.values())
