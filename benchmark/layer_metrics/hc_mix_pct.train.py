"""Share of the step's device self time under ``hc_pre`` and
``hc_post``: the residual streams' mixers — the maps from the streams
(the product with phi, Sinkhorn), H_pre . X before a sublayer and H_res
. X + H_post^T (x) y after it, the copy into the streams and their sum
at the trunk's two ends — every pass.  Nothing where the program names
neither (a family with one residual stream)."""

from benchmark import xplane_meta


def read(trace, counters, spans, cell):
    scopes = getattr(cell.family, "HC_SCOPES", ())
    mt = xplane_meta.of_cell(cell, trace)
    by = mt.self_time_by("scope") if mt else {}
    mix = sum(by.get(s, 0.0) for s in scopes)
    if not mix:
        return None
    return 100.0 * mix / sum(by.values())
