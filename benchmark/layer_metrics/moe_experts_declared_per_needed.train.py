"""The FLOPs the routed experts' grouped products ``grouped_mm`` +
``grouped_mm_dw`` declare — the tiles of the BOUND each pass was
launched at, every pass — over what the products need for the EXPECTED
pairs of the traced tokens (the family's ``expert_flops_per_token``,
the count ``moe_experts_roofline_pct.train`` divides by time): what not
dropping costs in scheduled tiles.  It rises when a layer-pass falls
from the load's bound to the bound of any load.  Nothing where the
family states no such cost or the trace holds no such kernel."""

from benchmark import declared_work, xplane_meta

KERNELS = ("grouped_mm", "grouped_mm_dw")


def read(trace, counters, spans, cell):
    fam = cell.family
    if not hasattr(fam, "expert_flops_per_token"):
        return None
    return declared_work.per_needed(
        xplane_meta.of_cell(cell, trace), counters, "flops", KERNELS,
        fam.expert_flops_per_token(cell.conf))
