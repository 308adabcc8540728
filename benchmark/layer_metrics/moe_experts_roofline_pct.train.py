"""The routed experts' grouped products against the chip's bf16 peak:
the operations the products need for the traced steps' tokens — the
family's ``expert_flops_per_token``: three products forward and six
backward, 2 x hidden x expert width each, for the EXPECTED pairs a token
sends to the experts held here (the harness hands a reader no counter
of the program; PERF.md states how far the real count lay from it),
recompute not counted — over the device time under the scope
``moe_experts`` (the kernels and the elementwise work between them),
every pass, over the peak.  Bound: compute, at about 1,024 rows an
expert.  Nothing where the program names no such scope or the family
states no such cost."""

from benchmark import peaks, xplane_meta


def read(trace, counters, spans, cell):
    fam = cell.family
    if not hasattr(fam, "expert_flops_per_token"):
        return None
    mt = xplane_meta.of_cell(cell, trace)
    busy_s = mt.self_time_by("scope").get("moe_experts", 0.0) if mt else 0.0
    steps = mt.executions("jit_step") if mt else 0
    if not busy_s or not steps:
        return None
    flops = fam.expert_flops_per_token(cell.conf) \
        * counters["tokens_per_step"] * steps / counters["chips"]
    peak = peaks.chip_peaks(counters["device_kind"]).flops
    return 100.0 * flops / (busy_s * peak)
