"""All FLOPs the traced steps DECLARE — XLA's figure for its fusions,
the program's ``cost_estimate`` for its kernels, every executed op once
— over the FLOPs the model needs for their tokens
(``kernel_costs.train_flops_per_token``, the count ``mfu_pct.train``
divides by time).  Recompute, masked block pairs, empty tiles and
padding are all in the numerator: 1.0 is a step that executes only
what it needs."""

from benchmark import declared_work, kernel_costs, xplane_meta


def read(trace, counters, spans, cell):
    return declared_work.per_needed(
        xplane_meta.of_cell(cell, trace), counters, "flops", None,
        kernel_costs.train_flops_per_token(cell.conf, cell.traffic["seq"]))
