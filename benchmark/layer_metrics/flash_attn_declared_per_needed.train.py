"""The FLOPs the dense flash-attention kernels ``flash_fwd`` +
``flash_bwd_dq`` + ``flash_bwd_dkv`` declare, every run of them (the
recompute's forward too), over what causal attention forward + backward
needs for the traced tokens (``kernel_costs_kernels``, the count
``flash_attn_roofline_pct.train`` divides by time): the second forward
under full remat, the diagonal's masked half, the two-kernel backward's
seven block products against the one-pass five all show here, at
unchanged needed work."""

from benchmark import declared_work, kernel_costs_kernels, xplane_meta

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(trace, counters, spans, cell):
    return declared_work.per_needed(
        xplane_meta.of_cell(cell, trace), counters, "flops", KERNELS,
        kernel_costs_kernels.flash_attn_train_flops_per_token(
            cell.conf, cell.traffic["seq"]))
