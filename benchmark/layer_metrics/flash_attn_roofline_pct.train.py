"""The dense flash-attention kernels against the chip's bf16 peak: the
operations causal attention forward + backward needs for the traced
steps' tokens (``kernel_costs_kernels``; recompute not counted) over
the device time of the kernels ``flash_fwd`` + ``flash_bwd_dq`` +
``flash_bwd_dkv`` — every run of them, the recompute's forward too —
over the peak.  Bound: compute."""

from benchmark import kernel_costs_kernels, peaks, xplane_meta

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(trace, counters, spans, cell):
    mt = xplane_meta.of_cell(cell, trace)
    by = mt.self_time_by("kernel") if mt else {}
    busy_s = sum(by.get(k, 0.0) for k in KERNELS)
    steps = mt.executions("jit_step") if mt else 0
    if not busy_s or not steps:
        return None
    flops = kernel_costs_kernels.flash_attn_train_flops_per_token(
        cell.conf, cell.traffic["seq"]) \
        * counters["tokens_per_step"] * steps / counters["chips"]
    peak = peaks.chip_peaks(counters["device_kind"]).flops
    return 100.0 * flops / (busy_s * peak)
