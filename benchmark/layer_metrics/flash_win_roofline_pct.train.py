"""The WINDOWED flash-attention kernels against the chip's bf16 peak:
the operations the window layers' attention needs forward + backward
for the traced steps' tokens — the family's
``window_attn_train_flops_per_token`` of the configuration and the
row: QK^T and PV over the ``W - W^2 / 2 seq`` keys a query sees, times 3
with the backward, recompute not counted — over the device time of the
kernels ``flash_win_fwd`` + ``flash_win_bwd_dq`` + ``flash_win_bwd_dkv``,
every run of them, over the peak.  Bound: compute.  Nothing where the
family states no such cost or the trace holds no such kernel (a program
without the windowed form)."""

from benchmark import peaks, xplane_meta

KERNELS = ("flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv")


def read(trace, counters, spans, cell):
    fam = cell.family
    if not hasattr(fam, "window_attn_train_flops_per_token"):
        return None
    mt = xplane_meta.of_cell(cell, trace)
    by = mt.self_time_by("kernel") if mt else {}
    busy_s = sum(by.get(k, 0.0) for k in KERNELS)
    steps = mt.executions("jit_step") if mt else 0
    if not busy_s or not steps:
        return None
    flops = fam.window_attn_train_flops_per_token(
        cell.conf, cell.traffic["seq"]) \
        * counters["tokens_per_step"] * steps / counters["chips"]
    peak = peaks.chip_peaks(counters["device_kind"]).flops
    return 100.0 * flops / (busy_s * peak)
