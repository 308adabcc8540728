"""The gated short-convolution kernels ``short_conv_fwd`` +
``short_conv_bwd`` against the chip's HBM peak: the bytes the operator's
middle NEEDS for the traced steps' tokens — the family's
``short_conv_bytes_per_token``, both passes, recompute not counted,
summed over the convolution layers — as the time the peak allows, over
the device time of the kernels, every run of them (the recompute's
forward too, so full remat alone holds the reading to 11 / 15).

Memory binds: the pass does 2 K + 1 operations a value it reads.  At the
entered configuration (C 2048, K 3, bf16) a layer needs 45,056 B a
token, 55.0 ns at 819 GB/s.  Nothing where the program has no such
kernel or the family states no such cost."""

from benchmark import peaks, xplane_meta

KERNELS = ("short_conv_fwd", "short_conv_bwd")


def read(trace, counters, spans, cell):
    fam = cell.family
    if not hasattr(fam, "short_conv_bytes_per_token"):
        return None
    mt = xplane_meta.of_cell(cell, trace)
    by = mt.self_time_by("kernel") if mt else {}
    busy_s = sum(by.get(k, 0.0) for k in KERNELS)
    steps = mt.executions("jit_step") if mt else 0
    if not busy_s or not steps:
        return None
    tokens = counters["tokens_per_step"] * steps / counters["chips"]
    need_s = fam.conv_layers(cell.conf) * tokens \
        * fam.short_conv_bytes_per_token(cell.conf) \
        / peaks.chip_peaks(counters["device_kind"]).hbm_bw
    return 100.0 * need_s / busy_s
