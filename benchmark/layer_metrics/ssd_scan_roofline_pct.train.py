"""The scan kernels ``ssd_scan_fwd`` + ``ssd_scan_bwd`` against the
chip's peaks: what the chunked scan NEEDS for the traced steps' tokens —
the family's operations (``scan_flops_per_token``, forward x 3 with the
backward) and bytes (``scan_kernel_bytes_per_token``, both passes;
recompute not counted), summed over the layers that scan — as the time
the SLOWER of the two bounds allows, over the device time of the
kernels, every run of them (the recompute's forward too).

Which bound binds: at the entered configuration (Q 256, N 128, P 64, 64
heads) a layer's scan needs 12.78 MFLOP and 61,952 B a token, 206 FLOP a
byte, under the v5e's ridge of 240: MEMORY binds, by 75.6 ns against
64.9 a token a layer.  It is a floor that no kernel of this shape
reaches: a 64-wide head uses half of a 128-wide MXU, so the work the MXU
executes is twice what is counted, and the ``[Q, Q]`` exp / mask / scale
passes run on the VPU.  Nothing where the program has no such kernel or
the family states no such costs."""

from benchmark import peaks, xplane_meta

KERNELS = ("ssd_scan_fwd", "ssd_scan_bwd")


def read(trace, counters, spans, cell):
    fam = cell.family
    if not hasattr(fam, "scan_kernel_bytes_per_token"):
        return None
    mt = xplane_meta.of_cell(cell, trace)
    by = mt.self_time_by("kernel") if mt else {}
    busy_s = sum(by.get(k, 0.0) for k in KERNELS)
    steps = mt.executions("jit_step") if mt else 0
    if not busy_s or not steps:
        return None
    layers = fam.layer_kinds(cell.conf).count("mamba")
    tokens = counters["tokens_per_step"] * steps / counters["chips"]
    peak = peaks.chip_peaks(counters["device_kind"])
    need_s = layers * tokens * max(
        3.0 * fam.scan_flops_per_token(cell.conf) / peak.flops,
        fam.scan_kernel_bytes_per_token(cell.conf) / peak.hbm_bw)
    return 100.0 * need_s / busy_s
