"""The chunked delta-rule kernels ``kda_chunk_fwd`` + ``kda_chunk_bwd``
against the chip's peaks: what the recurrence NEEDS for the traced steps'
tokens — the family's operations (``kda_chunk_flops_per_token``, forward
x 3 with the backward) and bytes (``kda_chunk_bytes_per_token``, both
passes; recompute not counted), summed over the delta-rule layers — as
the time the SLOWER of the two bounds allows, over the device time of
the kernels, every run of them (the recompute's forward too).

Which bound binds: at the entered configuration (Q 64, 64 heads of 128 x
128, bf16) a layer's recurrence needs 3 x 8.91 MFLOP and 279,296 B a
token — 136 ns at the bf16 peak against 341 ns at 819 GB/s: MEMORY
binds.  It is a floor far under what a kernel of this shape takes: the
pair sums inside a sub-block are channel by channel on the VPU (16
exponentials a key channel a position), the triangle is inverted by
fp32 products, and a 64-row chunk fills half of the MXU's rows.  Nothing
where the program has no such kernel or the family states no such
costs."""

from benchmark import peaks, xplane_meta

KERNELS = ("kda_chunk_fwd", "kda_chunk_bwd")


def read(trace, counters, spans, cell):
    fam = cell.family
    if not hasattr(fam, "kda_chunk_bytes_per_token"):
        return None
    mt = xplane_meta.of_cell(cell, trace)
    by = mt.self_time_by("kernel") if mt else {}
    busy_s = sum(by.get(k, 0.0) for k in KERNELS)
    steps = mt.executions("jit_step") if mt else 0
    if not busy_s or not steps:
        return None
    tokens = counters["tokens_per_step"] * steps / counters["chips"]
    peak = peaks.chip_peaks(counters["device_kind"])
    need_s = fam.kda_layers(cell.conf) * tokens * max(
        3.0 * fam.kda_chunk_flops_per_token(cell.conf) / peak.flops,
        fam.kda_chunk_bytes_per_token(cell.conf) / peak.hbm_bw)
    return 100.0 * need_s / busy_s
