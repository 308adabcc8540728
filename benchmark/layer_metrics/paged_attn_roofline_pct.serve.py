"""The paged-attention kernel against the chip's HBM bandwidth: the
keys and values of the tokens resident over the traced slice (from the
client's stamps), read once a decode step, over the device time of the
kernel ``paged_attn`` a step, over the bandwidth.  Bound: memory."""

from benchmark import kernel_costs_kernels, peaks, xplane_meta


def read(trace, counters, spans, cell):
    mt = xplane_meta.of_cell(cell, trace)
    busy_s = mt.self_time_by("kernel").get("paged_attn") if mt else None
    steps = mt.executions("jit_step") if mt else 0
    if not busy_s or not steps or spans.get("resident_tokens") is None:
        return None
    need = kernel_costs_kernels.paged_attn_step_bytes(
        cell.conf, spans["resident_tokens"], counters["chips"])
    least_s = need / peaks.chip_peaks(counters["device_kind"]).hbm_bw
    return 100.0 * least_s / (busy_s / steps)
