"""The decode step against its memory roofline: the bytes one step has
to move on one chip (its share of the weights once, plus the keys and
values of the tokens resident over the traced slice, from the client's
stamps) over the chip's HBM bandwidth, over the step's median device
time.  Bound: memory."""

from benchmark import kernel_costs, peaks


def read(trace, counters, spans, cell):
    ms = trace.module_median_ms("jit_step") if trace else None
    if not ms or spans.get("resident_tokens") is None:
        return None
    need = kernel_costs.decode_step_bytes(
        cell.conf, spans["resident_tokens"], counters["chips"])
    least_s = need / peaks.chip_peaks(counters["device_kind"]).hbm_bw
    return 100.0 * least_s / (ms * 1e-3)
