"""Packed prefill against the chip's bf16 peak: the operations the
prompts whose first token fell in the traced slice require (pads not
counted) over the device time of the packed-prefill executable
(``jit_run``, the jitted ``run`` of ``models/paged_decode
._prefill_packed``) in that slice."""

from benchmark import kernel_costs, peaks


def read(trace, counters, spans, cell):
    if not trace or not spans.get("prompt_lens_started"):
        return None
    dev_s = sum(trace.module_durations("jit_run"))
    if not dev_s:
        return None
    flops = kernel_costs.prefill_flops(cell.conf,
                                       spans["prompt_lens_started"])
    peak = peaks.chip_peaks(counters["device_kind"]).flops
    return 100.0 * flops / counters["chips"] / (dev_s * peak)
