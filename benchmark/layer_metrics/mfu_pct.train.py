"""Model FLOP/s utilisation of the train step: the operations forward
and backward need for a step's tokens (recompute not counted) over the
step's median device time (XLA module ``jit_step``) over the chip's
bf16 peak.  Taken on the device's clock, not from the traced run's
tokens a second: the profiler's own start and stop sit inside that
window and cost it 4 %."""

from benchmark import kernel_costs, peaks


def read(trace, counters, spans, cell):
    ms = trace.module_median_ms("jit_step") if trace else None
    if not ms:
        return None
    flops = kernel_costs.train_flops_per_token(
        cell.conf, cell.traffic["seq"]) * counters["tokens_per_step"]
    peak = peaks.chip_peaks(counters["device_kind"]).flops
    return 100.0 * flops / counters["chips"] / (ms * 1e-3 * peak)
