"""Share of the window the step loop spent waiting for its next batch
(``next(feed)`` and the copy to the device): the benchmark's own span
around the call into ``io.DataLoader``."""


def read(trace, counters, spans, cell):
    if "input_wait_s" not in spans:
        return None
    return 100.0 * spans["input_wait_s"] / spans["window_s"]
