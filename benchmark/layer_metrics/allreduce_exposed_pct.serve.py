"""Share of the decode steps' device time in which a collective ran
and nothing else did on that chip: the all-reduces the compute did not
hide."""


def read(trace, counters, spans, cell):
    if not trace or not trace.devices:
        return None
    step_s = sum(trace.module_durations("jit_step"))
    if not step_s or counters["chips"] == 1:
        return None
    return 100.0 * trace.devices[0].exposed_s() / step_s
