"""Backend compiles inside the measured window (JAX's own
``backend_compile_duration`` events: a program built, or loaded from
the persistent cache).  Expected: none."""


def read(trace, counters, spans, cell):
    return counters.get("compiles_in_window")
