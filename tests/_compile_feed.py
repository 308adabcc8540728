"""JAX's compile events fed to a ``CompileLog`` by hand, for the tests
that need a log of known contents (``test_compile_log.py``,
``test_setup_readers.py``)."""

TRACE_EV = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EV = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EV = "/jax/core/compile/backend_compile_duration"


def feed(log, program, t, trace=0.0, lower=0.0, backend=0.0, inner=(),
         cache=None, **cache_s):
    """One program's compile, by hand, starting at epoch second ``t``:
    the events in the order JAX fires them.  ``inner``: (name, start
    offset, seconds) of jitted functions traced inside the trace."""
    log.on_scalar(TRACE_EV, t, fun_name=program)
    for name, at, secs in inner:
        log.on_scalar(TRACE_EV, t + at, fun_name=name)
        log.on_span(TRACE_EV, t + at, t + at + secs, fun_name=name)
    log.on_span(TRACE_EV, t, t + trace, fun_name=program)
    t += trace
    if lower:
        log.on_span(LOWER_EV, t, t + lower, fun_name=f"jit({program})")
        t += lower
    if backend:
        if cache:
            log.on_event("/jax/compilation_cache/cache_" + cache)
        for key, secs in cache_s.items():
            log.on_duration("/jax/compilation_cache/" + key, secs)
        log.on_span(BACKEND_EV, t, t + backend,
                    fun_name=f"jit({program})")
    return t + backend
