"""Share of the device's self time in Pallas kernels that declare no
work — custom calls whose ``flops`` and ``bytes_accessed`` are both 0:
the counter that says the declaration (``cost_estimate=`` at every
``pallas_call`` site) is still whole, as ``unscoped_pct.train`` says of
the naming.  Expected 0.0.  Nothing where the trace holds no kernel."""

from benchmark import declared_work, xplane_meta


def read(trace, counters, spans, cell):
    mt = xplane_meta.of_cell(cell, trace)
    kernels = [op for op in declared_work.leaves(mt)
               if declared_work.is_kernel(op)] if mt else []
    if not kernels:
        return None
    silent = sum(op.self_s for op in kernels
                 if not op.flops and not op.bytes_accessed)
    return 100.0 * silent / mt.device_self_s()
