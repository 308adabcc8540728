"""Share of the device's self time spent recomputing: ops whose JAX op
path (``tf_op``) holds ``rematted_computation``, the forward that
``jax.checkpoint`` runs again inside the backward pass."""

from benchmark import xplane_meta


def read(trace, counters, spans, cell):
    mt = xplane_meta.of_cell(cell, trace)
    by = mt.self_time_by("phase") if mt else {}
    total = sum(by.values())
    return 100.0 * by.get("recompute", 0.0) / total if total else None
