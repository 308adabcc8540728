"""The work the program DECLARES it executes, read off a device trace.

Every device op carries its own ``flops`` and ``bytes_accessed`` in its
event metadata (``xplane_meta.Op``): XLA's figure for the fusions it
built, and for a Pallas kernel what the program stated at the call site
(``cost_estimate=`` of ``pallas_call``; docs/OBSERVABILITY.md, "a
kernel's work is its ``cost_estimate``").  The readers under
``layer_metrics/*_declared_per_needed.train.py`` divide sums of them by
what the ALGORITHM needs (``kernel_costs*.py``, the family files).

An op that CONTAINS other ops of the line — a ``while``, a
``conditional``, a call — carries a figure for its body (one trip of
it), and the body's ops are on the line too, once for every time they
ran: only LEAVES are summed, each execution once.
"""

from __future__ import annotations

from . import xplane_meta

STEP = "jit_step"


def leaves(mt) -> list:
    """Chip 0's ops that contain no other op of the line.  The ops nest
    and do not cross, sorted by start with the longer first: an op holds
    another exactly when the next one starts before it ends."""
    ops = mt.ops.get(mt.chip(), [])
    return [op for op, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt.start_s >= op.end_s]


def is_kernel(op) -> bool:
    """A Pallas kernel of the program: the compiler's category says
    custom call and the op path ends in ``pallas_call`` (XLA's own
    custom calls, ``ConcatBitcast`` and the like, compute and move
    nothing and are no kernel)."""
    return op.category == "custom-call" and \
        op.tf_op.rpartition("/")[2] == "pallas_call"


def declared(mt, field: str, kernels=None) -> float:
    """The sum of ``flops`` or ``bytes_accessed`` over the executed
    leaves: all of them, or the Pallas kernels named (the custom calls
    themselves: a copy XLA puts beside one inherits its op path)."""
    total = 0.0
    for op in leaves(mt):
        if kernels is None or (is_kernel(op) and xplane_meta.kernel_of(
                op.tf_op, mt.kernels) in kernels):
            total += getattr(op, field)
    return total


def traced_tokens(mt, counters) -> float:
    """Tokens one chip took through the traced steps."""
    return counters["tokens_per_step"] * mt.executions(STEP) \
        / counters["chips"]


def per_needed(mt, counters, field, kernels, needed_per_token):
    """Declared over needed for the traced tokens; None where nothing
    of the kind is declared in the trace or needed by the model."""
    if mt is None:
        return None
    have = declared(mt, field, kernels)
    need = needed_per_token * traced_tokens(mt, counters)
    return have / need if have and need else None
