"""Share of the step's device self time under the five scopes of the
state-space mixer (``ssm_in_proj``, ``ssm_conv``, ``ssm_scan``,
``ssm_gate_norm``, ``ssm_out_proj``), every pass: forward, recompute
and backward.  Nothing where the program names none of them (a family
without the mixer, a program from before it)."""

from benchmark import xplane_meta

SCOPES = ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm",
          "ssm_out_proj")


def read(trace, counters, spans, cell):
    mt = xplane_meta.of_cell(cell, trace)
    by = mt.self_time_by("scope") if mt else {}
    mixer = sum(by.get(s, 0.0) for s in SCOPES)
    if not mixer:
        return None
    return 100.0 * mixer / sum(by.values())
