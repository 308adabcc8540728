"""Share of the step's device self time under the six scopes of the
Kimi-Delta-Attention mixer (``kda_in_proj``, ``kda_conv``, ``kda_gates``,
``kda_chunk``, ``kda_out_gate``, ``kda_out_proj``), every pass: forward,
recompute and backward.  Nothing where the program names none of them (a
family without the mixer, a program from before it)."""

from benchmark import xplane_meta

SCOPES = ("kda_in_proj", "kda_conv", "kda_gates", "kda_chunk",
          "kda_out_gate", "kda_out_proj")


def read(trace, counters, spans, cell):
    mt = xplane_meta.of_cell(cell, trace)
    by = mt.self_time_by("scope") if mt else {}
    mixer = sum(by.get(s, 0.0) for s in SCOPES)
    if not mixer:
        return None
    return 100.0 * mixer / sum(by.values())
