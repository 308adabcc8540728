"""Share of the step's device self time under the five scopes of the
latent-attention mixer (``mla_q``, ``mla_kv``, ``rope``, ``attn``,
``attn_out``), every pass: forward, recompute and backward.  Nothing
where the program names no ``mla_*`` scope (a family without latent
attention, a program from before it: ``rope``, ``attn`` and ``attn_out``
alone are another attention's)."""

from benchmark import xplane_meta

MLA_SCOPES = ("mla_q", "mla_kv")
SCOPES = MLA_SCOPES + ("rope", "attn", "attn_out")


def read(trace, counters, spans, cell):
    mt = xplane_meta.of_cell(cell, trace)
    by = mt.self_time_by("scope") if mt else {}
    if not sum(by.get(s, 0.0) for s in MLA_SCOPES):
        return None
    return 100.0 * sum(by.get(s, 0.0) for s in SCOPES) / sum(by.values())
