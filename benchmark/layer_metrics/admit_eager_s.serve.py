"""Seconds of the traced slice inside the admission lane's eager
program groups: the spans ``admit.first_token_tail`` and
``admit.write_pages`` (where PR 23 found 0.85-0.9 s of compile per
wave layout not met before)."""

from benchmark import xplane_meta

EAGER = ("admit.first_token_tail", "admit.write_pages")


def read(trace, counters, spans, cell):
    mt = xplane_meta.of_cell(cell, trace)
    found = mt.spans(EAGER) if mt else []
    if not found:
        return None
    return sum(h.end_s - h.start_s for h in found)
