"""The HBM bytes the gated short-convolution kernels ``short_conv_fwd``
+ ``short_conv_bwd`` declare, every run of them, over the bytes the
operator's middle needs for the traced tokens exactly as
``short_conv_roofline_pct.train`` counts them (the family's
``short_conv_bytes_per_token`` a convolution layer, recompute not
counted): the recompute's forward (4 of 11 again), the 8-row halos and
the taps' tables show here.  Nothing where the family states no such
cost or the trace holds no such kernel."""

from benchmark import declared_work, xplane_meta

KERNELS = ("short_conv_fwd", "short_conv_bwd")


def read(trace, counters, spans, cell):
    fam = cell.family
    if not hasattr(fam, "short_conv_bytes_per_token"):
        return None
    return declared_work.per_needed(
        xplane_meta.of_cell(cell, trace), counters, "bytes_accessed",
        KERNELS, fam.conv_layers(cell.conf)
        * fam.short_conv_bytes_per_token(cell.conf))
