"""The FLOPs the chunked delta-rule kernels ``kda_chunk_fwd`` +
``kda_chunk_bwd`` declare, every run of them, over the FLOPs the
recurrence needs for the traced tokens exactly as
``kda_chunk_roofline_pct.train`` counts them (3 x the family's
``kda_chunk_flops_per_token`` a delta-rule layer, recompute not
counted): the recompute's forward, the backward's own forward, the pair
products on whole masked tiles, the fp32 inverse and the VPU's passes
show here.  Nothing where the family states no such cost or the trace
holds no such kernel."""

from benchmark import declared_work, xplane_meta

KERNELS = ("kda_chunk_fwd", "kda_chunk_bwd")


def read(trace, counters, spans, cell):
    fam = cell.family
    if not hasattr(fam, "kda_chunk_flops_per_token"):
        return None
    return declared_work.per_needed(
        xplane_meta.of_cell(cell, trace), counters, "flops", KERNELS,
        fam.kda_layers(cell.conf)
        * 3.0 * fam.kda_chunk_flops_per_token(cell.conf))
