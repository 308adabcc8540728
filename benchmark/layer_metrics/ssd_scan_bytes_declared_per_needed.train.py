"""The HBM bytes the scan kernels ``ssd_scan_fwd`` + ``ssd_scan_bwd``
declare, every run of them, over the bytes the chunked scan needs for
the traced tokens exactly as ``ssd_scan_roofline_pct.train`` counts
them (the family's ``scan_kernel_bytes_per_token`` a layer that scans,
recompute not counted): the recompute's run, a tile fetched more than
once and what the kernels move beside the count show here.  Nothing
where the family states no such cost or the trace holds no such
kernel."""

from benchmark import declared_work, xplane_meta

KERNELS = ("ssd_scan_fwd", "ssd_scan_bwd")


def read(trace, counters, spans, cell):
    fam = cell.family
    if not hasattr(fam, "scan_kernel_bytes_per_token"):
        return None
    return declared_work.per_needed(
        xplane_meta.of_cell(cell, trace), counters, "bytes_accessed",
        KERNELS, fam.layer_kinds(cell.conf).count("mamba")
        * fam.scan_kernel_bytes_per_token(cell.conf))
