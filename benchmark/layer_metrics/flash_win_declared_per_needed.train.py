"""The FLOPs the WINDOWED flash-attention kernels ``flash_win_fwd`` +
``flash_win_bwd_dq`` + ``flash_win_bwd_dkv`` declare, every run of them,
over what the window layers' attention needs forward + backward for the
traced tokens (the family's ``window_attn_train_flops_per_token``, the
count ``flash_win_roofline_pct.train`` divides by time): the two masked
blocks at the window's ends (252 block pairs a head executed where 224
are the visible keys' worth at S 16,384, W 4,096, block 512), a forward
run twice under full remat, the two-kernel backward's seven block
products against the needed four all show here.  A kernel that visited
every causal pair would read 3.5 there.  Nothing where the family
states no such cost or the trace holds no such kernel."""

from benchmark import declared_work, xplane_meta

KERNELS = ("flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv")


def read(trace, counters, spans, cell):
    fam = cell.family
    if not hasattr(fam, "window_attn_train_flops_per_token"):
        return None
    return declared_work.per_needed(
        xplane_meta.of_cell(cell, trace), counters, "flops", KERNELS,
        fam.window_attn_train_flops_per_token(cell.conf,
                                              cell.traffic["seq"]))
