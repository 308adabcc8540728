"""Operations and bytes that single KERNELS need, by the rule of
``kernel_costs.py``: from shapes and live token counts, never from
padded shapes or the program's own cost models.  These are the kernels
of the base vocabulary; a kernel of ONE family's own keeps its
operations and bytes in the family's file
(``benchmark/models/<family>.py``), where its reader finds them by
``cell.family``."""

from __future__ import annotations

from . import kernel_costs


def flash_attn_train_flops_per_token(conf: dict, seq: int) -> float:
    """Causal attention forward + backward for one token of a
    ``seq``-token row, recompute not counted: QK^T and PV over the S/2
    keys a query sees on average, 2 * 2 * S/2 * heads * head_dim a layer
    that attends forward, times 3 with the backward — the attention
    term of ``kernel_costs.train_flops_per_token``.  Bound: compute."""
    return 6.0 * seq * kernel_costs.over_layers(conf, "attn_width")


def paged_attn_step_bytes(conf: dict, resident_tokens: float,
                          chips: int = 1) -> float:
    """Least bytes the paged-attention kernel moves on one chip in one
    decode step (all layers): the keys and values of every resident
    token once.  Bound: memory."""
    return resident_tokens * kernel_costs.kv_bytes_per_token(conf, chips)
