"""Operations and bytes that the ALGORITHM needs, from shapes and live
token counts — never from padded shapes, never from the program's own
cost models.  ``conf`` is a configuration file's dict.

What ONE BLOCK costs is the family's to say (``block_costs`` of
``benchmark/models/<family>.py``, a :class:`BlockCosts` for each kind of
layer it has); the sum over the layers as the family orders them, the
embedding, the head and the arithmetic from those numbers to FLOPs and
bytes are here, the same for every architecture."""

from __future__ import annotations

from typing import NamedTuple

from . import models


class BlockCosts(NamedTuple):
    """One block, by its configuration.  The two counts of matrix
    parameters are equal where every token passes through every matrix;
    a block that routes a token to some of its experts multiplies fewer
    than it holds."""
    matmul_params: int      # matrix parameters a token multiplies: 2 FLOPs each, forward
    resident_params: int    # matrix parameters the block holds: the bytes a step may read
    vector_params: int      # norm vectors and the like: no matrix product
    attn_width: int         # heads x head_dim: QK^T and PV cost 2 x 2 x this a key a query; 0 where the layer attends to nothing
    kv_values: int          # values a token leaves in the cache
    scan_flops: int = 0     # FLOPs a token costs, forward, that come from no matrix parameter and do not grow with the row: a chunked scan at the configuration's own chunk size


def block_costs(conf: dict, *kind) -> BlockCosts:
    """One block of the family; ``kind`` where the family states kinds."""
    return models.family(conf).block_costs(conf, *kind)


def layer_costs(conf: dict) -> list:
    """One :class:`BlockCosts` a layer, in the family's order (its
    ``layer_kinds(conf)``; a family that states none has one kind)."""
    fam, depth = models.family(conf), conf["num_hidden_layers"]
    if not hasattr(fam, "layer_kinds"):
        return [fam.block_costs(conf)] * depth
    kinds = tuple(fam.layer_kinds(conf))
    if len(kinds) != depth:
        raise ValueError(f"{fam.__name__}: {len(kinds)} kinds for "
                         f"{depth} layers")
    by_kind = {k: fam.block_costs(conf, k) for k in dict.fromkeys(kinds)}
    return [by_kind[k] for k in kinds]


def over_layers(conf: dict, field: str) -> int:
    return sum(getattr(c, field) for c in layer_costs(conf))


def head_params(conf: dict) -> int:
    return conf["hidden_size"] * conf["vocab_size"]


def total_params(conf: dict) -> int:
    """Every parameter the model holds; a tied table once."""
    tables = 1 if conf.get("tie_word_embeddings") else 2
    return (over_layers(conf, "resident_params")
            + over_layers(conf, "vector_params")
            + tables * head_params(conf) + conf["hidden_size"])


def train_flops_per_token(conf: dict, seq: int) -> float:
    """Forward + backward, recompute not counted: 6 x (the block
    matrices a token multiplies + head) for the products, causal
    attention's QK^T and PV in the layers that attend: forward
    2 * 2 * S/2 * heads * head_dim a layer a token, times 3 with the
    backward, and 3 x what a layer's scan costs a token forward."""
    return 6.0 * (over_layers(conf, "matmul_params") + head_params(conf)) \
        + 6.0 * seq * over_layers(conf, "attn_width") \
        + 3.0 * over_layers(conf, "scan_flops")


def prefill_flops(conf: dict, prompt_lens) -> float:
    """Forward over whole prompts: 2 x the block matrices a token
    multiplies and a layer's scan, causal attention 2 * S^2 * heads *
    head_dim a layer that attends a sequence (QK^T and PV, half
    masked), and the head once a sequence.  Pads are not counted."""
    toks = sum(prompt_lens)
    attn = sum(2.0 * s * s for s in prompt_lens)
    return (2.0 * over_layers(conf, "matmul_params")
            + over_layers(conf, "scan_flops")) * toks \
        + over_layers(conf, "attn_width") * attn \
        + 2.0 * head_params(conf) * len(prompt_lens)


def weight_bytes_per_chip(conf: dict, chips: int = 1,
                          itemsize: int = 2) -> float:
    """Bytes of weights one decode step reads on ONE chip: every block
    matrix that is resident (a batch of tokens may reach every expert)
    and the head (its share under tensor parallelism), and not the
    embedding table, of which a step reads one row a sequence."""
    return (over_layers(conf, "resident_params")
            + head_params(conf)) * itemsize / chips


def kv_bytes_per_token(conf: dict, chips: int = 1,
                       itemsize: int = 2) -> float:
    return over_layers(conf, "kv_values") * itemsize / chips


def decode_step_bytes(conf: dict, resident_tokens: float,
                      chips: int = 1) -> float:
    """Least bytes one decode step moves on one chip: the weights once
    and the keys and values of every resident token once."""
    return weight_bytes_per_chip(conf, chips) \
        + resident_tokens * kv_bytes_per_token(conf, chips)
