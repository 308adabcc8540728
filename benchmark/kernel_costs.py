"""Operations and bytes that the ALGORITHM needs, from shapes and live
token counts — never from padded shapes, never from the program's own
cost models.  ``conf`` is a configuration file's dict.

What ONE BLOCK costs is the family's to say (``block_costs(conf)`` of
``benchmark/models/<family>.py``, a :class:`BlockCosts`); the trunk
around the blocks (depth, embedding, head) and the arithmetic from
those numbers to FLOPs and bytes are here, the same for every
architecture."""

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
    attn_width: int         # heads x head_dim: QK^T and PV cost 2 x 2 x this a key a query
    kv_values: int          # values a token leaves in the cache


def block_costs(conf: dict) -> BlockCosts:
    return models.family(conf).block_costs(conf)


def head_params(conf: dict) -> int:
    return conf["hidden_size"] * conf["vocab_size"]


def total_params(conf: dict) -> int:
    h = conf["hidden_size"]
    L = conf["num_hidden_layers"]
    blk = block_costs(conf)
    return (L * (blk.resident_params + blk.vector_params)
            + 2 * head_params(conf) + h)


def train_flops_per_token(conf: dict, seq: int) -> float:
    """Forward + backward, recompute not counted: 6 x (the block
    matrices a token multiplies + head) for the products, and causal
    attention's QK^T and PV: forward 2 * 2 * S/2 * heads * head_dim a
    layer a token, times 3 with the backward."""
    L, blk = conf["num_hidden_layers"], block_costs(conf)
    return 6.0 * (L * blk.matmul_params + head_params(conf)) \
        + 6.0 * L * seq * blk.attn_width


def prefill_flops(conf: dict, prompt_lens) -> float:
    """Forward over whole prompts: 2 x the block matrices a token
    multiplies, causal attention 2 * S^2 * heads * head_dim a layer a
    sequence (QK^T and PV, half masked), and the head once a sequence.
    Pads are not counted."""
    L, blk = conf["num_hidden_layers"], block_costs(conf)
    toks = sum(prompt_lens)
    attn = sum(2.0 * s * s * blk.attn_width for s in prompt_lens)
    return 2.0 * L * blk.matmul_params * toks + L * attn \
        + 2.0 * head_params(conf) * len(prompt_lens)


def weight_bytes_per_chip(conf: dict, chips: int = 1,
                          itemsize: int = 2) -> float:
    """Bytes of weights one decode step reads on ONE chip: every block
    matrix that is resident (a batch of tokens may reach every expert)
    and the head (its share under tensor parallelism), and not the
    embedding table, of which a step reads one row a sequence."""
    L = conf["num_hidden_layers"]
    return (L * block_costs(conf).resident_params
            + head_params(conf)) * itemsize / chips


def kv_bytes_per_token(conf: dict, chips: int = 1,
                       itemsize: int = 2) -> float:
    return (conf["num_hidden_layers"] * block_costs(conf).kv_values
            * itemsize) / chips


def decode_step_bytes(conf: dict, resident_tokens: float,
                      chips: int = 1) -> float:
    """Least bytes one decode step moves on one chip: the weights once
    and the keys and values of every resident token once."""
    return weight_bytes_per_chip(conf, chips) \
        + resident_tokens * kv_bytes_per_token(conf, chips)
