"""Operations and bytes that the ALGORITHM needs, from shapes and live
token counts — never from padded shapes, never from the program's own
cost models.  ``conf`` is a configuration file's dict."""

from __future__ import annotations


def block_params(conf: dict) -> int:
    """Matrix parameters of one block (norm vectors left out: they are
    no matrix product)."""
    h, f = conf["hidden_size"], conf["intermediate_size"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    return 2 * h * h + 2 * h * kv + 3 * h * f


def head_params(conf: dict) -> int:
    return conf["hidden_size"] * conf["vocab_size"]


def total_params(conf: dict) -> int:
    h = conf["hidden_size"]
    L = conf["num_hidden_layers"]
    return (L * (block_params(conf) + 2 * h) + 2 * head_params(conf) + h)


def train_flops_per_token(conf: dict, seq: int) -> float:
    """Forward + backward, recompute not counted: 6 x (block matrices +
    head) for the products, and causal attention's QK^T and PV: forward
    2 * 2 * S/2 * hidden a layer a token, times 3 with the backward."""
    L, h = conf["num_hidden_layers"], conf["hidden_size"]
    return 6.0 * (L * block_params(conf) + head_params(conf)) \
        + 6.0 * L * seq * h


def prefill_flops(conf: dict, prompt_lens) -> float:
    """Forward over whole prompts: 2 x block matrices a token, causal
    attention 2 * S^2 * hidden a layer a sequence (QK^T and PV, half
    masked), and the head once a sequence.  Pads are not counted."""
    L, h = conf["num_hidden_layers"], conf["hidden_size"]
    toks = sum(prompt_lens)
    attn = sum(2.0 * s * s * h for s in prompt_lens)
    return 2.0 * L * block_params(conf) * toks + L * attn \
        + 2.0 * head_params(conf) * len(prompt_lens)


def weight_bytes_per_chip(conf: dict, chips: int = 1,
                          itemsize: int = 2) -> float:
    """Bytes of weights one decode step reads on ONE chip: every block
    matrix and the head (its share under tensor parallelism), and not
    the embedding table, of which a step reads one row a sequence."""
    L = conf["num_hidden_layers"]
    return (L * block_params(conf) + head_params(conf)) * itemsize / chips


def kv_bytes_per_token(conf: dict, chips: int = 1,
                       itemsize: int = 2) -> float:
    return (conf["num_hidden_layers"] * 2 * conf["num_key_value_heads"]
            * conf["head_dim"] * itemsize) / chips


def decode_step_bytes(conf: dict, resident_tokens: float,
                      chips: int = 1) -> float:
    """Least bytes one decode step moves on one chip: the weights once
    and the keys and values of every resident token once."""
    return weight_bytes_per_chip(conf, chips) \
        + resident_tokens * kv_bytes_per_token(conf, chips)
