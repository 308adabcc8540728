"""Family plug-in ``solar_kda_moe`` (``model_type: solar_open2``,
upstage's Solar Open 2): layers of two kinds in one trunk — ``kda_moe``
(Kimi Delta Attention: a delta rule whose decay is a vector a head,
behind a depthwise causal convolution) and, on the layers of the
published ``gqa_layers``, ``gqa_gated_moe`` (softmax grouped-query
attention without rotation whose output is gated) — every layer before
sigmoid-routed SwiGLU experts beside a shared one; the default ends
(``embed``, ``final_norm``, ``lm_head``: untied).  The program is the
normal path: ``llama_pretrain.make_train_step`` over
``models/hybrid_trunk.py`` (``ops/kda.py`` over the ``kda_chunk_*``
kernels, ``ops/pallas/causal_conv.py``, ``ops/moe.py``), which
``build_cfg`` reaches through the published keys.  The plain reference
is ``solar_kda_moe_reference.py``; the contract,
``benchmark/models/__init__.py``.

THE SHARE.  A configuration of this family is one chip's share of a
deployment in which several chips share every layer: its
``n_routed_experts`` is the number of experts HELD here (``expert_first
.. + n_routed_experts - 1``), its ``vocab_size`` the slice of the
vocabulary held here; ``published`` keeps the model's own counts, and
the router stays ``published.n_routed_experts`` wide.  The shared expert
is whole on every chip.

THE RECURRENCE'S WORK (``kda_chunk_flops_per_token``,
``kda_chunk_bytes_per_token``): what the chunked delta rule NEEDS at the
program's chunk of Q = 64 positions, a head of K keys x K values — the
pair sums A and P over ``j <= i`` (2 Q K each a position), T applied to
``[Q, 2 K]`` as a triangular solve (2 Q K), P N (Q K) and the three
products with the ``[K, K]`` state (2 K^2 each): ``5 Q K + 6 K^2`` FLOPs
a position a head forward, counted three times with the backward like
the products; and the bytes of q, k, v and o in the compute type, the
log decay in fp32 and beta, forward, and q, k, v, do read, dq, dk, dv
written, the decay read and its gradient written, beta's too, backward.
At the entered configuration (64 heads of 128, bf16): 8.91 MFLOP and
279,296 B a token a layer — 136 ns at the bf16 peak against 341 ns at
819 GB/s: MEMORY binds.  ``block_costs`` states the same FLOPs as
``scan_flops``.

Weights from the seed (the configuration file lists this under
``assumed``): a matrix normal at 1/sqrt(the width it contracts) — the
head at 1/sqrt(hidden) —, the convolution's taps normal at
1/sqrt(taps), norms ones, ``A_log = log U[1, 16]`` a head, ``dt_bias``
the inverse softplus of a step log-uniform in [1e-3, 1e-1] a channel,
``gate_b`` zeros: ``hybrid_trunk.init_leaf``, one leaf at a time from a
key folded by the leaf's place in the tree.  THE EMBEDDING ROWS are
normal at std ``EMBED_STD`` = 2 (``smallthinker_moe``'s rule and
reason): the first layer is attention WITHOUT rotation, whose output is
a running mean of values; at rows of 1/sqrt(hidden) a component COMMON
to all tokens is 43 % of the first router's normed input and 35 % of the
fourth's, the picks pile onto a few of the 320 experts (one takes 271
rows where 26 are expected, eight none, a held expert three: the
reference's forward at 1,024 tokens on the CPU), an expert left with a
few rows has zero-gradient rows, adafactor's factored second moment
underflows there (r x vc = 9e-41 under eps1 = 1e-30: 0 x inf) and the
step after the first is NaN — the cell's first chip run; at std 2 the
common share is 3–20 %, no expert is empty and a held expert gets 11–40
rows of the 26 (PERF.md section 6, PR 52).
"""

from __future__ import annotations

from ..kernel_costs import BlockCosts
# the tree by kind with the three default top leaves, THE EMBEDDING ROWS
# at std ``EMBED_STD`` = 2 and the head at 1/sqrt(hidden): the same
# functions, for the same reason (the docstring above)
from .smallthinker_moe import (EMBED_STD, leaf_shapes,  # noqa: F401
                               make_leaf, make_params, seed_key)

KDA_CHUNK = 64      # the program's: paddle_tpu.ops.kda.CHUNK
# names this family's program adds to the base vocabulary
KDA_SCOPES = ("kda_in_proj", "kda_conv", "kda_gates", "kda_chunk",
              "kda_out_gate", "kda_out_proj")
MOE_SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine",
              "moe_shared")
SCOPES = KDA_SCOPES + ("attn_gate",) + MOE_SCOPES
KDA_KERNELS = ("kda_chunk_fwd", "kda_chunk_bwd")
KERNELS = KDA_KERNELS + ("causal_conv_fwd", "causal_conv_bwd", "grouped_mm",
                         "grouped_mm_dw", "moe_sum_pairs")


def layer_kinds(conf: dict):
    return tuple("gqa_gated_moe" if i in conf["gqa_layers"] else "kda_moe"
                 for i in range(conf["num_hidden_layers"]))


def kda_dims(conf: dict):
    """(heads, a head's keys = values, heads x that)."""
    kda = conf["linear_attn_config"]
    return kda["num_heads"], kda["head_dim"], \
        kda["num_heads"] * kda["head_dim"]


def kda_params(conf: dict) -> int:
    """The mixer's matrices: ``w_qkv``, the decay's and the gate's
    low-rank pairs, ``w_beta``, ``wo``."""
    c = conf["hidden_size"]
    heads, d, wide = kda_dims(conf)
    return 3 * c * wide + 2 * (c * d + d * wide) + c * heads + wide * c


def kda_vectors(conf: dict) -> int:
    heads, d, wide = kda_dims(conf)
    taps = conf["linear_attn_config"]["short_conv_kernel_size"]
    return 3 * wide * taps + heads + 2 * wide + d


def attention_params(conf: dict) -> int:
    """``wq``, ``wg``, ``wo`` and ``wk``, ``wv``."""
    c, d = conf["hidden_size"], conf["head_dim"]
    return c * d * (3 * conf["num_attention_heads"]
                    + 2 * conf["num_key_value_heads"])


def expert_params(conf: dict) -> int:
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def expected_pairs_per_token(conf: dict) -> float:
    """(token, pick) pairs a token sends to the experts held here, if the
    router spreads its picks evenly over the published experts."""
    return conf["num_experts_per_tok"] * conf["n_routed_experts"] \
        / conf["published"]["n_routed_experts"]


def expert_flops_per_token(conf: dict) -> float:
    """FLOPs a token costs in the routed experts' grouped products,
    forward + backward, all layers (recompute not counted): three
    products forward and six backward, 2 x hidden x expert width each, for
    the EXPECTED pairs a token sends to the experts held here."""
    return 9 * 2.0 * conf["hidden_size"] * conf["moe_intermediate_size"] \
        * expected_pairs_per_token(conf) * conf["num_hidden_layers"]


def kda_layers(conf: dict) -> int:
    return layer_kinds(conf).count("kda_moe")


def kda_chunk_flops_per_token(conf: dict, chunk: int = KDA_CHUNK) -> int:
    """FLOPs a token of ONE delta-rule layer costs FORWARD in the chunked
    recurrence (the module docstring has the count)."""
    heads, d, _ = kda_dims(conf)
    return heads * (5 * chunk * d + 6 * d * d)


def kda_chunk_bytes_per_token(conf: dict, itemsize: int = 2) -> int:
    """Least HBM bytes a token of ONE delta-rule layer moves through the
    recurrence, forward + backward (recompute not counted; the module
    docstring has the count)."""
    heads, _, wide = kda_dims(conf)
    forward = 4 * wide * itemsize + 4 * wide + 4 * heads
    backward = 7 * wide * itemsize + 2 * 4 * wide + 2 * 4 * heads
    return forward + backward


def block_costs(conf: dict, kind: str) -> BlockCosts:
    c = conf["hidden_size"]
    published = conf["published"]["n_routed_experts"]
    if kind == "gqa_gated_moe":
        op, vecs = attention_params(conf), 2 * c
        attends = dict(attn_width=conf["num_attention_heads"]
                       * conf["head_dim"],
                       kv_values=2 * conf["num_key_value_heads"]
                       * conf["head_dim"])
    else:
        op, vecs = kda_params(conf), 2 * c + kda_vectors(conf)
        attends = dict(attn_width=0, kv_values=0,
                       scan_flops=kda_chunk_flops_per_token(conf))
    outside = op + c * published \
        + conf["n_shared_experts"] * expert_params(conf)
    return BlockCosts(
        matmul_params=outside + round(expected_pairs_per_token(conf)
                                      * expert_params(conf)),
        resident_params=outside + conf["n_routed_experts"]
        * expert_params(conf),
        vector_params=vecs, **attends)


def build_cfg(conf: dict, train: bool, job: dict | None = None):
    """The program's config object from the published keys and the
    share."""
    import jax.numpy as jnp
    from paddle_tpu.models.llama_pretrain import LlamaPretrainConfig
    kda = conf["linear_attn_config"]
    if conf["first_k_dense_replace"] or not conf["norm_topk_prob"] \
            or conf["tie_word_embeddings"] or kda["num_kv_heads"] \
            or conf["partial_rotary_factor"] != 1 \
            or not conf["use_gqa_gate"] or conf["kda_use_full_proj"] \
            or not conf["kda_allow_neg_eigval"]:
        raise ValueError(
            "solar_kda_moe: every layer before an expert layer, gates "
            "normalised over the picks, an untied head, as many "
            "delta-rule key heads as value heads, a gate on attention's "
            "output, low-rank gate maps and beta = 2 sigmoid are what it "
            "states")
    job = job or {}
    return LlamaPretrainConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        intermediate_size=conf["intermediate_size"],
        num_hidden_layers=conf["num_hidden_layers"],
        num_attention_heads=conf["num_attention_heads"],
        num_key_value_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"], max_seq_len=job.get("seq", 2048),
        rope_theta=float(conf["rope_theta"]),
        position_embedding_type="rope" if conf["use_rope"] else "nope",
        rms_norm_eps=float(conf["rms_norm_eps"]),
        gqa_layers=tuple(conf["gqa_layers"]),
        kda_num_heads=kda["num_heads"], kda_head_dim=kda["head_dim"],
        short_conv_kernel_size=kda["short_conv_kernel_size"],
        moe_intermediate_size=conf["moe_intermediate_size"],
        n_routed_experts=conf["published"]["n_routed_experts"],
        n_shared_experts=conf["n_shared_experts"],
        experts_held=conf["n_routed_experts"],
        expert_first=conf["expert_first"],
        num_experts_per_tok=conf["num_experts_per_tok"],
        routed_scaling_factor=float(conf["routed_scaling_factor"]),
        use_pallas_attention=True, sequence_parallel=False,
        remat=train, remat_policy=job.get("remat_policy", "full"),
        dtype=jnp.bfloat16,
        param_dtype=jnp.float32 if train else jnp.bfloat16,
        loss_chunks=job.get("loss_chunks", 0) if train else 0)
