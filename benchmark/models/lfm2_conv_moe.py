"""Family plug-in ``lfm2_conv_moe`` (``model_type: lfm2_moe``, LiquidAI's
LFM2 expert models): layers of three kinds in one trunk — ``conv_dense``
(a gated short convolution before a dense SwiGLU MLP: the leading
layers), ``conv_moe`` (the same operator before an expert layer) and
``gqa_qknorm_moe`` (grouped-query attention whose q and k heads are
normed before the rotation, before an expert layer) — by the published
``layer_types`` and ``num_dense_layers``; the router takes the top k of
its sigmoid scores PLUS a held bias and gates by the scores alone; one
TIED table.  The program is the normal path:
``llama_pretrain.make_train_step`` over ``models/hybrid_trunk.py``
(``ops/pallas/causal_conv.py``'s gated form, ``ops/moe.py``'s third
rule), which ``build_cfg`` reaches through the published keys.  The
plain reference is ``lfm2_conv_moe_reference.py``; the contract,
``benchmark/models/__init__.py``.

THE SHARE.  A configuration of this family is one chip's share of a
deployment in which several chips share every layer: its ``num_experts``
is the number of experts HELD here (``expert_first .. + num_experts -
1``), its ``vocab_size`` the slice of the vocabulary held here;
``published`` keeps the model's own counts, and the router (and its
bias) stays ``published.num_experts`` wide.

Weights from the seed (the configuration file lists this under
``assumed``): a matrix normal at 1/sqrt(the width it contracts), the
tied table at 1/sqrt(hidden), the convolution's taps normal at
1/sqrt(conv_L_cache), norms ones, ``expert_bias`` normal at
``hybrid_trunk.EXPERT_BIAS_STD`` — a LEAF of the expert kinds' stacks
that reads no gradient (``stop_gradient``: adafactor's update of it is
0; the job's weight decay shrinks it like any leaf, ~2e-5 of itself a
step, in the program and in the reference alike) —,
``hybrid_trunk.init_leaf``, one leaf at a time from a key folded by the
leaf's place in the tree.
"""

from __future__ import annotations

from ..kernel_costs import BlockCosts
# a tied table: the tree by kind with ``embed`` and ``final_norm`` alone
# at the top, the table at 1/sqrt(hidden) — the same functions
from .granite_hybrid import (leaf_shapes, make_leaf,    # noqa: F401
                             make_params, seed_key)

# names this family's program adds to the base vocabulary
CONV_SCOPES = ("conv_in_proj", "short_conv", "conv_out_proj")
MOE_SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine")
SCOPES = CONV_SCOPES + ("qk_norm",) + MOE_SCOPES
CONV_KERNELS = ("short_conv_fwd", "short_conv_bwd")
KERNELS = CONV_KERNELS + ("grouped_mm", "grouped_mm_dw", "moe_sum_pairs")
TOP_LEAVES = ("embed", "final_norm")


def layer_kinds(conf: dict):
    dense = conf["num_dense_layers"]
    names = {"conv": "conv_moe", "full_attention": "gqa_qknorm_moe"}
    return tuple("conv_dense" if i < dense and t == "conv" else names[t]
                 for i, t in enumerate(
                     conf["layer_types"][:conf["num_hidden_layers"]]))


def head_dim(conf: dict) -> int:
    return conf["hidden_size"] // conf["num_attention_heads"]


def conv_params(conf: dict) -> int:
    """The operator's two projections: C x 3 C and C x C."""
    return 4 * conf["hidden_size"] ** 2


def attention_params(conf: dict) -> int:
    return 2 * conf["hidden_size"] * head_dim(conf) * (
        conf["num_attention_heads"] + conf["num_key_value_heads"])


def expert_params(conf: dict) -> int:
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def expected_pairs_per_token(conf: dict) -> float:
    """(token, pick) pairs a token sends to the experts held here, if the
    router spreads its picks evenly over the published experts."""
    return conf["num_experts_per_tok"] * conf["num_experts"] \
        / conf["published"]["num_experts"]


def expert_flops_per_token(conf: dict) -> float:
    """FLOPs a token costs in the routed experts' grouped products,
    forward + backward, all expert layers (recompute not counted): three
    products forward and six backward, 2 x hidden x expert width each, for
    the EXPECTED pairs a token sends to the experts held here."""
    kinds = layer_kinds(conf)
    return 9 * 2.0 * conf["hidden_size"] * conf["moe_intermediate_size"] \
        * expected_pairs_per_token(conf) \
        * (len(kinds) - kinds.count("conv_dense"))


def conv_layers(conf: dict) -> int:
    kinds = layer_kinds(conf)
    return kinds.count("conv_dense") + kinds.count("conv_moe")


def short_conv_bytes_per_token(conf: dict, itemsize: int = 2) -> int:
    """Least HBM bytes a token of ONE convolution layer the kernel pair
    ``short_conv_fwd`` / ``short_conv_bwd`` moves, forward + backward
    (recompute not counted), in the compute type: B, Cg, X read and the
    result written (4 C), then B, Cg, X and the result's gradient read
    and dB, dCg, dX written (7 C).  The taps and their gradient (K
    numbers a channel) are not counted.  Bound: memory — the pass does
    2 K + 1 operations a value forward."""
    return (4 + 7) * conf["hidden_size"] * itemsize


def block_costs(conf: dict, kind: str) -> BlockCosts:
    c, taps = conf["hidden_size"], conf["conv_L_cache"]
    if kind == "gqa_qknorm_moe":
        op, vecs = attention_params(conf), 2 * c + 2 * head_dim(conf)
        attends = dict(attn_width=conf["num_attention_heads"]
                       * head_dim(conf),
                       kv_values=2 * conf["num_key_value_heads"]
                       * head_dim(conf))
    else:
        # B X, K taps and the gate: operations of no matrix parameter
        op, vecs = conv_params(conf), 2 * c + c * taps
        attends = dict(attn_width=0, kv_values=0,
                       scan_flops=(2 * taps + 1) * c)
    if kind == "conv_dense":
        mats = op + 3 * c * conf["intermediate_size"]
        return BlockCosts(matmul_params=mats, resident_params=mats,
                          vector_params=vecs, **attends)
    published = conf["published"]["num_experts"]
    outside = op + c * published
    return BlockCosts(
        matmul_params=outside + round(expected_pairs_per_token(conf)
                                      * expert_params(conf)),
        resident_params=outside + conf["num_experts"] * expert_params(conf),
        vector_params=vecs + published, **attends)


def build_cfg(conf: dict, train: bool, job: dict | None = None):
    """The program's config object from the published keys and the
    share."""
    import jax.numpy as jnp
    from paddle_tpu.models.llama_pretrain import LlamaPretrainConfig
    rope = conf["rope_parameters"]
    if conf["conv_bias"] or not conf["norm_topk_prob"] \
            or not conf["use_expert_bias"] \
            or not conf["tie_word_embeddings"] \
            or rope["rope_type"] != "default":
        raise ValueError(
            "lfm2_conv_moe: a convolution without a bias, gates "
            "normalised over the picks, a router with its bias, one tied "
            "table and plain rotation are what it states")
    job = job or {}
    return LlamaPretrainConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        intermediate_size=conf["intermediate_size"],
        num_hidden_layers=conf["num_hidden_layers"],
        num_attention_heads=conf["num_attention_heads"],
        num_key_value_heads=conf["num_key_value_heads"],
        max_seq_len=job.get("seq", 2048),
        rope_theta=float(rope["rope_theta"]),
        rms_norm_eps=float(conf["norm_eps"]),
        layer_types=tuple(conf["layer_types"]),
        num_dense_layers=conf["num_dense_layers"],
        conv_L_cache=conf["conv_L_cache"],
        use_expert_bias=conf["use_expert_bias"],
        moe_intermediate_size=conf["moe_intermediate_size"],
        n_routed_experts=conf["published"]["num_experts"],
        experts_held=conf["num_experts"],
        expert_first=conf["expert_first"],
        num_experts_per_tok=conf["num_experts_per_tok"],
        routed_scaling_factor=float(conf["routed_scaling_factor"]),
        tie_word_embeddings=True,
        use_pallas_attention=True, sequence_parallel=False,
        remat=train, remat_policy=job.get("remat_policy", "full"),
        dtype=jnp.bfloat16,
        param_dtype=jnp.float32 if train else jnp.bfloat16,
        loss_chunks=job.get("loss_chunks", 0) if train else 0)
