"""Family plug-in ``kanana_mla_moe`` (``model_type: deepseek_v3`` as
kakaocorp's Kanana-2 30B-A3B states it): latent attention (MLA) whose
QUERY has no latent — a direct projection, no ``q_norm`` — with plain
rotation on the rope dims, before a dense SwiGLU MLP (``mla_dense``, the
leading layers) or before sigmoid-routed experts beside shared ones
(``mla_moe``), on ONE residual stream, an untied head.  The program is
the normal path: ``llama_pretrain.make_train_step`` over
``models/hybrid_trunk.py`` (``hc_mult`` 1 and ``q_lora_rank`` 0 are what
select the plain block and the direct query; ``ops/moe.py``,
``flash_attention_split``), which ``build_cfg`` reaches through the
published keys.  The plain reference is ``kanana_mla_moe_reference.py``;
the contract, ``benchmark/models/__init__.py``.

THE SHARE.  A configuration of this family is one chip's share of a
deployment in which several chips share every layer: its
``n_routed_experts`` is the number of experts HELD here (``expert_first
.. + n_routed_experts - 1``), its ``vocab_size`` the slice of the
vocabulary held here; ``published`` keeps the model's own counts, and
the router stays ``published.n_routed_experts`` wide.  Attention and the
shared experts are whole on every chip.

Weights from the seed (the configuration file lists this under
``assumed``): a matrix normal at 1/sqrt(the width it contracts) — the
head at 1/sqrt(hidden) — norms ones: ``hybrid_trunk.init_leaf``, one
leaf at a time from a key folded by the leaf's place in the tree.  THE
EMBEDDING ROWS are normal at std ``EMBED_STD`` = 2 (``smallthinker_moe``'s
rule and reason): that the first layer ROTATES does not spare it.  At
rows of 1/sqrt(hidden) the blocks' outputs (rms 0.6 after the dense
lead) outgrow a token's own row (0.022) at once, attention's running
mean of values is a component COMMON to all tokens — 43 % of the first
router's normed input, 47 % of the third's — and the picks pile up: of
1,024 tokens' 6,144 picks one expert takes 314 where 48 are expected,
a held expert 1 to 3, one of the 128 none (the reference's forward at
1,024 tokens, real widths, on the CPU: PERF.md section 6, PR 56); an
expert left with a few rows has near-zero gradient rows, where
adafactor's factored second moment gave PRs 44 and 52 their NaN.  At
std 2 the common share is 5-7 %, every expert is picked 23 to 74 times
and a held one 29 to 70.
"""

from __future__ import annotations

from ..kernel_costs import BlockCosts
# the tree by kind with the three default top leaves, THE EMBEDDING ROWS
# at std ``EMBED_STD`` = 2 and the head at 1/sqrt(hidden): the same
# functions, for the same reason (the docstring above)
from .smallthinker_moe import (EMBED_STD, leaf_shapes,  # noqa: F401
                               make_leaf, make_params, seed_key)
# a dense lead then expert layers, an expert's parameters, the EXPECTED
# pairs a token sends to the experts held here and the grouped products'
# FLOPs for them: the same published keys, the same functions
from .xing_mhc_moe import (expected_pairs_per_token,  # noqa: F401
                           expert_flops_per_token, expert_params,
                           layer_kinds)

# names this family's program adds to the base vocabulary
MLA_SCOPES = ("mla_q", "mla_kv")
MOE_SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine",
              "moe_shared")
SCOPES = MLA_SCOPES + MOE_SCOPES
KERNELS = ("grouped_mm", "grouped_mm_dw", "moe_sum_pairs")


def attention_params(conf: dict) -> int:
    """MLA's four matrices: the direct query, kv_a, kv_b whole, wo."""
    c, heads = conf["hidden_size"], conf["num_attention_heads"]
    qk = conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
    return (c * heads * qk
            + c * (conf["kv_lora_rank"] + conf["qk_rope_head_dim"])
            + conf["kv_lora_rank"] * heads
            * (conf["qk_nope_head_dim"] + conf["v_head_dim"])
            + heads * conf["v_head_dim"] * c)


def block_costs(conf: dict, kind: str) -> BlockCosts:
    c = conf["hidden_size"]
    attn = attention_params(conf)
    vecs = 2 * c + conf["kv_lora_rank"]
    # 192-wide scores, 128-wide values: the mean of the two products
    width = conf["num_attention_heads"] * (
        conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
        + conf["v_head_dim"]) // 2
    cache = conf["kv_lora_rank"] + conf["qk_rope_head_dim"]
    if kind == "mla_dense":
        mats = attn + 3 * c * conf["intermediate_size"]
        return BlockCosts(matmul_params=mats, resident_params=mats,
                          vector_params=vecs, attn_width=width,
                          kv_values=cache)
    outside = attn + c * conf["published"]["n_routed_experts"] \
        + conf["n_shared_experts"] * expert_params(conf)
    return BlockCosts(
        matmul_params=outside + round(expected_pairs_per_token(conf)
                                      * expert_params(conf)),
        resident_params=outside + conf["n_routed_experts"]
        * expert_params(conf),
        vector_params=vecs, attn_width=width, kv_values=cache)


def build_cfg(conf: dict, train: bool, job: dict | None = None):
    """The program's config object from the published keys and the
    share."""
    import jax.numpy as jnp
    from paddle_tpu.models.llama_pretrain import LlamaPretrainConfig
    if conf["n_group"] != 1 or conf["topk_group"] != 1 \
            or conf["rope_scaling"] or conf["q_lora_rank"] \
            or conf["tie_word_embeddings"] \
            or conf.get("num_nextn_predict_layers") \
            or conf["scoring_func"] != "sigmoid" \
            or not conf["norm_topk_prob"] or conf["moe_layer_freq"] != 1:
        raise ValueError(
            "kanana_mla_moe: one routing group, plain rotation (no "
            "rope_scaling), a query without a latent (q_lora_rank null), "
            "an untied head, no multi-token prediction, sigmoid scores "
            "normalised over the picks and an expert layer every layer "
            "after the leading dense ones are what it states")
    job = job or {}
    return LlamaPretrainConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        intermediate_size=conf["intermediate_size"],
        num_hidden_layers=conf["num_hidden_layers"],
        num_attention_heads=conf["num_attention_heads"],
        num_key_value_heads=conf["num_key_value_heads"],
        max_seq_len=job.get("seq", 2048),
        rope_theta=float(conf["rope_theta"]),
        rms_norm_eps=float(conf["rms_norm_eps"]),
        q_lora_rank=0, kv_lora_rank=conf["kv_lora_rank"],
        qk_nope_head_dim=conf["qk_nope_head_dim"],
        qk_rope_head_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"],
        first_k_dense_replace=conf["first_k_dense_replace"],
        moe_intermediate_size=conf["moe_intermediate_size"],
        n_routed_experts=conf["published"]["n_routed_experts"],
        experts_held=conf["n_routed_experts"],
        expert_first=conf["expert_first"],
        n_shared_experts=conf["n_shared_experts"],
        num_experts_per_tok=conf["num_experts_per_tok"],
        routed_scaling_factor=float(conf["routed_scaling_factor"]),
        use_pallas_attention=True, sequence_parallel=False,
        remat=train, remat_policy=job.get("remat_policy", "full"),
        dtype=jnp.bfloat16,
        param_dtype=jnp.float32 if train else jnp.bfloat16,
        loss_chunks=job.get("loss_chunks", 0) if train else 0)
