"""Family plug-in ``xing_mhc_moe`` (``model_type: xing4_0``): latent
attention (MLA, YaRN) before a dense SwiGLU MLP (``mla_dense``, the
leading layers) or before sigmoid-routed experts beside shared ones
(``mla_moe``), on a residual of ``hc_mult`` streams, an untied head.
The program is the normal path: ``llama_pretrain.make_train_step`` over
``models/hybrid_trunk.py`` (``ops/moe.py``, ``flash_attention_split``),
which ``build_cfg`` reaches through the published keys.  The plain
reference is ``xing_mhc_moe_reference.py``; the contract,
``benchmark/models/__init__.py``.

THE SHARE.  A configuration of this family is one chip's share of a
deployment in which several chips share every layer: its
``n_routed_experts`` is the number of experts HELD here (``expert_first
.. + n_routed_experts - 1``), its ``vocab_size`` the slice of the
vocabulary held here; ``published`` keeps the model's own counts, and
the router stays ``published.n_routed_experts`` wide.

Weights from the seed (the configuration file lists this under
``assumed``): a matrix normal at 1/sqrt(the width it contracts) — the
tables at 1/sqrt(hidden) — norms ones, the mixers' ``alpha`` ones and
``b`` zeros: ``hybrid_trunk.init_leaf``, one leaf at a time from a key
folded by the leaf's place in the tree.
"""

from __future__ import annotations

from ..kernel_costs import BlockCosts
from . import tree_of
from .llama_block import seed_key       # noqa: F401  (the same rule)

# names this family's program adds to the base vocabulary
SCOPES = ("hc_pre", "hc_post", "mla_q", "mla_kv", "moe_route",
          "moe_dispatch", "moe_experts", "moe_combine", "moe_shared")
KERNELS = ("grouped_mm", "grouped_mm_dw")
TOP_LEAVES = ("embed", "final_norm", "lm_head")
MOE_SCOPES = SCOPES[4:]
HC_SCOPES = SCOPES[:2]


def layer_kinds(conf: dict):
    dense = min(conf["first_k_dense_replace"], conf["num_hidden_layers"])
    return ("mla_dense",) * dense + ("mla_moe",) * (
        conf["num_hidden_layers"] - dense)


def attention_params(conf: dict) -> int:
    """MLA's five matrices (q_b and kv_b whole)."""
    c, heads = conf["hidden_size"], conf["num_attention_heads"]
    qk = conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
    return (c * conf["q_lora_rank"] + conf["q_lora_rank"] * heads * qk
            + c * (conf["kv_lora_rank"] + conf["qk_rope_head_dim"])
            + conf["kv_lora_rank"] * heads
            * (conf["qk_nope_head_dim"] + conf["v_head_dim"])
            + heads * conf["v_head_dim"] * c)


def expert_params(conf: dict) -> int:
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def mixer_maps(conf: dict) -> int:
    n = conf["hc_mult"]
    return n * n + 2 * n


def expected_pairs_per_token(conf: dict) -> float:
    """(token, pick) pairs a token sends to the experts held here, if the
    router spreads its picks evenly over the published experts."""
    return conf["num_experts_per_tok"] * conf["n_routed_experts"] \
        / conf["published"]["n_routed_experts"]


def expert_flops_per_token(conf: dict) -> float:
    """FLOPs a token costs in the routed experts' grouped products,
    forward + backward, all expert layers (recompute not counted): three
    products forward and six backward, 2 x hidden x expert width each, for
    the EXPECTED pairs a token sends to the experts held here."""
    return 9 * 2.0 * conf["hidden_size"] * conf["moe_intermediate_size"] \
        * expected_pairs_per_token(conf) \
        * layer_kinds(conf).count("mla_moe")


def mixer_bytes_per_token(conf: dict, itemsize: int = 2) -> int:
    """Least HBM bytes a token's residual mixers move in ONE layer,
    forward + backward (recompute not counted), the n streams of C
    values in the compute type.  A sublayer forward reads the streams
    once (a pass that keeps them serves the maps, ``H_pre . X`` and
    ``H_res . X``), writes h, reads y and writes the new streams: 2 n +
    2 stream-widths; backward it reads the streams and the two incoming
    gradients (n + 1) and writes two (n + 1): 3 n + 2.  Two sublayers a
    layer.  The maps themselves (n^2 + 2 n numbers a token) are not
    counted."""
    n, c = conf["hc_mult"], conf["hidden_size"]
    return 2 * ((2 * n + 2) + (3 * n + 2)) * c * itemsize


def block_costs(conf: dict, kind: str) -> BlockCosts:
    c = conf["hidden_size"]
    attn = attention_params(conf)
    mixers = 2 * conf["hc_mult"] * c * mixer_maps(conf)
    vecs = 2 * c + conf["q_lora_rank"] + conf["kv_lora_rank"] \
        + 2 * (3 + mixer_maps(conf))
    width = conf["num_attention_heads"] * (
        conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
        + conf["v_head_dim"]) // 2
    cache = conf["kv_lora_rank"] + conf["qk_rope_head_dim"]
    if kind == "mla_dense":
        mats = attn + mixers + 3 * c * conf["intermediate_size"]
        return BlockCosts(matmul_params=mats, resident_params=mats,
                          vector_params=vecs, attn_width=width,
                          kv_values=cache)
    outside = attn + mixers + c * conf["published"]["n_routed_experts"] \
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
    if conf["n_group"] != 1 or conf["topk_group"] != 1 or \
            conf["scoring_func"] != "sigmoid" or not conf["norm_topk_prob"] \
            or conf["moe_layer_freq"] != 1 or conf["tie_word_embeddings"] \
            or conf.get("num_nextn_predict_layers"):
        raise ValueError(
            "xing_mhc_moe: one routing group, sigmoid scores normalised "
            "over the picks, an expert layer every layer after the "
            "leading dense ones, an untied head and no multi-token "
            "prediction are what it states")
    job = job or {}
    return LlamaPretrainConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        intermediate_size=conf["intermediate_size"],
        num_hidden_layers=conf["num_hidden_layers"],
        num_attention_heads=conf["num_attention_heads"],
        num_key_value_heads=conf["num_key_value_heads"],
        max_seq_len=job.get("seq", 2048),
        rope_theta=float(conf["rope_theta"]),
        rope_scaling=conf["rope_scaling"],
        rms_norm_eps=float(conf["rms_norm_eps"]),
        q_lora_rank=conf["q_lora_rank"], kv_lora_rank=conf["kv_lora_rank"],
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
        hc_mult=conf["hc_mult"],
        hc_sinkhorn_iters=conf["hc_sinkhorn_iters"],
        hc_eps=float(conf["hc_eps"]),
        mhc_h_res_clamp_min=float(conf["mhc_h_res_clamp_min"]),
        mhc_h_res_clamp_max=float(conf["mhc_h_res_clamp_max"]),
        use_pallas_attention=True, sequence_parallel=False,
        remat=train, remat_policy=job.get("remat_policy", "full"),
        dtype=jnp.bfloat16,
        param_dtype=jnp.float32 if train else jnp.bfloat16,
        loss_chunks=job.get("loss_chunks", 0) if train else 0)


def leaf_shapes(cfg) -> dict:
    from paddle_tpu.models import hybrid_trunk
    out = {("blocks", kind, nm): (hybrid_trunk.layers_of(cfg, kind),) + shape
           for kind in dict.fromkeys(cfg.layer_types)
           for nm, shape in hybrid_trunk.kind_shapes(cfg, kind).items()}
    out[("embed",)] = (cfg.vocab_size, cfg.hidden_size)
    out[("final_norm",)] = (cfg.hidden_size,)
    out[("lm_head",)] = (cfg.hidden_size, cfg.vocab_size)
    return out


def make_leaf(cfg, key, path, dtype=None):
    """One leaf from the run's key.  Traceable, and the same values
    whether called alone or inside :func:`make_params`."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import hybrid_trunk
    shapes = leaf_shapes(cfg)
    dtype = dtype or cfg.param_dtype
    k = jax.random.fold_in(key, list(shapes).index(path))
    if path == ("final_norm",):
        return jnp.ones(shapes[path], dtype)
    if len(path) == 1:
        return (jax.random.normal(k, shapes[path], jnp.float32)
                / cfg.hidden_size ** 0.5).astype(dtype)
    _, kind, name = path
    return hybrid_trunk.init_leaf(cfg, k, kind, name, shapes[path][0], dtype)


def make_params(cfg, seed: int, mesh):
    """The whole tree in one jitted call, laid out by the program's own
    ``param_specs``."""
    import jax
    from jax.sharding import NamedSharding
    from paddle_tpu.models.llama_pretrain import param_specs
    shard = jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp), param_specs(cfg, 1),
        is_leaf=lambda x: not isinstance(x, dict))
    return jax.jit(lambda k: tree_of(leaf_shapes(cfg),
                                     lambda p: make_leaf(cfg, k, p)),
                   out_shardings=shard)(seed_key(seed))
