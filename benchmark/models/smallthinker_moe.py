"""Family plug-in ``smallthinker_moe`` (``model_name:
smallthinker_21b_instruct``): grouped-query attention of two kinds in
one trunk — ``gqa_moe_window`` (rotated, the last ``sliding_window_size``
keys) and ``gqa_moe_global`` (no rotation, every earlier key), by
``sliding_window_layout`` / ``rope_layout`` — before routed ReGLU
experts without a shared one; the router reads the attention's input
and takes a softmax over its picked logits.  The program is the normal
path: ``llama_pretrain.make_train_step`` over ``models/hybrid_trunk.py``
(``ops/moe.py``, ``flash_attention`` dense and windowed), which
``build_cfg`` reaches through the published keys.  The plain reference
is ``smallthinker_moe_reference.py``; the contract,
``benchmark/models/__init__.py``.

THE SHARE.  A configuration of this family is one chip's share of a
deployment in which several chips share every layer: its
``moe_num_primary_experts`` is the number of experts HELD here
(``expert_first .. + moe_num_primary_experts - 1``), its ``vocab_size``
the slice of the vocabulary held here; ``published`` keeps the model's
own counts, and the router stays ``published.moe_num_primary_experts``
wide.

THE WINDOW'S WORK.  ``kernel_costs`` counts attention as ``6 seq
attn_width`` a token — every query sees seq / 2 keys.  A window layer's
query sees ``W - W^2 / 2 seq`` (3,584 of 16,384 at W 4,096), so a window
kind states ``attn_width = 0`` and carries its attention as
``scan_flops``: FLOPs a token costs forward that come from no matrix
parameter and do not grow with the row — true of a window once the row
is ``max_position_embeddings`` long, which is the row the cell runs
(``tests/test_smallthinker_cell_rehearsal.py`` holds the traffic's
``seq`` to it).  ``flash_attn_*`` then read the GLOBAL layers' kernels
against the global layers' work, and the window kernels have readers of
their own (``flash_win_*``) over :func:`window_attn_train_flops_per_token`.

Weights from the seed (the configuration file lists this under
``assumed``): a matrix normal at 1/sqrt(the width it contracts) — the
head at 1/sqrt(hidden) — norms ones: ``hybrid_trunk.init_leaf``, one
leaf at a time from a key folded by the leaf's place in the tree.  THE
EMBEDDING ROWS are normal at std ``EMBED_STD`` = 2: a token's own row
then leads its state through all the layers (the blocks add ~0.14 a
channel a layer at this initialisation) and the router's picks stay
spread over the experts, as the published top-6 of 64 presumes.  At
1/sqrt(hidden) the blocks' outputs outgrow the row by the third layer, a
component COMMON to all tokens is 64 % of the normed state by the
eighth, the picks collapse onto a few experts (PERF.md section 6, PR
44), and an expert left with one or two rows has dead ReLU units:
adafactor's factored second moment turns that leaf's zero-gradient
columns into 0 x inf (r x vc underflows under eps1 = 1e-30), in the
program and in the float32 reference alike — NaN from the second step.
"""

from __future__ import annotations

from ..kernel_costs import BlockCosts
from . import tree_of
from .llama_block import seed_key       # noqa: F401  (the same rule)
# the tree by kind under the reference's paths and its layout on the
# mesh: the same functions for every family of ``hybrid_trunk``
from .xing_mhc_moe import leaf_shapes   # noqa: F401

EMBED_STD = 2.0

# names this family's program adds to the base vocabulary
MOE_SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine")
SCOPES = MOE_SCOPES
WINDOW_KERNELS = ("flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv")
KERNELS = ("grouped_mm", "grouped_mm_dw", "moe_sum_pairs") + WINDOW_KERNELS
KINDS = ("gqa_moe_global", "gqa_moe_window")


def layer_kinds(conf: dict):
    return tuple(KINDS[w] for w in
                 conf["sliding_window_layout"][:conf["num_hidden_layers"]])


def attention_params(conf: dict) -> int:
    c, d = conf["hidden_size"], conf["head_dim"]
    return 2 * c * d * (conf["num_attention_heads"]
                        + conf["num_key_value_heads"])


def expert_params(conf: dict) -> int:
    return 3 * conf["hidden_size"] * conf["moe_ffn_hidden_size"]


def expected_pairs_per_token(conf: dict) -> float:
    """(token, pick) pairs a token sends to the experts held here, if the
    router spreads its picks evenly over the published experts."""
    return conf["moe_num_active_primary_experts"] \
        * conf["moe_num_primary_experts"] \
        / conf["published"]["moe_num_primary_experts"]


def expert_flops_per_token(conf: dict) -> float:
    """FLOPs a token costs in the routed experts' grouped products,
    forward + backward, all layers (recompute not counted): three
    products forward and six backward, 2 x hidden x expert width each, for
    the EXPECTED pairs a token sends to the experts held here."""
    return 9 * 2.0 * conf["hidden_size"] * conf["moe_ffn_hidden_size"] \
        * expected_pairs_per_token(conf) * conf["num_hidden_layers"]


def window_keys_per_query(conf: dict, seq: int) -> float:
    """Keys a query of a ``seq``-token row sees in a window layer, on
    average: ``W - W^2 / 2 seq`` (seq / 2, the causal count, where the
    row is no longer than the window)."""
    w = min(conf["sliding_window_size"], seq)
    return w - w * w / (2.0 * seq)


def window_attn_train_flops_per_token(conf: dict, seq: int) -> float:
    """The window layers' attention forward + backward for one token of
    a ``seq``-token row, recompute not counted: QK^T and PV over the keys
    a query sees, 2 x 2 x keys x heads x head_dim a layer forward, times 3
    with the backward — what ``flash_win_*`` are read against.  Bound:
    compute."""
    return 3 * 4.0 * conf["num_attention_heads"] * conf["head_dim"] \
        * window_keys_per_query(conf, seq) \
        * layer_kinds(conf).count("gqa_moe_window")


def block_costs(conf: dict, kind: str) -> BlockCosts:
    c = conf["hidden_size"]
    width = conf["num_attention_heads"] * conf["head_dim"]
    outside = attention_params(conf) \
        + c * conf["published"]["moe_num_primary_experts"]
    window = kind == "gqa_moe_window"
    return BlockCosts(
        matmul_params=outside + round(expected_pairs_per_token(conf)
                                      * expert_params(conf)),
        resident_params=outside + conf["moe_num_primary_experts"]
        * expert_params(conf),
        vector_params=2 * c, attn_width=0 if window else width,
        kv_values=2 * conf["num_key_value_heads"] * conf["head_dim"],
        scan_flops=round(4 * width * window_keys_per_query(
            conf, conf["max_position_embeddings"])) if window else 0)


def build_cfg(conf: dict, train: bool, job: dict | None = None):
    """The program's config object from the published keys and the
    share."""
    import jax.numpy as jnp
    from paddle_tpu.models.llama_pretrain import LlamaPretrainConfig
    if conf["rope_scaling"] or conf["tie_word_embeddings"] \
            or not conf["norm_topk_prob"]:
        raise ValueError(
            "smallthinker_moe: plain rotation, an untied head and gates "
            "that sum to one over the picks are what it states")
    job = job or {}
    return LlamaPretrainConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        intermediate_size=conf["moe_ffn_hidden_size"],
        num_hidden_layers=conf["num_hidden_layers"],
        num_attention_heads=conf["num_attention_heads"],
        num_key_value_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"],
        max_seq_len=job.get("seq", conf["max_position_embeddings"]),
        rope_theta=float(conf["rope_theta"]),
        rms_norm_eps=float(conf["rms_norm_eps"]),
        rope_layout=tuple(conf["rope_layout"]),
        sliding_window_layout=tuple(conf["sliding_window_layout"]),
        sliding_window_size=conf["sliding_window_size"],
        moe_primary_router_apply_softmax=conf[
            "moe_primary_router_apply_softmax"],
        moe_intermediate_size=conf["moe_ffn_hidden_size"],
        n_routed_experts=conf["published"]["moe_num_primary_experts"],
        experts_held=conf["moe_num_primary_experts"],
        expert_first=conf["expert_first"],
        num_experts_per_tok=conf["moe_num_active_primary_experts"],
        use_pallas_attention=True, sequence_parallel=False,
        remat=train, remat_policy=job.get("remat_policy", "full"),
        dtype=jnp.bfloat16,
        param_dtype=jnp.float32 if train else jnp.bfloat16,
        loss_chunks=job.get("loss_chunks", 0) if train else 0)


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
        std = EMBED_STD if path == ("embed",) else cfg.hidden_size ** -0.5
        return (jax.random.normal(k, shapes[path], jnp.float32)
                * std).astype(dtype)
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
