"""Family plug-in ``granite_hybrid``: layers of two kinds, ``mamba`` (a
Mamba-2 mixer) and ``attention`` (GQA without rotation at the
configuration's own score scale), each before the same SwiGLU MLP, a
tied table, multipliers at both ends and on every residual — HF
``GraniteMoeHybrid*`` with no experts.  The program is the normal path:
``llama_pretrain.make_train_step`` over ``models/hybrid_trunk.py``,
which this file's ``build_cfg`` reaches through the published keys.
The plain reference is ``granite_hybrid_reference.py``; the contract,
``benchmark/models/__init__.py``.

Weights from the seed (the configuration file lists this under
``assumed``): matrices normal at 1/sqrt(hidden), norms and ``D`` ones,
the convolution uniform in +-1/sqrt(d_conv), ``A_log = log U[1, 16]``,
``dt_bias`` the inverse softplus of a step log-uniform in [1e-3, 1e-1]
— the program's own rule (``hybrid_trunk.init_leaf``), one leaf at a
time from a key folded by the leaf's place in the tree.
"""

from __future__ import annotations

from ..kernel_costs import BlockCosts
from . import tree_of
from .llama_block import seed_key       # noqa: F401  (the same rule)

# names this family's program adds to the base vocabulary
SCOPES = ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm",
          "ssm_out_proj")
KERNELS = ("ssd_scan_fwd", "ssd_scan_bwd", "causal_conv_fwd",
           "causal_conv_bwd")
TOP_LEAVES = ("embed", "final_norm")


def layer_kinds(conf: dict):
    return tuple(conf["layer_types"][:conf["num_hidden_layers"]])


def _mamba(conf: dict):
    """(heads, head width, state, d_inner, conv channels, in_proj width)
    — one B/C group."""
    nh, p, n = (conf["mamba_n_heads"], conf["mamba_d_head"],
                conf["mamba_d_state"])
    di = nh * p
    conv = di + 2 * conf["mamba_n_groups"] * n
    return nh, p, n, di, conv, di + conv + nh


def scan_flops_per_token(conf: dict) -> int:
    """Forward FLOPs a token of one ``mamba`` layer's chunked scan at
    the configuration's own chunk Q (state N, head width P, H heads, one
    group): the scores ``C B^T`` of a chunk, 2 Q N a token ONCE for the
    group; a head's masked product with x, 2 Q P; its share of the
    chunk's end state, 2 N P, and of what the entering state adds,
    2 N P.  The causal half of the two Q-wide products is not taken off:
    a chunk's tile is computed whole."""
    nh, p, n = _mamba(conf)[:3]
    q = conf["mamba_chunk_size"]
    return 2 * q * n + nh * (2 * q * p + 4 * n * p)


def scan_kernel_bytes_per_token(conf: dict, itemsize: int = 2) -> int:
    """Least HBM bytes a token of one layer the kernel pair
    ``ssd_scan_fwd`` / ``ssd_scan_bwd`` moves, forward + backward
    (recompute not counted): x read twice, y written, dy read and dx
    written (5 d_inner values) and B, C read twice with their gradients
    written (6 N), in the compute type; the state that enters each chunk
    written by the forward and read by the backward, fp32 (2 x N x
    d_inner / Q a token); dt and the running sum read twice in both
    layouts and their gradients written in both, fp32 (12 H values).
    The kernels' operations are :func:`scan_flops_per_token`, times 3
    with the backward: they do the whole scan."""
    nh, _, n, di = _mamba(conf)[:4]
    q = conf["mamba_chunk_size"]
    return (5 * di + 6 * n) * itemsize + 2 * 4 * n * di // q + 12 * nh * 4


def block_costs(conf: dict, kind: str) -> BlockCosts:
    h, f = conf["hidden_size"], conf["intermediate_size"]
    mlp = 3 * h * f
    if kind == "mamba":
        nh, _, _, di, conv, proj = _mamba(conf)
        mats = h * proj + di * h + mlp
        vecs = 2 * h + conv * conf["mamba_d_conv"] + conv + 3 * nh + di
        return BlockCosts(matmul_params=mats, resident_params=mats,
                          vector_params=vecs, attn_width=0, kv_values=0,
                          scan_flops=scan_flops_per_token(conf))
    d = h // conf["num_attention_heads"]
    q, kv = conf["num_attention_heads"] * d, conf["num_key_value_heads"] * d
    mats = 2 * h * q + 2 * h * kv + mlp
    return BlockCosts(matmul_params=mats, resident_params=mats,
                      vector_params=2 * h, attn_width=q, kv_values=2 * kv)


def build_cfg(conf: dict, train: bool, job: dict | None = None):
    """The program's config object from the published keys."""
    import jax.numpy as jnp
    from paddle_tpu.models.llama_pretrain import LlamaPretrainConfig
    if conf.get("num_local_experts") or conf["mamba_n_groups"] != 1 or \
            conf["mamba_n_heads"] * conf["mamba_d_head"] != \
            conf["mamba_expand"] * conf["hidden_size"]:
        raise ValueError("granite_hybrid: dense MLP, one B/C group and "
                         "d_inner = expand x hidden are what it states")
    job = job or {}
    return LlamaPretrainConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        intermediate_size=conf["intermediate_size"],
        num_hidden_layers=conf["num_hidden_layers"],
        num_attention_heads=conf["num_attention_heads"],
        num_key_value_heads=conf["num_key_value_heads"],
        max_seq_len=job.get("seq", 2048),
        rms_norm_eps=float(conf["rms_norm_eps"]),
        layer_types=layer_kinds(conf),
        mamba_n_heads=conf["mamba_n_heads"],
        mamba_d_head=conf["mamba_d_head"],
        mamba_d_state=conf["mamba_d_state"],
        mamba_d_conv=conf["mamba_d_conv"],
        mamba_n_groups=conf["mamba_n_groups"],
        mamba_chunk_size=conf["mamba_chunk_size"],
        position_embedding_type=conf["position_embedding_type"],
        attention_multiplier=float(conf["attention_multiplier"]),
        embedding_multiplier=float(conf["embedding_multiplier"]),
        residual_multiplier=float(conf["residual_multiplier"]),
        logits_scaling=float(conf["logits_scaling"]),
        tie_word_embeddings=bool(conf["tie_word_embeddings"]),
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
    if path == ("embed",):
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
