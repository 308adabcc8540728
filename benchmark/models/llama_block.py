"""Family plug-in: the pre-norm RMSNorm / RoPE / GQA / SwiGLU block, no
biases, untied head — the one block the program's main path runs
(``LlamaPretrainConfig``).  A configuration file names this module by
``"family": "llama_block"``; another family is another file here with
the same functions (``benchmark/models/__init__.py`` lists them), and
its plain reference beside it (``llama_block_reference.py``).

The benchmark, not the program, makes the weights: one jitted call from
the seed, on the device, in the type they are used in.  The reference
is given these same arrays.  This block's program uses only names of
the base vocabulary, so it states no ``SCOPES`` or ``KERNELS``.
"""

from __future__ import annotations

import math

from ..kernel_costs import BlockCosts

# leaves of one block, in a fixed order: a leaf's key is its index
BLOCK_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
                "w_down")
TOP_LEAVES = ("embed", "final_norm", "lm_head")


def block_costs(conf: dict) -> BlockCosts:
    """Every token passes through every matrix: q and o are hidden x
    heads*head_dim, k and v hidden x kv_heads*head_dim, gate, up and
    down hidden x intermediate."""
    h, f = conf["hidden_size"], conf["intermediate_size"]
    q = conf["num_attention_heads"] * conf["head_dim"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    mats = 2 * h * q + 2 * h * kv + 3 * h * f
    return BlockCosts(matmul_params=mats, resident_params=mats,
                      vector_params=2 * h, attn_width=q, kv_values=2 * kv)


def build_cfg(conf: dict, train: bool, job: dict | None = None):
    """The program's config object from the configuration file's
    published keys."""
    import jax.numpy as jnp
    from paddle_tpu.models.llama_pretrain import LlamaPretrainConfig
    if conf["hidden_size"] // conf["num_attention_heads"] != \
            conf["head_dim"]:
        raise ValueError("the program derives head_dim = hidden/heads; "
                         "this configuration states another")
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
        use_pallas_attention=True, sequence_parallel=False,
        remat=train, remat_policy=job.get("remat_policy", "full"),
        dtype=jnp.bfloat16,
        param_dtype=jnp.float32 if train else jnp.bfloat16,
        loss_chunks=job.get("loss_chunks", 0) if train else 0)


def leaf_shapes(cfg) -> dict:
    h, f, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    kvh = cfg.num_key_value_heads * cfg.head_dim
    blk = {"ln1": (h,), "ln2": (h,), "wq": (h, h), "wk": (h, kvh),
           "wv": (h, kvh), "wo": (h, h), "w_gate": (h, f),
           "w_up": (h, f), "w_down": (f, h)}
    out = {("blocks", k): (L,) + v for k, v in blk.items()}
    out[("embed",)] = (cfg.vocab_size, h)
    out[("final_norm",)] = (h,)
    out[("lm_head",)] = (h, cfg.vocab_size)
    return out


def _leaf_index(path) -> int:
    return (BLOCK_LEAVES.index(path[1]) if path[0] == "blocks"
            else len(BLOCK_LEAVES) + TOP_LEAVES.index(path[0]))


def make_leaf(cfg, key, path, dtype=None):
    """One leaf from the run's key: norms are ones, matrices normal with
    std 1/sqrt(hidden).  Traceable, and the same values whether called
    alone or inside :func:`make_params`."""
    import jax
    import jax.numpy as jnp
    shape = leaf_shapes(cfg)[path]
    dtype = dtype or cfg.param_dtype
    if path[-1] in ("ln1", "ln2", "final_norm"):
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, _leaf_index(path))
    std = 1.0 / math.sqrt(cfg.hidden_size)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def seed_key(seed: int):
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _tree(cfg, key):
    out = {"blocks": {}}
    for path in leaf_shapes(cfg):
        if path[0] == "blocks":
            out["blocks"][path[1]] = make_leaf(cfg, key, path)
        else:
            out[path[0]] = make_leaf(cfg, key, path)
    return out


def make_params(cfg, seed: int, mesh):
    """The whole parameter tree in one jitted call, laid out over
    ``mesh`` by the program's own ``param_specs``."""
    import jax
    from jax.sharding import NamedSharding
    from paddle_tpu.models.llama_pretrain import param_specs
    specs = param_specs(cfg, 1)
    shard = jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp), specs,
        is_leaf=lambda x: not isinstance(x, dict))
    return jax.jit(lambda k: _tree(cfg, k), out_shardings=shard)(
        seed_key(seed))
