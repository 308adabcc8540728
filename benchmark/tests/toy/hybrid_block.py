"""Program side of the toy family ``hybrid_block`` (tests only; see
``hybrid_block_reference.py``): the tree by kind under the reference's
paths, the weights from the seed, what a layer of each kind costs.  The
repo's program runs one kind of layer, so this family's "program" is
the stand-in of ``hybrid_program.py``."""

from __future__ import annotations

import math
import types

from benchmark.kernel_costs import BlockCosts
from benchmark.models import tree_of
from benchmark.models.llama_block import seed_key      # noqa: F401

MLP = ("ln2", "w_gate", "w_up", "w_down")
LEAVES = {"mix": ("ln1", "w_in", "decay", "w_out") + MLP,
          "attention": ("ln1", "wq", "wk", "wv", "wo") + MLP}
TOP_LEAVES = ("embed", "final_norm")


def layer_kinds(conf: dict):
    return tuple(conf["layer_types"][:conf["num_hidden_layers"]])


def block_costs(conf: dict, kind: str) -> BlockCosts:
    h, f = conf["hidden_size"], conf["intermediate_size"]
    q = conf["num_attention_heads"] * conf["head_dim"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    if kind == "mix":
        # one multiply and one add a channel a token, whatever the row
        return BlockCosts(2 * h * h + 3 * h * f, 2 * h * h + 3 * h * f,
                          3 * h, attn_width=0, kv_values=0, scan_flops=2 * h)
    mats = 2 * h * q + 2 * h * kv + 3 * h * f
    return BlockCosts(mats, mats, 2 * h, attn_width=q, kv_values=2 * kv)


def build_cfg(conf: dict, train: bool, job: dict | None = None):
    import jax.numpy as jnp
    return types.SimpleNamespace(conf=conf, vocab_size=conf["vocab_size"],
                                 hidden_size=conf["hidden_size"],
                                 param_dtype=jnp.float32)


def leaf_shapes(cfg) -> dict:
    c = cfg.conf
    h, f = c["hidden_size"], c["intermediate_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    one = {"ln1": (h,), "ln2": (h,), "decay": (h,), "w_in": (h, h),
           "w_out": (h, h), "wq": (h, q), "wk": (h, kv), "wv": (h, kv),
           "wo": (q, h), "w_gate": (h, f), "w_up": (h, f), "w_down": (f, h)}
    kinds = layer_kinds(c)
    out = {("blocks", k, nm): (kinds.count(k),) + one[nm]
           for k in LEAVES for nm in LEAVES[k]}
    out[("embed",)] = (c["vocab_size"], h)
    out[("final_norm",)] = (h,)
    return out


def make_leaf(cfg, key, path, dtype=None):
    import jax
    import jax.numpy as jnp
    shapes = leaf_shapes(cfg)
    if path[-1] in ("ln1", "ln2", "final_norm"):
        return jnp.ones(shapes[path], dtype or cfg.param_dtype)
    k = jax.random.fold_in(key, list(shapes).index(path))
    std = 1.0 / math.sqrt(cfg.hidden_size)
    return (jax.random.normal(k, shapes[path], jnp.float32) * std).astype(
        dtype or cfg.param_dtype)


def make_params(cfg, seed: int, mesh):
    import jax
    return jax.jit(lambda k: tree_of(
        leaf_shapes(cfg), lambda p: make_leaf(cfg, k, p)))(seed_key(seed))
