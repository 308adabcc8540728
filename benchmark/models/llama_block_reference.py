"""Plain reference of the family ``llama_block``: the pre-norm RMSNorm /
RoPE / GQA / SwiGLU block as published, in straightforward
``jax.numpy``, float32 — no kernel, no cache, no scan, no batching
tricks.  ``benchmark/reference.py`` drives it (serving: one forward
pass; training: loss, gradients, adafactor) and gives it the matrix
product, the norm and the rotation, so that the int8 CONTROL reaches
every product of the block.

The contract it fills (``BLOCK_LEAVES``, ``dims_of``, ``block``) is
stated in ``benchmark/models/__init__.py``.  This block adds nothing to
the loss.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..reference import F32, HI, matmul, rms_norm, rope

BLOCK_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
                "w_down")


def dims_of(conf: dict):
    return (conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["head_dim"], float(conf["rope_theta"]),
            float(conf["rms_norm_eps"]))


def block(x, w, dims, precision="f32"):
    """``dims`` = (heads, kv_heads, head_dim, theta, eps)."""
    n, nkv, d, theta, eps = dims
    b, s, h = x.shape
    mm = functools.partial(matmul, precision=precision)
    y = rms_norm(x, w["ln1"], eps)
    q = rope(mm(y, w["wq"]).reshape(b, s, n, d), theta)
    k = rope(mm(y, w["wk"]).reshape(b, s, nkv, d), theta)
    v = mm(y, w["wv"]).reshape(b, s, nkv, d)
    k = jnp.repeat(k, n // nkv, axis=2)
    v = jnp.repeat(v, n // nkv, axis=2)
    sc = jnp.einsum("bqnd,bknd->bnqk", q, k, precision=HI) / math.sqrt(d)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    a = jnp.einsum("bnqk,bknd->bqnd", p, v, precision=HI).reshape(b, s, h)
    x = x + mm(a, w["wo"])
    y = rms_norm(x, w["ln2"], eps)
    x = x + mm(jax.nn.silu(mm(y, w["w_gate"])) * mm(y, w["w_up"]),
               w["w_down"])
    return x, jnp.zeros((), F32)
