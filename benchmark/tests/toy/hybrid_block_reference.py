"""Plain reference of the toy family ``hybrid_block`` (tests only: the
seam's tests copy this file into ``models/`` of a COPY of
``benchmark/``).  Layers of two kinds in the order the configuration's
``layer_types`` gives, cut to ``num_hidden_layers``: ``mix``, whose
mixer is a per-channel decayed running sum (it attends to nothing and
carries a state along the row), and ``attention``, GQA with no rotation
and the configuration's own score scale; each followed by a SwiGLU MLP,
every residual add scaled.  The table is tied: the first block's input
is ``embedding_multiplier . E[ids]``, the logits are ``rms_norm(x) . E^T
/ logits_scaling``.  The contract: ``benchmark/models/__init__.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import F32, HI, matmul, rms_norm

MLP_LEAVES = ("ln2", "w_gate", "w_up", "w_down")
TOP_LEAVES = ("embed", "final_norm")


def dims_of(conf: dict):
    return (conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["head_dim"], float(conf["rms_norm_eps"]),
            float(conf["embedding_multiplier"]),
            float(conf["residual_multiplier"]),
            float(conf["attention_multiplier"]),
            float(conf["logits_scaling"]))


def layer_kinds(conf: dict):
    return tuple(conf["layer_types"][:conf["num_hidden_layers"]])


def _mlp(x, w, dims, mm):
    eps, res = dims[3], dims[5]
    v = rms_norm(x, w["ln2"], eps)
    return x + res * mm(jax.nn.silu(mm(v, w["w_gate"])) * mm(v, w["w_up"]),
                        w["w_down"])


def mix_block(x, w, dims, precision="f32"):
    """y_t = sigmoid(decay) * y_{t-1} + v_t, a channel at a time."""
    eps, res = dims[3], dims[5]
    mm = functools.partial(matmul, precision=precision)
    v = mm(rms_norm(x, w["ln1"], eps), w["w_in"])
    a = jax.nn.sigmoid(w["decay"])
    _, y = jax.lax.scan(lambda h, vt: (a * h + vt,) * 2,
                        jnp.zeros_like(v[:, 0]), jnp.swapaxes(v, 0, 1))
    x = x + res * mm(jnp.swapaxes(y, 0, 1), w["w_out"])
    return _mlp(x, w, dims, mm), jnp.zeros((), F32)


def attention_block(x, w, dims, precision="f32"):
    n, nkv, d, eps, _, res, scale, _ = dims
    b, s, _ = x.shape
    mm = functools.partial(matmul, precision=precision)
    y = rms_norm(x, w["ln1"], eps)
    q = mm(y, w["wq"]).reshape(b, s, n, d)
    k = jnp.repeat(mm(y, w["wk"]).reshape(b, s, nkv, d), n // nkv, axis=2)
    v = jnp.repeat(mm(y, w["wv"]).reshape(b, s, nkv, d), n // nkv, axis=2)
    sc = jnp.einsum("bqnd,bknd->bnqk", q, k, precision=HI) * scale
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    a = jnp.einsum("bnqk,bknd->bqnd", p, v, precision=HI).reshape(b, s, n * d)
    x = x + res * mm(a, w["wo"])
    return _mlp(x, w, dims, mm), jnp.zeros((), F32)


KINDS = {
    "mix": (("ln1", "w_in", "decay", "w_out") + MLP_LEAVES, mix_block),
    "attention": (("ln1", "wq", "wk", "wv", "wo") + MLP_LEAVES,
                  attention_block),
}


def first_input(top, ids, dims):
    return dims[4] * jnp.take(top["embed"], ids, axis=0)


def logits(top, x, dims, precision):
    return matmul(rms_norm(x, top["final_norm"], dims[3]), top["embed"].T,
                  precision) / dims[7]
