"""Plain reference of the family ``smallthinker_moe`` (``model_name:
smallthinker_21b_instruct``): grouped-query attention — a WINDOW layer
(rotated q and k, the last ``sliding_window_size`` keys) or a GLOBAL one
(no rotation, every earlier key) by the layer's entry of
``sliding_window_layout`` / ``rope_layout`` — before routed ReGLU
experts without a shared one, on one residual stream, the ROUTER
READING THE ATTENTION'S INPUT.  Float32, every matrix product with a
weight through ``reference.matmul`` so that the int8 CONTROL reaches it
— the router's too; nothing of the program is imported.

    x  = rms_norm(h; ln1)
    z  = x . w_router                          [published experts], fp32
    picks = the top k of z;  g = softmax(z[picks])    (sums to 1 over the
            k picks, held here or not: norm_topk_prob changes nothing)
    q, k, v = x . wq [H, d], x . wk [KV, d], x . wv [KV, d]
    window: q, k rotated (rotate-half, rope_theta);
            key j visible to query i iff i - W < j <= i
    global: no rotation; j <= i
    h1 = h + softmax(q k^T / sqrt(d) over the visible keys) v . wo
    u  = rms_norm(h1; ln2)
    h2 = h1 + sum over the picks whose expert is HELD of
              g_e (relu(u . w_gate_e) * (u . w_up_e)) . w_down_e
    No auxiliary loss: the block's scalar is 0.

DEPARTURES from the published description, each also under ``assumed``
in the configuration's file:
  * the catalog says "router placed before attention" and not which
    tensor it reads: the NORMED one, ``input_layernorm``'s output (the
    attention's own input), is taken.
  * no projection has a bias; no secondary experts (the config states
    primary ones only); no dense layer (every layer routes).
  * the rotation is rotate-half (first half, second half), HF's form.
  * an expert's gate_proj and up_proj are one leaf, ``we_gate_up
    [experts, C, 2 F]`` (gate | up): one product, the same function.
  * THE SHARE: the experts held are ``expert_first .. +
    moe_num_primary_experts - 1`` of ``published.moe_num_primary_experts``;
    what the absent experts would add is left out, here as in the
    program.

The window is a plain ``[s, s]`` mask.  Attention runs a head at a time
(``lax.map`` under ``jax.checkpoint``: one head's scores at 16,384 are
1.07 GB in float32), the routed sum is a masked loop over the held
experts (every expert sees every token; no sort, no kernel), and a
block takes its rows one at a time.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..reference import F32, HI, matmul, rms_norm, rope

ATTN_LEAVES = ("ln1", "wq", "wk", "wv", "wo")
BLOCK_LEAVES = ATTN_LEAVES + ("ln2", "w_router", "we_gate_up", "we_down")


def dict_tuple(**kw):
    """Hashable, and read by name: ``dict(dims)``."""
    return tuple(kw.items())


def dims_of(conf: dict):
    return dict_tuple(
        heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        theta=float(conf["rope_theta"]), eps=float(conf["rms_norm_eps"]),
        window=conf["sliding_window_size"],
        k=conf["moe_num_active_primary_experts"],
        first=conf["expert_first"], held=conf["moe_num_primary_experts"])


def layer_kinds(conf: dict):
    depth = conf["num_hidden_layers"]
    windows = tuple(conf["sliding_window_layout"][:depth])
    if tuple(conf["rope_layout"][:depth]) != windows:
        raise ValueError("smallthinker_moe: a window layer rotates and a "
                         "global one does not; the two layouts differ")
    return tuple("gqa_moe_window" if w else "gqa_moe_global"
                 for w in windows)


def _attention(x, w, d, mm, window: bool):
    """x [1, s, C] (normed) -> [1, s, C]."""
    b, s, _ = x.shape
    n, nkv, hd = d["heads"], d["kv_heads"], d["head_dim"]
    q = mm(x, w["wq"]).reshape(b, s, n, hd)
    k = mm(x, w["wk"]).reshape(b, s, nkv, hd)
    v = mm(x, w["wv"]).reshape(b, s, nkv, hd)
    if window:
        q, k = rope(q, d["theta"]), rope(k, d["theta"])
    k = jnp.repeat(k, n // nkv, axis=2)
    v = jnp.repeat(v, n // nkv, axis=2)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if window:
        seen &= j > i - d["window"]

    def head(qkv):                  # a head at a time: [b, s, s] alive
        qh, kh, vh = qkv
        sc = jnp.einsum("bqd,bkd->bqk", qh, kh, precision=HI) \
            / math.sqrt(hd)
        sc = jnp.where(seen, sc, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(sc, axis=-1), vh,
                          precision=HI)
    o = jax.lax.map(jax.checkpoint(head),
                    tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return mm(jnp.moveaxis(o, 0, 2).reshape(b, s, -1), w["wo"])


def _route(x, w, d, mm):
    """The picks and their gates from the ROUTER's input."""
    z = mm(x, w["w_router"])
    top, idx = jax.lax.top_k(z, d["k"])
    return idx, jax.nn.softmax(top, axis=-1)


def _experts(u, idx, g, w, d, mm):
    @jax.checkpoint
    def expert(we):
        wgu, wd, e = we
        f = wd.shape[0]
        mine = jnp.sum(jnp.where(idx == e + d["first"], g, 0.0), -1)
        hid = jax.nn.relu(mm(u, wgu[:, :f])) * mm(u, wgu[:, f:])
        return mine[..., None] * mm(hid, wd)
    held = (w["we_gate_up"], w["we_down"],
            jnp.arange(d["held"], dtype=idx.dtype))
    routed, _ = jax.lax.scan(lambda acc, we: (acc + expert(we), None),
                             jnp.zeros_like(u), held)
    return routed


def _block(x, w, dims, precision, window: bool):
    d = dict(dims)
    mm = functools.partial(matmul, precision=precision)

    @jax.checkpoint
    def row(h):
        h = h[None]
        x = rms_norm(h, w["ln1"], d["eps"])
        idx, g = _route(x, w, d, mm)
        h = h + _attention(x, w, d, mm, window)
        u = rms_norm(h, w["ln2"], d["eps"])
        return (h + _experts(u, idx, g, w, d, mm))[0]
    return jax.lax.map(row, x), jnp.zeros((), F32)


def window_block(x, w, dims, precision="f32"):
    return _block(x, w, dims, precision, True)


def global_block(x, w, dims, precision="f32"):
    return _block(x, w, dims, precision, False)


KINDS = {"gqa_moe_global": (BLOCK_LEAVES, global_block),
         "gqa_moe_window": (BLOCK_LEAVES, window_block)}
