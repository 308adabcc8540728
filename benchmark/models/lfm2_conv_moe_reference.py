"""Plain reference of the family ``lfm2_conv_moe`` (``model_type:
lfm2_moe``): an OPERATOR — a gated short convolution, or grouped-query
attention with per-head q / k norms — before a dense SwiGLU MLP (the
leading layers) or routed SwiGLU experts without a shared one, on one
residual stream, and the model's two ends over ONE tied table.
Float32, every matrix product with a weight through
``reference.matmul`` so that the int8 CONTROL reaches it — the router's
too; nothing of the program is imported.

    y  = rms_norm(h; ln1)                                (operator_norm)
    conv:  [B | Cg | X] = y . w_in   (w_in [C, 3 C], chunks in THIS order)
           u = B * X
           v[t] = sum_{k<K} conv_w[:, k] u[t - (K-1) + k]     (zeros before
                  the row; no bias, no activation: K shifted adds)
           h1 = h + (Cg * v) . w_out
    attention: q, k, v = y . wq [H, d], y . wk [KV, d], y . wv [KV, d]
           q = rms_norm(q; q_layernorm [d]), k = rms_norm(k; k_layernorm
           [d]) a head;  q, k rotated (rotate-half, theta);  j <= i
           h1 = h + softmax(q k^T / sqrt(d)) v . wo
    u  = rms_norm(h1; ln2)                               (ffn_norm)
    dense: h2 = h1 + (silu(u . w_gate) * (u . w_up)) . w_down
    experts: s = sigmoid(u . w_router)                   [published experts]
           picks = the top k of s + expert_bias  (the bias SELECTS: it
                   gates nothing and reads no gradient)
           g_e = scale * s_e / (sum of the picked s + 1e-6)
           h2 = h1 + sum over the picks whose expert is HELD of
                     g_e (silu(u . w_gate_e) * (u . w_up_e)) . w_down_e
    ends:  h_0 = embed[ids];  logits = rms_norm(h_L; final_norm) . embed^T
    No auxiliary loss: the block's scalar is 0.

DEPARTURES from ``transformers/models/lfm2/modeling_lfm2.py`` (4.57.6:
``Lfm2ShortConv`` :415, ``Lfm2Attention`` :356, the block :537, the ends
:607 / :670) and from the catalog row, each also under ``assumed`` in the
configuration's file:
  * ``lfm2_moe``'s expert block is not in that file: the router is taken
    from the row's keys (``use_expert_bias``, ``norm_topk_prob``,
    ``routed_scaling_factor``) and LiquidAI's description ("normalized
    sigmoid gating with adaptive routing biases"): sigmoid scores, the
    bias added for the top-k only, the picked SCORES normalised with
    1e-6 in the denominator.
  * HF's convolution is ``nn.Conv1d(groups=C, padding=K-1)`` cut to the
    row, on a transposed array: the same sums, written as K shifted adds.
  * ``in_proj``'s output is chunked B, C, x in that order (HF's names;
    Cg here, since C is the width); no projection has a bias
    (``conv_bias: false``).
  * an expert's w1 and w3 are one leaf, ``we_gate_up [experts, C, 2 F]``
    (gate | up): one product, the same function; the dense MLP's w1, w3,
    w2 are ``w_gate``, ``w_up``, ``w_down``; ``operator_norm`` /
    ``ffn_norm`` / ``embedding_norm`` are ``ln1`` / ``ln2`` /
    ``final_norm`` (the program's names: the paths must meet).
  * ``intermediate_size`` is the dense MLP's width as stated (no
    ``block_auto_adjust_ff_dim``); ``tie_word_embeddings`` true (the
    family's default).
  * THE SHARE: the experts held are ``expert_first .. + num_experts - 1``
    of ``published.num_experts``; what the absent experts would add is
    left out, here as in the program.

Attention runs a head at a time (``lax.map`` under ``jax.checkpoint``:
two rows' scores of one head at 8,192 are 537 MB in float32), the routed
sum is a masked loop over the held experts (every expert sees every
token; no sort, no kernel), and a block takes its rows one at a time.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..reference import F32, HI, matmul, rms_norm, rope

CONV_LEAVES = ("ln1", "w_in", "conv_w", "w_out")
ATTN_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "q_layernorm", "k_layernorm")
DENSE_LEAVES = ("ln2", "w_gate", "w_up", "w_down")
MOE_LEAVES = ("ln2", "w_router", "expert_bias", "we_gate_up", "we_down")
TOP_LEAVES = ("embed", "final_norm")


def dict_tuple(**kw):
    """Hashable, and read by name: ``dict(dims)``."""
    return tuple(kw.items())


def dims_of(conf: dict):
    return dict_tuple(
        heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"],
        head_dim=conf["hidden_size"] // conf["num_attention_heads"],
        theta=float(conf["rope_parameters"]["rope_theta"]),
        eps=float(conf["norm_eps"]), taps=conf["conv_L_cache"],
        k=conf["num_experts_per_tok"],
        scale=float(conf["routed_scaling_factor"]),
        first=conf["expert_first"], held=conf["num_experts"])


def layer_kinds(conf: dict):
    depth, dense = conf["num_hidden_layers"], conf["num_dense_layers"]
    kinds = []
    for i, t in enumerate(conf["layer_types"][:depth]):
        if t == "conv":
            kinds.append("conv_dense" if i < dense else "conv_moe")
        elif t == "full_attention" and i >= dense:
            kinds.append("gqa_qknorm_moe")
        else:
            raise ValueError(f"lfm2_conv_moe: layer {i} is {t!r}")
    return tuple(kinds)


def _short_conv(y, w, d, mm):
    """y [1, s, C] (normed) -> [1, s, C]."""
    s, c = y.shape[1], y.shape[2]
    bcx = mm(y, w["w_in"])
    b_, cg, x = bcx[..., :c], bcx[..., c:2 * c], bcx[..., 2 * c:]
    up = jnp.pad(b_ * x, ((0, 0), (d["taps"] - 1, 0), (0, 0)))
    v = sum(up[:, i:i + s] * w["conv_w"][:, i] for i in range(d["taps"]))
    return mm(cg * v, w["w_out"])


def _attention(y, w, d, mm):
    """y [1, s, C] (normed) -> [1, s, C]."""
    b, s, _ = y.shape
    n, nkv, hd = d["heads"], d["kv_heads"], d["head_dim"]
    q = mm(y, w["wq"]).reshape(b, s, n, hd)
    k = mm(y, w["wk"]).reshape(b, s, nkv, hd)
    v = mm(y, w["wv"]).reshape(b, s, nkv, hd)
    q = rms_norm(q, w["q_layernorm"], d["eps"])
    k = rms_norm(k, w["k_layernorm"], d["eps"])
    q, k = rope(q, d["theta"]), rope(k, d["theta"])
    k = jnp.repeat(k, n // nkv, axis=2)
    v = jnp.repeat(v, n // nkv, axis=2)
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

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


def _route(u, w, d, mm):
    """The picks (by score + bias) and their gates (by score)."""
    s = jax.nn.sigmoid(mm(u, w["w_router"]))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(s + w["expert_bias"]),
                           d["k"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    return idx, d["scale"] * top / (jnp.sum(top, -1, keepdims=True) + 1e-6)


def _experts(u, idx, g, w, d, mm):
    @jax.checkpoint
    def expert(we):
        wgu, wd, e = we
        f = wd.shape[0]
        mine = jnp.sum(jnp.where(idx == e + d["first"], g, 0.0), -1)
        hid = jax.nn.silu(mm(u, wgu[:, :f])) * mm(u, wgu[:, f:])
        return mine[..., None] * mm(hid, wd)
    held = (w["we_gate_up"], w["we_down"],
            jnp.arange(d["held"], dtype=idx.dtype))
    routed, _ = jax.lax.scan(lambda acc, we: (acc + expert(we), None),
                             jnp.zeros_like(u), held)
    return routed


def _block(x, w, dims, precision, operator):
    d = dict(dims)
    mm = functools.partial(matmul, precision=precision)

    @jax.checkpoint
    def row(h):
        h = h[None]
        h = h + operator(rms_norm(h, w["ln1"], d["eps"]), w, d, mm)
        u = rms_norm(h, w["ln2"], d["eps"])
        if "w_router" in w:
            idx, g = _route(u, w, d, mm)
            return (h + _experts(u, idx, g, w, d, mm))[0]
        return (h + mm(jax.nn.silu(mm(u, w["w_gate"])) * mm(u, w["w_up"]),
                       w["w_down"]))[0]
    return jax.lax.map(row, x), jnp.zeros((), F32)


def conv_block(x, w, dims, precision="f32"):
    return _block(x, w, dims, precision, _short_conv)


def attention_block(x, w, dims, precision="f32"):
    return _block(x, w, dims, precision, _attention)


KINDS = {"conv_dense": (CONV_LEAVES + DENSE_LEAVES, conv_block),
         "conv_moe": (CONV_LEAVES + MOE_LEAVES, conv_block),
         "gqa_qknorm_moe": (ATTN_LEAVES + MOE_LEAVES, attention_block)}


def first_input(top, ids, dims):
    return jnp.take(top["embed"], ids, axis=0)


def logits(top, x, dims, precision):
    return matmul(rms_norm(x, top["final_norm"], dict(dims)["eps"]),
                  top["embed"].T, precision)
