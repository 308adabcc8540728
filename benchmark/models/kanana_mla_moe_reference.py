"""Plain reference of the family ``kanana_mla_moe`` (``model_type:
deepseek_v3`` with ``q_lora_rank: null``, kakaocorp's Kanana-2 30B-A3B):
latent attention (MLA) whose query is a direct projection, before a
dense SwiGLU MLP (``mla_dense``, the first ``first_k_dense_replace``
layers) or before sigmoid-routed experts beside shared ones
(``mla_moe``), on ONE residual stream; the default ends (``embed``,
``final_norm``, ``lm_head``).  Float32, every matrix product through
``reference.matmul`` so that the int8 CONTROL reaches it — the router's
too; the norm and the rotation are ``reference.rms_norm`` and
``reference.rope``; nothing of the program is imported.

    y   = rms_norm(x; ln1)
    q   = y . [w_q_nope | w_q_rope]                       [H, nope | rope]
    [c | k_r] = y . w_kva            (kv_lora_rank | rope)
    [k_nope | v] = rms_norm(c; kv_norm) . [w_kvb_k | w_kvb_v]   [H, nope | v]
    q_r, k_r rotated (rotate-half) at rope_theta's plain frequencies over
        the rope dims, k_r ONE vector a token for all heads
    S   = ([q_nope | q_r] . [k_nope | k_r]^T) (nope + rope)^-1/2, causal
    x1  = x + softmax(S) v . wo
    u   = rms_norm(x1; ln2)
    mla_dense: x2 = x1 + (silu(u . w_gate) * (u . w_up)) . w_down
    mla_moe:   s = sigmoid(u . w_router) over ALL the published experts;
               the top k of s;  g = routed_scaling_factor s_e / (sum of
               the picked s + 1e-20) over all k picks, held here or not
               x2 = x1 + sum over the picks whose expert is HELD of
                    g_e E_e(u) + E_shared(u),  E(u) = (silu(u w_g) * u w_u) w_d
    No auxiliary loss: the block's scalar is 0.

It follows ``transformers/models/deepseek_v3/modeling_deepseek_v3.py``
(4.57.6): ``DeepseekV3Attention`` with ``q_lora_rank is None`` (:353,
:393: ``q_proj``, no query norm; ``scaling = qk_head_dim ** -0.5`` with
no ``rope_scaling``), ``apply_rotary_pos_emb_interleave`` (:283),
``DeepseekV3TopkRouter`` at one group (:109: sigmoid scores, the top k,
``/ (sum + 1e-20)``, ``* routed_scaling_factor``), ``DeepseekV3MoE``
(:156: the shared experts ONE MLP of ``moe_intermediate_size *
n_shared_experts``, added to the routed sum), ``DeepseekV3DecoderLayer``
(:449: pre-norm, two residual adds).

DEPARTURES from that file, each also under ``assumed`` in the
configuration's file:
  * ``q_proj`` and ``kv_b_proj`` are two leaves each — all heads' nope
    columns | all heads' rope columns, all heads' k_nope | all heads' v:
    a fixed permutation of the published per-head column order, the same
    function.
  * the rotation is rotate-half (first half, second half of the rope
    dims): ``apply_rotary_pos_emb_interleave`` moves the published
    interleaved pairs (2i, 2i + 1) to (i, i + rope / 2) and THEN rotates
    halves; the leaves hold the rope columns after that fixed
    permutation (of ``w_q_rope``'s columns a head and of ``w_kva``'s last
    ``rope`` columns), so the permutation itself is not run.
  * a routed expert's gate_proj and up_proj are one leaf, ``we_gate_up
    [experts, C, 2 F]`` (gate | up): one product, the same function; the
    shared experts' are ``ws_gate``, ``ws_up``, ``ws_down`` of width
    ``n_shared_experts x F``.
  * ``noaux_tc``'s correction bias (``e_score_correction_bias``, a
    buffer that selects only) is zero and not held, one group
    (``n_group = topk_group = 1``: the group mask keeps every expert).
  * THE SHARE: the experts held are ``expert_first .. + n_routed_experts
    - 1`` of ``published.n_routed_experts``; what the absent experts
    would add is left out, here as in the program.  Attention and the
    shared experts are whole on every chip.

Attention runs a head at a time (``lax.map`` under ``jax.checkpoint``:
one head's scores at 16,384 are 1.07 GB in float32), the routed sum is a
masked loop over the held experts (every expert sees every token; no
sort, no kernel), and a block takes its rows one at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..reference import F32, HI, matmul, rms_norm, rope

ATTN_LEAVES = ("ln1", "w_q_nope", "w_q_rope", "w_kva", "kv_norm", "w_kvb_k",
               "w_kvb_v", "wo", "ln2")


def dict_tuple(**kw):
    """Hashable, and read by name: ``dict(dims)``."""
    return tuple(kw.items())


def dims_of(conf: dict):
    return dict_tuple(
        heads=conf["num_attention_heads"], eps=float(conf["rms_norm_eps"]),
        kv_rank=conf["kv_lora_rank"], theta=float(conf["rope_theta"]),
        score_scale=(conf["qk_nope_head_dim"]
                     + conf["qk_rope_head_dim"]) ** -0.5,
        k=conf["num_experts_per_tok"],
        gate_scale=float(conf["routed_scaling_factor"]),
        first=conf["expert_first"], held=conf["n_routed_experts"])


def layer_kinds(conf: dict):
    dense = min(conf["first_k_dense_replace"], conf["num_hidden_layers"])
    return ("mla_dense",) * dense + ("mla_moe",) * (
        conf["num_hidden_layers"] - dense)


def _swiglu(x, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


def _attention(y, w, d, mm):
    """y [b, s, C] (normed) -> [b, s, C]."""
    b, s, _ = y.shape
    H = d["heads"]
    q = jnp.concatenate(
        [mm(y, w["w_q_nope"]).reshape(b, s, H, -1),
         rope(mm(y, w["w_q_rope"]).reshape(b, s, H, -1), d["theta"])], -1)
    ckr = mm(y, w["w_kva"])
    c = rms_norm(ckr[..., :d["kv_rank"]], w["kv_norm"], d["eps"])
    k_r = rope(ckr[..., None, d["kv_rank"]:], d["theta"])[:, :, 0]
    k_nope = mm(c, w["w_kvb_k"]).reshape(b, s, H, -1)
    v = mm(c, w["w_kvb_v"]).reshape(b, s, H, -1)
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    def head(qkv):                  # a head at a time: [b, s, s] alive
        qh, kh, vh = qkv
        kh = jnp.concatenate([kh, k_r], -1)
        sc = jnp.einsum("bqd,bkd->bqk", qh, kh, precision=HI) \
            * d["score_scale"]
        sc = jnp.where(seen, sc, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(sc, axis=-1), vh,
                          precision=HI)
    o = jax.lax.map(jax.checkpoint(head),
                    tuple(jnp.moveaxis(t, 2, 0) for t in (q, k_nope, v)))
    return mm(jnp.moveaxis(o, 0, 2).reshape(b, s, -1), w["wo"])


def _route(u, w, d, mm):
    s = jax.nn.sigmoid(mm(u, w["w_router"]))
    top, idx = jax.lax.top_k(s, d["k"])
    return idx, d["gate_scale"] * top / (
        jnp.sum(top, -1, keepdims=True) + 1e-20)


def _experts(u, w, d, mm):
    idx, g = _route(u, w, d, mm)

    @jax.checkpoint
    def expert(we):
        wgu, wd, e = we
        f = wd.shape[0]
        mine = jnp.sum(jnp.where(idx == e + d["first"], g, 0.0), -1)
        return mine[..., None] * _swiglu(u, wgu[:, :f], wgu[:, f:], wd, mm)
    held = (w["we_gate_up"], w["we_down"],
            jnp.arange(d["held"], dtype=idx.dtype))
    routed, _ = jax.lax.scan(lambda acc, we: (acc + expert(we), None),
                             jnp.zeros_like(u), held)
    return routed + _swiglu(u, w["ws_gate"], w["ws_up"], w["ws_down"], mm)


def _block(x, w, dims, precision, ffn):
    """A row at a time (``jax.lax.map`` over the rows given, each under
    ``jax.checkpoint``): the backward then holds one row's
    activations."""
    d = dict(dims)
    mm = functools.partial(matmul, precision=precision)

    @jax.checkpoint
    def row(h):
        h = h[None]
        h = h + _attention(rms_norm(h, w["ln1"], d["eps"]), w, d, mm)
        return (h + ffn(rms_norm(h, w["ln2"], d["eps"]), w, d, mm))[0]
    return jax.lax.map(row, x), jnp.zeros((), F32)


def dense_block(x, w, dims, precision="f32"):
    return _block(x, w, dims, precision, lambda u, w, d, mm: _swiglu(
        u, w["w_gate"], w["w_up"], w["w_down"], mm))


def moe_block(x, w, dims, precision="f32"):
    return _block(x, w, dims, precision, _experts)


KINDS = {
    "mla_dense": (ATTN_LEAVES + ("w_gate", "w_up", "w_down"), dense_block),
    "mla_moe": (ATTN_LEAVES + ("w_router", "we_gate_up", "we_down",
                               "ws_gate", "ws_up", "ws_down"), moe_block),
}
