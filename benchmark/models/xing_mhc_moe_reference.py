"""Plain reference of the family ``xing_mhc_moe`` (``model_type:
xing4_0``): latent attention (MLA) before a dense SwiGLU MLP
(``mla_dense``, the first ``first_k_dense_replace`` layers) or before
sigmoid-routed experts beside shared ones (``mla_moe``), on a residual
of ``n = hc_mult`` streams (manifold-constrained hyper-connections,
arXiv:2512.24880 over arXiv:2409.19606).  Float32, every matrix product
through ``reference.matmul`` so that the int8 CONTROL reaches it — the
mixers' and the router's too; nothing of the program is imported.

The streams travel between blocks FLATTENED, ``[rows, s, n C]`` (stream
i is columns ``i C .. (i + 1) C``): the machinery never looks inside x.

    ends       X_0 = the table's row, copied into the n streams
               logits = rms_norm(sum of the streams; final_norm) . lm_head
    a sublayer F (attention; then the MLP or the expert layer), with its
    own mixer leaves phi [n C, n^2 + 2n], alpha [3], b [n^2 + 2n]:
               u = vec(X) / rms(vec(X))   (eps rms_norm_eps, no weight)
               m = u . phi
               H_pre  = sigmoid(alpha_1 m[:n] + b[:n])
               H_post = 2 sigmoid(alpha_2 m[n:2n] + b[n:2n])
               H_res  = Sinkhorn(exp(clip(alpha_3 mat(m[2n:]) + mat(b[2n:]),
                        clamp_min, clamp_max))): hc_sinkhorn_iters rounds
                        of row then column normalisation, each denominator
                        + hc_eps; mat() is row-major, row j = what output
                        stream j takes
               h = H_pre . X;  y = F(rms_norm(h; ln));  X' = H_res . X +
               H_post^T (x) y
    MLA        q = rms_norm(x w_qa; q_norm) . [w_qb_nope | w_qb_rope]
               [c | k_r] = x w_kva;  [k_nope | v] = rms_norm(c; kv_norm) .
               [w_kvb_k | w_kvb_v];  q_r and k_r rotated (rotate-half) at
               YaRN's frequencies, cos / sin times mscale / mscale_all_dim;
               k_r ONE vector a token for all heads;
               S = ([q_nope | q_r] . [k_nope | k_r]^T) (128 + 64)^-1/2 m^2,
               m = 0.1 mscale_all_dim ln(factor) + 1; causal softmax; o = P v;
               out = o w_o
    experts    s = sigmoid(x w_router) over ALL the published experts; the
               top k of s; g = routed_scaling_factor s_e / (sum of the
               picked s + 1e-20) over all k picks, held here or not;
               y = sum over the picks whose expert is HELD of g_e E_e(x) +
               E_shared(x),  E(x) = (silu(x w_g) * x w_u) w_d.
               No auxiliary loss: the block's scalar is 0.

DEPARTURES from the published description, each also under ``assumed``
in the configuration's file:
  * q_b_proj and kv_b_proj are two leaves each — all heads' nope columns
    | all heads' rope columns, all heads' k_nope | all heads' v: a fixed
    permutation of the published per-head column order, the same function.
  * the rotation is rotate-half (first half, second half), HF's form after
    its own permutation of the interleaved pairs.
  * a routed expert's gate_proj and up_proj are one leaf, ``we_gate_up
    [experts, C, 2 F]`` (gate | up): one product, the same function.
  * ``noaux_tc``'s correction bias is zero and not held (it is no
    parameter of the loss; its update rule is not built), one group.
  * multi-token prediction (the checkpoint's layer 40) is not here.
  * THE SHARE: the experts held are ``expert_first .. + n_routed_experts
    - 1`` of ``published.n_routed_experts``; what the absent experts
    would add is left out, here as in the program.

The routed sum is a plain loop over the held experts with a mask (every
expert sees every token; no sort, no kernel), attention a head at a
time and a block a row at a time, each under ``jax.checkpoint`` inside
``lax.scan`` / ``lax.map``, so that two rows of 8,192 fit beside the
machinery's own copies.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..reference import F32, HI, matmul, rms_norm

ATTN_LEAVES = ("ln1", "w_qa", "q_norm", "w_qb_nope", "w_qb_rope", "w_kva",
               "kv_norm", "w_kvb_k", "w_kvb_v", "wo")
MIXER_LEAVES = tuple(f"{pre}_{nm}" for pre in ("hc1", "hc2")
                     for nm in ("phi", "alpha", "b"))
TOP_LEAVES = ("embed", "final_norm", "lm_head")


def yarn_frequencies(dim, theta, sc):
    """The dim / 2 frequencies: each blended between its own and its
    ``factor``-th by a linear ramp over the pairs that make between
    ``beta_slow`` and ``beta_fast`` turns over the original context."""
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    turns_at = lambda t: dim * math.log(
        sc["original_max_position_embeddings"] / (t * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(turns_at(sc["beta_fast"])), 0)
    high = min(math.ceil(turns_at(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return tuple(float(f) for f in
                 inv / sc["factor"] * (1 - keep) + inv * keep)


def _mscale(sc, key):
    return 0.1 * sc[key] * math.log(sc["factor"]) + 1.0 \
        if sc["factor"] > 1 else 1.0


def dims_of(conf: dict):
    sc = conf["rope_scaling"]
    rope = conf["qk_rope_head_dim"]
    return dict_tuple(
        heads=conf["num_attention_heads"], hidden=conf["hidden_size"],
        n=conf["hc_mult"], eps=float(conf["rms_norm_eps"]),
        kv_rank=conf["kv_lora_rank"], rope=rope,
        freqs=yarn_frequencies(rope, float(conf["rope_theta"]), sc),
        cos_scale=_mscale(sc, "mscale") / _mscale(sc, "mscale_all_dim"),
        score_scale=(conf["qk_nope_head_dim"] + rope) ** -0.5
        * _mscale(sc, "mscale_all_dim") ** 2,
        k=conf["num_experts_per_tok"],
        gate_scale=float(conf["routed_scaling_factor"]),
        first=conf["expert_first"], held=conf["n_routed_experts"],
        iters=conf["hc_sinkhorn_iters"], hc_eps=float(conf["hc_eps"]),
        lo=float(conf["mhc_h_res_clamp_min"]),
        hi=float(conf["mhc_h_res_clamp_max"]))


def dict_tuple(**kw):
    """Hashable, and read by name: ``dict(dims)``."""
    return tuple(kw.items())


def layer_kinds(conf: dict):
    dense = min(conf["first_k_dense_replace"], conf["num_hidden_layers"])
    return ("mla_dense",) * dense + ("mla_moe",) * (
        conf["num_hidden_layers"] - dense)


def _swiglu(x, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


def _rotate(x, d):
    """Rotate-half on [rows, s, (heads,) rope] at positions 0..s-1."""
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] \
        * jnp.asarray(d["freqs"], F32)[None, :]
    cos, sin = jnp.cos(ang) * d["cos_scale"], jnp.sin(ang) * d["cos_scale"]
    if x.ndim == 4:
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., :d["rope"] // 2], x[..., d["rope"] // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, w, d, mm):
    b, s, _ = x.shape
    H, eps = d["heads"], d["eps"]
    qa = rms_norm(mm(x, w["w_qa"]), w["q_norm"], eps)
    q = jnp.concatenate(
        [mm(qa, w["w_qb_nope"]).reshape(b, s, H, -1),
         _rotate(mm(qa, w["w_qb_rope"]).reshape(b, s, H, -1), d)], -1)
    ckr = mm(x, w["w_kva"])
    c = rms_norm(ckr[..., :d["kv_rank"]], w["kv_norm"], eps)
    k_r = _rotate(ckr[..., d["kv_rank"]:], d)
    k_nope = mm(c, w["w_kvb_k"]).reshape(b, s, H, -1)
    v = mm(c, w["w_kvb_v"]).reshape(b, s, H, -1)
    seen = jnp.tril(jnp.ones((s, s), bool))

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


def _experts(x, w, d, mm):
    s = jax.nn.sigmoid(mm(x, w["w_router"]))
    top, idx = jax.lax.top_k(s, d["k"])
    g = d["gate_scale"] * top / (jnp.sum(top, -1, keepdims=True) + 1e-20)

    @jax.checkpoint
    def expert(we):
        wgu, wd, e = we
        f = wd.shape[0]
        mine = jnp.sum(jnp.where(idx == e + d["first"], g, 0.0), -1)
        return mine[..., None] * _swiglu(x, wgu[:, :f], wgu[:, f:], wd, mm)
    held = (w["we_gate_up"], w["we_down"],
            jnp.arange(d["held"], dtype=idx.dtype))
    routed, _ = jax.lax.scan(lambda acc, we: (acc + expert(we), None),
                             jnp.zeros_like(x), held)
    return routed + _swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"], mm)


def _sublayer(x, w, pre, ln, fn, d, mm):
    """One mixer around ``fn`` on the flattened streams x [rows, s, n C].
    The maps are held with the TOKENS LAST, ``[n, n, rows s]``: a
    trailing ``[n, n]`` would be padded to a whole tile a token, sixty-four
    times its size, in each of Sinkhorn's forty steps."""
    n, rows, s = d["n"], x.shape[0], x.shape[1]
    u = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                          + d["eps"])
    m = mm(u, w[pre + "_phi"]).reshape(rows * s, -1).T      # [n^2 + 2n, T]
    alpha, bias = w[pre + "_alpha"], w[pre + "_b"][:, None]
    h_pre = jax.nn.sigmoid(alpha[0] * m[:n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + bias[n:2 * n])
    r = (alpha[2] * m[2 * n:] + bias[2 * n:]).reshape(n, n, rows * s)
    r = jnp.exp(jnp.clip(r, d["lo"], d["hi"]))
    for _ in range(d["iters"]):
        r = r / (jnp.sum(r, 1, keepdims=True) + d["hc_eps"])      # rows
        r = r / (jnp.sum(r, 0, keepdims=True) + d["hc_eps"])      # columns
    streams = x.reshape(rows * s, n, -1)
    h = jnp.einsum("nt,tnc->tc", h_pre, streams, precision=HI)
    y = fn(rms_norm(h.reshape(rows, s, -1), w[ln], d["eps"]))
    out = jnp.einsum("jit,tic->tjc", r, streams, precision=HI) \
        + h_post.T[:, :, None] * y.reshape(rows * s, 1, -1)
    return out.reshape(x.shape)


def _block(x, w, dims, precision, ffn):
    """A row at a time (``jax.lax.map`` over the rows given, each under
    ``jax.checkpoint``): the backward then holds one row's activations,
    beside the six ``[2, s, n C]`` arrays the machinery keeps."""
    d = dict(dims)
    mm = functools.partial(matmul, precision=precision)

    @jax.checkpoint
    def row(x):
        x = x[None]
        x = _sublayer(x, w, "hc1", "ln1",
                      lambda v: _attention(v, w, d, mm), d, mm)
        x = _sublayer(x, w, "hc2", "ln2", lambda v: ffn(v, w, d, mm), d, mm)
        return x[0]
    return jax.lax.map(row, x), jnp.zeros((), F32)


def dense_block(x, w, dims, precision="f32"):
    return _block(x, w, dims, precision, lambda v, w, d, mm: _swiglu(
        v, w["w_gate"], w["w_up"], w["w_down"], mm))


def moe_block(x, w, dims, precision="f32"):
    return _block(x, w, dims, precision, _experts)


KINDS = {
    "mla_dense": (ATTN_LEAVES + ("ln2",) + MIXER_LEAVES
                  + ("w_gate", "w_up", "w_down"), dense_block),
    "mla_moe": (ATTN_LEAVES + ("ln2",) + MIXER_LEAVES
                + ("w_router", "we_gate_up", "we_down",
                   "ws_gate", "ws_up", "ws_down"), moe_block),
}


def first_input(top, ids, dims):
    return jnp.tile(jnp.take(top["embed"], ids, axis=0),
                    (1, 1, dict(dims)["n"]))


def logits(top, x, dims, precision):
    """x [..., n C], or [..., C] where the streams are already one."""
    d = dict(dims)
    x = x.reshape(*x.shape[:-1], -1, d["hidden"]).sum(-2)
    return matmul(rms_norm(x, top["final_norm"], d["eps"]), top["lm_head"],
                  precision)
