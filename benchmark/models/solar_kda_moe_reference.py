"""Plain reference of the family ``solar_kda_moe`` (``model_type:
solar_open2``): a MIXER — Kimi Delta Attention (Kimi Linear,
arXiv:2510.26692: a delta rule whose decay is a vector a head, behind a
depthwise causal convolution) or, on the layers of ``gqa_layers``,
softmax grouped-query attention whose output is gated — before routed
SwiGLU experts beside a shared one, on one residual stream; the default
ends (``embed``, ``final_norm``, ``lm_head``).  Float32, every matrix
product with a weight through ``reference.matmul`` so that the int8
CONTROL reaches it — the router's and the low-rank maps' too; nothing of
the program is imported.

    y  = rms_norm(h; ln1);   h1 = h + Mixer(y)
    u  = rms_norm(h1; ln2);  h2 = h1 + Experts(u)
    Experts(u) = sum over the picks whose expert is HELD of
                   g_e (silu(u . w_gate_e) * (u . w_up_e)) . w_down_e
               + (silu(u . ws_gate) * (u . ws_up)) . ws_down       (shared)
      s = sigmoid(u . w_router)  [published experts];  picks = the top k
      g_e = scale * s_e / (sum of the picked s + 1e-20)
    gqa_gated_moe:
      q, k, v = y . wq [H, d], y . wk [KV, d], y . wv [KV, d];  z = y . wg
      a = softmax(q k^T / sqrt(d) over j <= i) v      no rotation, no norm
      Mixer = (a * sigmoid(z)) . wo
    kda_moe (H heads of K keys x K values):
      [q | k | v] = y . w_qkv;   q, k, v = silu(conv(.))   depthwise,
          causal, ``short_conv_kernel_size`` taps [conv_q | conv_k |
          conv_v], zeros before the row, no bias: 4 shifted adds
      q = q / sqrt(sum_K q^2 + 1e-6) / sqrt(K);  k = k / sqrt(sum_K k^2 +
          1e-6)                                          a head at a time
      g = -exp(A_log_h) softplus((y . w_fa) . w_fb + dt_bias)   [s, H, K]
      beta = 2 sigmoid(y . w_beta)                              [s, H]
      S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t                         S [K, K] a head, S_0 = 0
      Mixer = (rms_norm(o_t; o_norm [K], a head at a time)
               * sigmoid((y . w_ga) . w_gb + gate_b)) . wo
    No auxiliary loss: the block's scalar is 0.

KDA here is the RECURRENCE, position by position under ``lax.scan`` with
every head's state at once (``[H, K, K]``: 4 MB a row at 64 heads of 128)
— not the chunked identity the program's kernels use, so that the two
share no algorithm.  Its backward would hold a state a position (64 GB at
16,384); the positions run in blocks of ``KDA_BLOCK`` under
``jax.checkpoint``: one state a block is kept (1 GB) and a block's own
states (268 MB) are formed again.

DEPARTURES from the published model and what the catalog row leaves
open, each also under ``assumed`` in the configuration's file:
  * ``solar_open2`` is in no ``transformers`` on this machine.  The
    delta rule, the l2 norm of q and k and the attention gated by the
    sigmoid of a second q-sized projection follow
    ``transformers/models/qwen3_next/modeling_qwen3_next.py`` (4.57.6:
    ``torch_recurrent_gated_delta_rule`` :522, ``l2norm`` :436, the
    gated attention :362-396) with the decay a vector a head (Kimi
    Linear's KDA) instead of a scalar; the router follows
    ``glm4_moe/modeling_glm4_moe.py`` :227-271 with its selection-only
    correction bias zero and not held.
  * the decay is ``-exp(A_log) softplus(f(y) + dt_bias)`` with ``A_log``
    a head and ``dt_bias`` a channel, f and the output gate's map the
    low-rank pairs ``hidden -> head_dim -> heads x head_dim``
    (``kda_use_full_proj: false``; Kimi Linear's ``f_a_proj`` /
    ``f_b_proj`` and ``g_a_proj`` / ``g_b_proj``, the latter with a
    bias); beta is ``2 sigmoid`` (``kda_allow_neg_eigval``).
  * the GQA layer's gate is a matrix of its own, ``wg [C, H d]`` (HF
    reads it out of a doubled ``q_proj``: the same function).
  * an expert's gate and up matrices are one leaf, ``we_gate_up
    [experts, C, 2 F]`` (gate | up).
  * THE SHARE: the experts held are ``expert_first .. + n_routed_experts
    - 1`` of ``published.n_routed_experts``; what the absent experts
    would add is left out, here as in the program.  The shared expert is
    whole on every chip.

Attention runs a head at a time (``lax.map`` under ``jax.checkpoint``:
one head's scores at 16,384 are 1.07 GB in float32), the routed sum is a
masked loop over the held experts (every expert sees every token; no
sort, no kernel), and a block takes its rows one at a time.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..reference import F32, HI, matmul, rms_norm, rope

KDA_LEAVES = ("ln1", "w_qkv", "conv_q", "conv_k", "conv_v", "w_fa", "w_fb",
              "A_log", "dt_bias", "w_beta", "w_ga", "w_gb", "gate_b",
              "o_norm", "wo")
ATTN_LEAVES = ("ln1", "wq", "wk", "wv", "wg", "wo")
MOE_LEAVES = ("ln2", "w_router", "we_gate_up", "we_down", "ws_gate",
              "ws_up", "ws_down")
KDA_BLOCK = 64      # positions between the states the backward keeps
L2_EPS = 1e-6


def dict_tuple(**kw):
    """Hashable, and read by name: ``dict(dims)``."""
    return tuple(kw.items())


def dims_of(conf: dict):
    kda = conf["linear_attn_config"]
    return dict_tuple(
        heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        rotate=bool(conf["use_rope"]), theta=float(conf["rope_theta"]),
        eps=float(conf["rms_norm_eps"]),
        kda_heads=kda["num_heads"], kda_dim=kda["head_dim"],
        taps=kda["short_conv_kernel_size"],
        beta_scale=2.0 if conf["kda_allow_neg_eigval"] else 1.0,
        k=conf["num_experts_per_tok"],
        scale=float(conf["routed_scaling_factor"]),
        first=conf["expert_first"], held=conf["n_routed_experts"])


def layer_kinds(conf: dict):
    return tuple("gqa_gated_moe" if i in conf["gqa_layers"] else "kda_moe"
                 for i in range(conf["num_hidden_layers"]))


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _delta_rule(q, k, v, g, beta):
    """q, k, v, g [s, H, K], beta [s, H] -> o [s, H, K]: the recurrence,
    a position at a time, in blocks the backward recomputes."""
    s, heads, width = q.shape
    block = math.gcd(s, KDA_BLOCK)

    def step(state, inp):                           # state [H, K, V]
        qt, kt, vt, gt, bt = inp
        state = state * jnp.exp(gt)[..., None]
        held = jnp.einsum("hk,hkv->hv", kt, state, precision=HI)
        state = state + jnp.einsum("hk,hv->hkv", kt * bt[:, None],
                                   vt - held, precision=HI)
        return state, jnp.einsum("hk,hkv->hv", qt, state, precision=HI)
    walk = jax.checkpoint(lambda state, inp: jax.lax.scan(step, state, inp))
    cut = lambda a: a.reshape(s // block, block, *a.shape[1:])
    _, o = jax.lax.scan(walk, jnp.zeros((heads, width, width), F32),
                        tuple(cut(a) for a in (q, k, v, g, beta)))
    return o.reshape(s, heads, width)


def _kda(y, w, d, mm):
    """y [1, s, C] (normed) -> [1, s, C]."""
    s = y.shape[1]
    heads, width, taps = d["kda_heads"], d["kda_dim"], d["taps"]
    conv_w = jnp.concatenate([w["conv_q"], w["conv_k"], w["conv_v"]])
    qkv = jnp.pad(mm(y, w["w_qkv"]), ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(qkv[:, i:i + s] * conv_w[:, i]
                          for i in range(taps)))
    q, k, v = (a.reshape(s, heads, width) for a in jnp.split(qkv[0], 3, -1))
    q, k = _l2norm(q) / math.sqrt(width), _l2norm(k)
    low = lambda a, b: mm(mm(y, w[a]), w[b])[0]
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
        low("w_fa", "w_fb").reshape(s, heads, width)
        + w["dt_bias"].reshape(heads, width))
    beta = d["beta_scale"] * jax.nn.sigmoid(mm(y, w["w_beta"])[0])
    o = rms_norm(_delta_rule(q, k, v, g, beta), w["o_norm"], d["eps"])
    gate = jax.nn.sigmoid(low("w_ga", "w_gb") + w["gate_b"])
    return mm((o.reshape(s, -1) * gate)[None], w["wo"])


def _attention(y, w, d, mm):
    """y [1, s, C] (normed) -> [1, s, C]."""
    b, s, _ = y.shape
    n, nkv, hd = d["heads"], d["kv_heads"], d["head_dim"]
    q = mm(y, w["wq"]).reshape(b, s, n, hd)
    k = mm(y, w["wk"]).reshape(b, s, nkv, hd)
    v = mm(y, w["wv"]).reshape(b, s, nkv, hd)
    if d["rotate"]:
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
    o = jnp.moveaxis(o, 0, 2).reshape(b, s, -1)
    return mm(o * jax.nn.sigmoid(mm(y, w["wg"])), w["wo"])


def _route(u, w, d, mm):
    s = jax.nn.sigmoid(mm(u, w["w_router"]))
    top, idx = jax.lax.top_k(s, d["k"])
    return idx, d["scale"] * top / (jnp.sum(top, -1, keepdims=True) + 1e-20)


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
    shared = mm(jax.nn.silu(mm(u, w["ws_gate"])) * mm(u, w["ws_up"]),
                w["ws_down"])
    return routed + shared


def _block(x, w, dims, precision, mixer):
    d = dict(dims)
    mm = functools.partial(matmul, precision=precision)

    @jax.checkpoint
    def row(h):
        h = h[None]
        h = h + mixer(rms_norm(h, w["ln1"], d["eps"]), w, d, mm)
        u = rms_norm(h, w["ln2"], d["eps"])
        idx, g = _route(u, w, d, mm)
        return (h + _experts(u, idx, g, w, d, mm))[0]
    return jax.lax.map(row, x), jnp.zeros((), F32)


def kda_block(x, w, dims, precision="f32"):
    return _block(x, w, dims, precision, _kda)


def attention_block(x, w, dims, precision="f32"):
    return _block(x, w, dims, precision, _attention)


KINDS = {"kda_moe": (KDA_LEAVES + MOE_LEAVES, kda_block),
         "gqa_gated_moe": (ATTN_LEAVES + MOE_LEAVES, attention_block)}
