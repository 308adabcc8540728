"""Plain reference of the family ``granite_hybrid`` (HF
``GraniteMoeHybrid*`` with ``num_local_experts`` 0): layers of two
kinds in the order ``layer_types`` gives, cut to ``num_hidden_layers``;
float32, every matrix product through ``reference.matmul`` so that the
int8 CONTROL reaches it; nothing of the program is imported.

    ends       h0 = embedding_multiplier . E[ids]
               logits = rms_norm(h_L; final_norm) . E^T / logits_scaling
               (ONE table, read by both ends)
    a layer    h += r . mixer(rms_norm(h; ln1))
               h += r . w_down(silu(w_gate v) * (w_up v)),  v = rms_norm(h; ln2)
               r = residual_multiplier.  HF holds gate and up as one
               ``input_linear`` of 2 x intermediate; here they are the two
               leaves ``w_gate``, ``w_up`` (its first and second half).
    attention  GQA, NO rotation (``position_embedding_type: nope``),
               softmax(q k^T . attention_multiplier + causal) v, then wo
    mamba      Mamba-2, one B/C group: [z | xBC | dt] = v . w_in;
               xBC = silu(conv(xBC) + conv_b), conv causal and depthwise
               over the last ``mamba_d_conv`` positions, zeros before the
               row; [x | B | C] = xBC; D_t = softplus(dt_t + dt_bias),
               a_t = exp(-exp(A_log) D_t) a head;
               H_t = a_t H_{t-1} + D_t x_t (x) B_t,  y_t = H_t C_t + D x_t;
               out = rms_norm(y * silu(z); gate_norm) . w_out

THE RECURRENCE IS STATED WITHOUT THE PROGRAM'S CHUNKING, as the masked
quadratic form over the WHOLE row, a head at a time (``jax.lax.map``):
``y_i = sum_{j<=i} (C_i . B_j) exp(l_i - l_j) D_j x_j`` with ``l`` the
running sum of ``log a`` along the row — the recurrence unrolled: no
chunk, no state handed on.  Token by token it would be 8,192 steps of a
4 MiB state a layer whose backward keeps every step; the quadratic form
of one head over two rows of 8,192 is 0.5 GiB a matrix
(``tests/aot_compile.py reference`` says what the block's backward
needs).  ``C B^T`` is one product for all heads (one group).  A head's
function is under ``jax.checkpoint``: the loop's backward then keeps a
head's inputs and forms its matrices again, instead of every head's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..reference import F32, HI, matmul, rms_norm

KIND_NAMES = ("mamba", "attention")
MLP_LEAVES = ("ln2", "w_gate", "w_up", "w_down")
TOP_LEAVES = ("embed", "final_norm")


def dims_of(conf: dict):
    heads = conf["num_attention_heads"]
    return (heads, conf["num_key_value_heads"],
            conf["hidden_size"] // heads, float(conf["rms_norm_eps"]),
            float(conf["embedding_multiplier"]),
            float(conf["residual_multiplier"]),
            float(conf["attention_multiplier"]),
            float(conf["logits_scaling"]),
            conf["mamba_n_heads"], conf["mamba_d_head"],
            conf["mamba_d_state"], conf["mamba_d_conv"])


def layer_kinds(conf: dict):
    return tuple(conf["layer_types"][:conf["num_hidden_layers"]])


def _mlp(x, w, dims, mm):
    eps, res = dims[3], dims[5]
    v = rms_norm(x, w["ln2"], eps)
    return x + res * mm(jax.nn.silu(mm(v, w["w_gate"])) * mm(v, w["w_up"]),
                        w["w_down"])


def attention_block(x, w, dims, precision="f32"):
    n, nkv, d, eps, _, res, scale = dims[:7]
    b, s, _ = x.shape
    mm = functools.partial(matmul, precision=precision)
    y = rms_norm(x, w["ln1"], eps)
    q = mm(y, w["wq"]).reshape(b, s, n, d)
    k = jnp.repeat(mm(y, w["wk"]).reshape(b, s, nkv, d), n // nkv, axis=2)
    v = jnp.repeat(mm(y, w["wv"]).reshape(b, s, nkv, d), n // nkv, axis=2)

    def head(qkv):                  # a head at a time: [b, s, s] alive
        qh, kh, vh = qkv
        sc = jnp.einsum("bqd,bkd->bqk", qh, kh, precision=HI) * scale
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(sc, axis=-1), vh,
                          precision=HI)
    a = jax.lax.map(jax.checkpoint(head), tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    a = jnp.moveaxis(a, 0, 2).reshape(b, s, n * d)
    x = x + res * mm(a, w["wo"])
    return _mlp(x, w, dims, mm), jnp.zeros((), F32)


def _conv(x, w, bias):
    """out[t, c] = bias[c] + sum_k w[c, k] x[t - (K - 1) + k, c]."""
    k = w.shape[1]
    s = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return bias + sum(xp[:, i:i + s, :] * w[:, i] for i in range(k))


def mamba_block(x, w, dims, precision="f32"):
    eps, res = dims[3], dims[5]
    nh, p, n, _ = dims[8:]
    b, s, _ = x.shape
    di = nh * p
    mm = functools.partial(matmul, precision=precision)
    zxbcdt = mm(rms_norm(x, w["ln1"], eps), w["w_in"])
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:di + di + 2 * n],
                  zxbcdt[..., di + di + 2 * n:])
    xbc = jax.nn.silu(_conv(xbc, w["conv_w"], w["conv_b"]))
    xs, B, C = xbc[..., :di], xbc[..., di:di + n], xbc[..., di + n:]
    step = jax.nn.softplus(dt + w["dt_bias"])               # [b, s, H]
    log_a = -jnp.exp(w["A_log"]) * step
    run = jnp.cumsum(log_a, axis=1)
    cb = jnp.einsum("bin,bjn->bij", C, B, precision=HI)     # all heads'
    behind = jnp.tril(jnp.ones((s, s), bool))

    def head(inp):
        xh, stp, l = inp                    # [b, s, P], [b, s], [b, s]
        seg = jnp.where(behind, l[:, :, None] - l[:, None, :], -jnp.inf)
        m = cb * jnp.exp(seg) * stp[:, None, :]
        return jnp.einsum("bij,bjp->bip", m, xh, precision=HI)
    y = jax.lax.map(jax.checkpoint(head), (jnp.moveaxis(xs.reshape(b, s, nh, p), 2, 0),
                           jnp.moveaxis(step, 2, 0),
                           jnp.moveaxis(run, 2, 0)))        # [H, b, s, P]
    y = jnp.moveaxis(y, 0, 2) + w["D"][:, None] * xs.reshape(b, s, nh, p)
    y = rms_norm(y.reshape(b, s, di) * jax.nn.silu(z), w["gate_norm"], eps)
    x = x + res * mm(y, w["w_out"])
    return _mlp(x, w, dims, mm), jnp.zeros((), F32)


KINDS = {
    "mamba": (("ln1", "w_in", "conv_w", "conv_b", "A_log", "D", "dt_bias",
               "gate_norm", "w_out") + MLP_LEAVES, mamba_block),
    "attention": (("ln1", "wq", "wk", "wv", "wo") + MLP_LEAVES,
                  attention_block),
}


def first_input(top, ids, dims):
    return dims[4] * jnp.take(top["embed"], ids, axis=0)


def logits(top, x, dims, precision):
    return matmul(rms_norm(x, top["final_norm"], dims[3]), top["embed"].T,
                  precision) / dims[7]
