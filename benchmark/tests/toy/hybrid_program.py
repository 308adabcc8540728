"""ONE plain function of the whole toy hybrid model (written apart from
``hybrid_block_reference.py``: the decayed sum as an explicit matrix of
powers, all rows at once), and a train step on it by whole-model
autodiff — what the reference's machinery is held against, and the
stand-in program of the seam's tests."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import reference

HI = jax.lax.Precision.HIGHEST


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _dot(a, b):
    return jnp.matmul(a, b, precision=HI)


def logits(p, ids, conf, kinds=None):
    """[B, S] ids -> [B, S, vocab]; ``kinds`` overrides the order."""
    kinds = kinds or conf["layer_types"][:conf["num_hidden_layers"]]
    eps, res = conf["rms_norm_eps"], conf["residual_multiplier"]
    n, nkv, d = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    B, S = ids.shape
    x = conf["embedding_multiplier"] * p["embed"][ids]
    seen = {"mix": 0, "attention": 0}
    t = jnp.arange(S)
    for kind in kinds:
        w = {nm: a[seen[kind]] for nm, a in p["blocks"][kind].items()}
        seen[kind] += 1
        y = _norm(x, w["ln1"], eps)
        if kind == "mix":
            a = jax.nn.sigmoid(w["decay"])
            lag = t[:, None] - t[None, :]                       # [t, s]
            powers = jnp.where(lag[..., None] >= 0,
                               a ** jnp.maximum(lag, 0)[..., None], 0.0)
            mixed = jnp.einsum("tsh,bsh->bth", powers, _dot(y, w["w_in"]),
                               precision=HI)
            x = x + res * _dot(mixed, w["w_out"])
        else:
            q = _dot(y, w["wq"]).reshape(B, S, n, d)
            k = _dot(y, w["wk"]).reshape(B, S, nkv, d)
            v = _dot(y, w["wv"]).reshape(B, S, nkv, d)
            outs = []
            for head in range(n):
                kh, vh = k[:, :, head // (n // nkv)], v[:, :, head // (n // nkv)]
                sc = jnp.einsum("bqd,bkd->bqk", q[:, :, head], kh,
                                precision=HI) * conf["attention_multiplier"]
                sc = jnp.where(t[:, None] >= t[None, :], sc, -jnp.inf)
                outs.append(jnp.einsum("bqk,bkd->bqd",
                                       jax.nn.softmax(sc, -1), vh,
                                       precision=HI))
            x = x + res * _dot(jnp.concatenate(outs, -1), w["wo"])
        y = _norm(x, w["ln2"], eps)
        x = x + res * _dot(jax.nn.silu(_dot(y, w["w_gate"]))
                           * _dot(y, w["w_up"]), w["w_down"])
    return _dot(_norm(x, p["final_norm"], eps), p["embed"].T) \
        / conf["logits_scaling"]


def loss(p, tokens, conf):
    lg = logits(p, tokens[:, :-1], conf)
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(nll)


def init_adafactor_state(params) -> dict:
    """The program's layout: ``moments`` mirrors the parameters, a
    stacked block leaf holds its layers' states stacked."""
    def of(path, p):
        one = reference._opt_init
        return jax.vmap(one)(p) if path[0].key == "blocks" else one(p)
    return {"moments": jax.tree_util.tree_map_with_path(of, params),
            "t": jnp.zeros((), jnp.float32)}


def make_train_step(cfg, mesh, lr, weight_decay, optimizer):
    """Whole-model autodiff, then adafactor a LAYER's leaf at a time."""
    rule = reference._adafactor_leaf.__wrapped__

    def step(params, opt, tokens):
        value, grads = jax.value_and_grad(loss)(params, tokens, cfg.conf)
        t = opt["t"] + 1.0

        def update(path, p, g, st):
            one = lambda p, g, st: rule(p, g, st, t, lr, weight_decay)
            return jax.vmap(one)(p, g, st) if path[0].key == "blocks" \
                else one(p, g, st)
        both = jax.tree_util.tree_map_with_path(
            update, params, grads, opt["moments"])
        is_pair = lambda x: isinstance(x, tuple)
        return (jax.tree_util.tree_map(lambda b: b[0], both, is_leaf=is_pair),
                {"moments": jax.tree_util.tree_map(lambda b: b[1], both,
                                                   is_leaf=is_pair),
                 "t": t}, value)
    return jax.jit(step)
