"""The plain reference's machinery, the same for every architecture:
the matrix product (float32 at ``Precision.HIGHEST``, and the int8
CONTROL), RMSNorm and RoPE, the trunk (embedding, the blocks in turn,
final norm, untied head), the head's loss and gradients, adafactor, and
the loops that drive them.  The BLOCK is the family's:
``benchmark/models/<family>_reference.py`` (``blk`` below) gives its
leaf names, its ``dims_of(conf)`` and ``block(x, w, dims, precision)``
-> (output, a scalar added to the loss); ``benchmark/models/__init__.py``
states the contract.
Nothing here imports the program, and it is handed only what the
benchmark itself made from the seed (weights, token ids).

Serving: :func:`serve_gaps` runs one full forward pass over a prompt
and the tokens that were served after it, layer by layer with each
layer's weights cast up in turn.  Training: :class:`TrainReference`
follows the first steps of the job (loss, gradients, adafactor) in
blocks of rows, layer by layer.

``precision="int8"`` is the CONTROL, never the reference: the same
mathematics with every matrix product's operands rounded to an int8
grid (per row of the activations, per column of the weights), the
nearest precision below the bf16 the configurations state.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


# ---------------------------------------------------------------------------
# matrix products: the reference's, and the control's
# ---------------------------------------------------------------------------
def _fq(a, axis):
    """Round to a symmetric int8 grid along ``axis``."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(a / s) * s


@jax.custom_vjp
def _mm_int8(x, w):
    return jnp.matmul(_fq(x, -1), _fq(w, 0), precision=HI)


def _mm_int8_fwd(x, w):
    return _mm_int8(x, w), (x, w)


def _mm_int8_bwd(res, g):
    x, w = res
    gq = _fq(g, -1)
    dx = jnp.matmul(gq, _fq(w, 0).T, precision=HI)
    x2 = _fq(x, -1).reshape(-1, x.shape[-1])
    dw = jnp.matmul(x2.T, gq.reshape(-1, g.shape[-1]), precision=HI)
    return dx, dw


_mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)


def matmul(x, w, precision: str):
    if precision == "f32":
        return jnp.matmul(x, w, precision=HI)
    if precision == "int8":
        return _mm_int8(x, w)
    raise ValueError(f"unknown precision {precision!r}")


# ---------------------------------------------------------------------------
# what every block is made of
# ---------------------------------------------------------------------------
def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, theta):
    """Rotate-half RoPE on [b, s, heads, d] at positions 0..s-1."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------------------
# the trunk around the family's block
# ---------------------------------------------------------------------------
TOP_LEAVES = ("embed", "final_norm", "lm_head")


@functools.partial(jax.jit, static_argnames=("blk", "dims", "precision"))
def _block_up(x, w, aux, blk, dims, precision):
    """The family's block with the layer's leaves cast up: its output,
    and ``aux`` plus its term of the loss (summed here, so that the
    sum costs no program of its own on the device)."""
    y, term = blk.block(x, jax.tree_util.tree_map(
        lambda a: a.astype(F32), w), dims, precision)
    return y, aux + term


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head_rows(x, final_norm, lm_head, eps, precision):
    return matmul(rms_norm(x, final_norm.astype(F32), eps),
                  lm_head.astype(F32), precision)


# ---------------------------------------------------------------------------
# serving: the gap of each served token below the reference's best
# ---------------------------------------------------------------------------
def _pad_len(n: int) -> int:
    """Right-pad to a power of two (>= 256): causal, so no earlier
    position changes, and few shapes compile."""
    return max(256, 1 << (n - 1).bit_length())


def _layer_of(params, i, dev):
    return {k: jax.device_put(v[i], dev) if dev is not None else v[i]
            for k, v in params["blocks"].items()}


def forward_rows(blk, params, conf, tokens, rows, precision="f32",
                 dev=None):
    """Logits [len(rows), vocab] (float32, on the host) of one sequence
    at positions ``rows``.  ``params`` is the benchmark's tree (stacked
    layers, any float type, any layout); ``dev`` gathers a sharded
    layer onto one device first."""
    n = len(tokens)
    toks = np.zeros((_pad_len(n),), np.int64)
    toks[:n] = tokens
    dims = blk.dims_of(conf)
    emb = params["embed"]
    x = jnp.take(emb, jnp.asarray(toks), axis=0).astype(F32)[None]
    if dev is not None:
        x = jax.device_put(x, dev)
    for i in range(conf["num_hidden_layers"]):
        x, _ = _block_up(x, _layer_of(params, i, dev), np.float32(0), blk,
                         dims, precision)
    put = (lambda a: jax.device_put(a, dev)) if dev is not None \
        else (lambda a: a)
    out = _head_rows(x[0, jnp.asarray(rows)], put(params["final_norm"]),
                     put(params["lm_head"]),
                     float(conf["rms_norm_eps"]), precision)
    return np.asarray(out)


def serve_gaps(blk, params, conf, prompt, served, control=False,
               dev=None):
    """For one finished request: at each served token's position, how
    far the reference's logit of that token lies below the reference's
    best.  ``control=True`` reads instead the gap of the token the int8
    computation puts first at that position (it decodes nothing)."""
    seq = list(prompt) + list(served[:-1])
    rows = np.arange(len(prompt) - 1, len(seq))
    ref = forward_rows(blk, params, conf, seq, rows, "f32", dev)
    if control:
        low = forward_rows(blk, params, conf, seq, rows, "int8", dev)
        picked = low.argmax(-1)
    else:
        picked = np.asarray(served, np.int64)
    return ref.max(-1) - ref[np.arange(len(rows)), picked]


# ---------------------------------------------------------------------------
# training: loss, gradients and adafactor over the first steps
# ---------------------------------------------------------------------------
# Rows that go through a block together.  A block's own term of the
# loss is a mean of per-row quantities (the contract in
# ``benchmark/models/__init__.py``), so this number changes no result.
ROW_BLOCK = 2


def _factored(shape) -> bool:
    return len(shape) >= 2 and shape[-1] >= 128 and shape[-2] >= 128


@functools.partial(jax.jit, static_argnames=("blk", "dims", "precision"))
def _block_bwd(x, w, g, g_aux, blk, dims, precision):
    """Gradients of a block's input and leaves from ``g``, the gradient
    of its output, and ``g_aux``, the weight of its own term in the
    step's loss."""
    _, vjp = jax.vjp(lambda x, w: blk.block(x, w, dims, precision), x, w)
    return vjp((g, g_aux))


@functools.partial(jax.jit, static_argnames=("eps", "precision", "total"))
def _head_loss(x, final_norm, lm_head, targets, eps, precision, total):
    """Sum of the rows' NLL over ``total`` tokens, and its gradients."""
    def f(x, fn, lm):
        logits = matmul(rms_norm(x, fn, eps), lm, precision)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
        return jnp.sum(lse - tgt) / total
    return jax.value_and_grad(f, argnums=(0, 1, 2))(x, final_norm, lm_head)


@functools.partial(jax.jit, donate_argnums=(0,))
def _acc(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


@functools.partial(jax.jit, donate_argnums=(0, 2),
                   static_argnames=("lr", "wd"))
def _adafactor_leaf(p, g, st, t, lr, wd):
    """Adafactor (Shazeer & Stern 2018) as the job states it: factored
    second moment for matrices, beta2_t = 1 - t^-0.8, update clipped to
    rms 1, step scaled by max(1e-3, rms(p)), no momentum."""
    beta2 = 1.0 - t ** -0.8
    g2 = g * g + 1e-30
    if "vr" in st:
        vr = beta2 * st["vr"] + (1 - beta2) * jnp.mean(g2, axis=-1)
        vc = beta2 * st["vc"] + (1 - beta2) * jnp.mean(g2, axis=-2)
        new = {"vr": vr, "vc": vc}
        r = vr / jnp.mean(vr, axis=-1, keepdims=True)
        u = g * jax.lax.rsqrt(r[..., :, None] * vc[..., None, :])
    else:
        v = beta2 * st["v"] + (1 - beta2) * g2
        new = {"v": v}
        u = g * jax.lax.rsqrt(v)
    rms = lambda a: jnp.sqrt(jnp.mean(jnp.square(a)) + 1e-30)
    u = u / jnp.maximum(1.0, rms(u))
    alpha = lr * jnp.maximum(1e-3, rms(p))
    return p * (1 - alpha * wd) - alpha * u, new


def _opt_init(p):
    if _factored(p.shape):
        return {"vr": jnp.zeros(p.shape[:-1], F32),
                "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], F32)}
    return {"v": jnp.zeros(p.shape, F32)}


class TrainReference:
    """Float32 copy of the job's state, stepped leaf by leaf.

    ``leaf(path)`` returns the benchmark's stacked float32 leaf for a
    path such as ``("blocks", <a leaf of the family's block>)`` or
    ``("embed",)``; the stacked block leaves are split into per-layer
    arrays so that a layer's gradient can be accumulated and applied
    alone."""

    def __init__(self, blk, conf, leaf, job, precision="f32"):
        self.blk, self.conf, self.job = blk, conf, job
        self.precision = precision
        self.dims = blk.dims_of(conf)
        self.eps = float(conf["rms_norm_eps"])
        self.L = conf["num_hidden_layers"]
        self.leaf = leaf
        self.layers = [dict() for _ in range(self.L)]
        for nm in blk.BLOCK_LEAVES:
            stacked = leaf(("blocks", nm))
            for i in range(self.L):
                self.layers[i][nm] = stacked[i]
            del stacked
        self.top = {nm: leaf((nm,)) for nm in TOP_LEAVES}
        self.opt_layers = [jax.tree_util.tree_map(_opt_init, w)
                           for w in self.layers]
        self.opt_top = jax.tree_util.tree_map(_opt_init, self.top)
        self.t = 0
        self.grad_norms = None      # of the FIRST step, per stacked leaf

    def _blocks_up(self, x):
        """Every layer's input and the last one's output, and the sum
        of the blocks' own loss terms.  A function of its own, so that
        no local outlives it: the backward pass pops the activations
        one by one, and one more [rows, s, hidden] kept alive shows in
        the device's peak."""
        acts, aux = [x], np.float32(0)
        for w in self.layers:
            y, aux = _block_up(acts[-1], w, aux, self.blk, self.dims,
                               self.precision)
            acts.append(y)
        return acts, aux

    def step(self, tokens) -> float:
        """One training step on ``tokens`` [B, S+1]; returns its loss:
        the tokens' mean NLL plus every block's own term, each weighted
        by its rows' share of the batch.
        Rows go forward in blocks; the backward pass then walks the
        layers from the top, sums a layer's gradient over the blocks,
        applies it and frees it before the next layer, so that only one
        layer's gradient is alive at a time."""
        tokens = np.asarray(tokens)
        B, S1 = tokens.shape
        total, eps = B * (S1 - 1), self.eps
        first = self.t == 0
        self.t += 1
        lr, wd = float(self.job["lr"]), float(self.job["weight_decay"])
        t = jnp.asarray(self.t, F32)
        sq = lambda a: float(jnp.sum(jnp.square(a)))
        norms = {}

        xs, gxs, inps, shares, g_top, loss = [], [], [], [], None, 0.0
        for r0 in range(0, B, ROW_BLOCK):
            tok = jnp.asarray(tokens[r0:r0 + ROW_BLOCK])
            inp, tgt = tok[:, :-1], tok[:, 1:]
            share = np.float32(tok.shape[0] / B)
            acts, aux = self._blocks_up(
                jnp.take(self.top["embed"], inp, axis=0))
            part, (gx, gfn, glm) = _head_loss(
                acts.pop(), self.top["final_norm"], self.top["lm_head"],
                tgt, eps, self.precision, total)
            loss += float(part) + float(share) * float(aux)
            shares.append(share)
            gt = {"final_norm": gfn, "lm_head": glm}
            g_top = gt if g_top is None else _acc(g_top, gt)
            xs.append(acts)
            gxs.append(gx)
            inps.append(inp)

        for i in reversed(range(self.L)):
            gw = None
            for b in range(len(xs)):
                gxs[b], g = _block_bwd(xs[b].pop(), self.layers[i], gxs[b],
                                       shares[b], self.blk, self.dims,
                                       self.precision)
                gw = g if gw is None else _acc(gw, g)
            for nm in list(self.layers[i]):
                g = gw.pop(nm)
                if first:
                    norms[("blocks", nm)] = \
                        norms.get(("blocks", nm), 0.0) + sq(g)
                self.layers[i][nm], self.opt_layers[i][nm] = \
                    _adafactor_leaf(self.layers[i][nm], g,
                                    self.opt_layers[i][nm], t, lr, wd)
        gemb = jnp.zeros_like(self.top["embed"])
        for inp, gx in zip(inps, gxs):
            gemb = gemb.at[inp].add(gx)
        g_top["embed"] = gemb
        for nm in list(self.top):
            g = g_top.pop(nm)
            if first:
                norms[(nm,)] = sq(g)
            self.top[nm], self.opt_top[nm] = _adafactor_leaf(
                self.top[nm], g, self.opt_top[nm], t, lr, wd)
        if first:
            self.grad_norms = {k: math.sqrt(v) for k, v in norms.items()}
        return loss

    def change_norms(self) -> dict:
        """||p_now - p_0|| per stacked leaf; p_0 is made again from the
        seed, one leaf at a time."""
        out = {}
        sq = lambda a: float(jnp.sum(jnp.square(a)))
        for nm in self.layers[0]:
            p0 = self.leaf(("blocks", nm))
            out[("blocks", nm)] = math.sqrt(sum(
                sq(self.layers[i][nm] - p0[i]) for i in range(self.L)))
            del p0
        for nm in self.top:
            out[(nm,)] = math.sqrt(sq(self.top[nm] - self.leaf((nm,))))
        return out
