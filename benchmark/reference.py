"""The plain reference's machinery, the same for every architecture:
the matrix product (float32 at ``Precision.HIGHEST``, and the int8
CONTROL), RMSNorm and RoPE, the walk over the layers in their order,
the NLL over the logits and its gradients, the sum of a leaf's gradient
over every use of it, adafactor, and the loops that drive them.  The
BLOCKS and the model's two ENDS are the family's:
``benchmark/models/<family>_reference.py`` (``blk`` below) gives the
leaf names and ``block(x, w, dims, precision)`` -> (output, a scalar
added to the loss) of each KIND of layer it has, the kind of every
layer, its ``dims_of(conf)``, and — where they are not the bare lookup
and ``rms_norm(x) . lm_head`` — its top leaves, ids -> the first
block's input and the last block's output -> logits;
``benchmark/models/__init__.py`` states the contract, :class:`Model`
reads a family against a configuration.
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
import operator

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
# the model around the family's blocks
# ---------------------------------------------------------------------------
# The two ends of a family that states none: three leaves, the bare
# lookup, and the untied head over the final norm.  ``dims`` of these
# two is the configuration's ``rms_norm_eps``.
TOP_LEAVES = ("embed", "final_norm", "lm_head")


def _first_input(top, ids, eps):
    return jnp.take(top["embed"], ids, axis=0)


def _logits(top, x, eps, precision):
    return matmul(rms_norm(x, top["final_norm"], eps), top["lm_head"],
                  precision)


class Model:
    """A family's reference read against one configuration: the kind
    of every layer and its index within its kind, each kind's leaves
    and block, and the two ends (the contract:
    ``benchmark/models/__init__.py``).  A family that states no kinds
    is one kind, ``None``, under the paths ``("blocks", leaf)``."""

    def __init__(self, blk, conf):
        self.dims = blk.dims_of(conf)
        self.hidden = conf["hidden_size"]
        depth = conf["num_hidden_layers"]
        if hasattr(blk, "KINDS"):
            self.kinds = dict(blk.KINDS)
            order = tuple(blk.layer_kinds(conf))
        else:
            self.kinds = {None: (blk.BLOCK_LEAVES, blk.block)}
            order = (None,) * depth
        if len(order) != depth or not set(order) <= set(self.kinds):
            raise ValueError(
                f"{blk.__name__}: layer_kinds gives {order} for "
                f"{depth} layers of the kinds {sorted(self.kinds)}")
        seen = {k: 0 for k in self.kinds}
        self.layers = []                # (kind, index within the kind)
        for k in order:
            self.layers.append((k, seen[k]))
            seen[k] += 1
        ends = [hasattr(blk, a)
                for a in ("TOP_LEAVES", "first_input", "logits")]
        if any(ends) != all(ends):
            raise ValueError(f"{blk.__name__} states some of TOP_LEAVES, "
                             "first_input, logits and not all three")
        if all(ends):
            self.top_leaves = tuple(blk.TOP_LEAVES)
            self.first_input, self.logits = blk.first_input, blk.logits
            self.ends_dims = self.dims
        else:
            self.top_leaves = TOP_LEAVES
            self.first_input, self.logits = _first_input, _logits
            self.ends_dims = float(conf["rms_norm_eps"])

    @staticmethod
    def path(kind, leaf) -> tuple:
        return ("blocks", leaf) if kind is None else ("blocks", kind, leaf)

    def block_paths(self):
        """(kind, leaf, path) of every stacked block leaf."""
        return [(kind, nm, self.path(kind, nm))
                for kind, (leaves, _) in self.kinds.items() for nm in leaves]

    def reads(self, top: dict) -> tuple:
        """The top leaves each end's result depends on (first input's,
        logits'), found by tracing the two functions: nothing runs.
        Each end is differentiated with respect to these alone, so that
        no table-sized zero is ever made."""
        shapes = {nm: jax.ShapeDtypeStruct(top[nm].shape, F32)
                  for nm in self.top_leaves}

        def of(fn):
            jaxpr = jax.make_jaxpr(fn)(shapes).jaxpr
            used = {id(v) for e in jaxpr.eqns for v in e.invars}
            used |= {id(v) for v in jaxpr.outvars}
            # a dict flattens in the order of its sorted keys
            return tuple(nm for nm, v in zip(sorted(shapes), jaxpr.invars)
                         if id(v) in used)
        first = of(lambda t: self.first_input(
            t, jnp.zeros((1, 1), jnp.int32), self.ends_dims))
        head = of(lambda t: self.logits(
            t, jnp.zeros((1, self.hidden), F32), self.ends_dims, "f32"))
        unread = set(self.top_leaves) - set(first) - set(head)
        if unread:
            raise ValueError(f"top leaves {sorted(unread)} are read by "
                             "neither end of the model")
        return first, head


def _up(w):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), w)


@functools.partial(jax.jit, static_argnames=("block", "dims", "precision"))
def _block_up(x, w, aux, block, dims, precision):
    """A block of the family with the layer's leaves cast up: its
    output, and ``aux`` plus its term of the loss (summed here, so that
    the sum costs no program of its own on the device)."""
    y, term = block(x, _up(w), dims, precision)
    return y, aux + term


@functools.partial(jax.jit, static_argnames=("first_input", "dims"))
def _first_rows(top, ids, first_input, dims):
    return first_input(_up(top), ids, dims)


@functools.partial(jax.jit, static_argnames=("logits", "dims", "precision"))
def _head_rows(x, top, logits, dims, precision):
    return logits(_up(top), x, dims, precision)


# ---------------------------------------------------------------------------
# serving: the gap of each served token below the reference's best
# ---------------------------------------------------------------------------
def _pad_len(n: int) -> int:
    """Right-pad to a power of two (>= 256): causal, so no earlier
    position changes, and few shapes compile."""
    return max(256, 1 << (n - 1).bit_length())


def _layer_of(params, model, i, dev):
    """The leaves of layer ``i``: slice ``j`` of its kind's stacks."""
    kind, j = model.layers[i]
    out = {nm: functools.reduce(operator.getitem, model.path(kind, nm),
                                params)[j] for nm in model.kinds[kind][0]}
    return out if dev is None else jax.device_put(out, dev)


def forward_rows(blk, params, conf, tokens, rows, precision="f32",
                 dev=None):
    """Logits [len(rows), vocab] (float32, on the host) of one sequence
    at positions ``rows``.  ``params`` is the benchmark's tree (stacked
    layers, any float type, any layout); ``dev`` gathers a sharded
    layer onto one device first."""
    n = len(tokens)
    toks = np.zeros((1, _pad_len(n)), np.int64)
    toks[0, :n] = tokens
    model = Model(blk, conf)
    put = (lambda a: jax.device_put(a, dev)) if dev is not None \
        else (lambda a: a)
    first, head = model.reads(params)
    x = _first_rows({nm: put(params[nm]) for nm in first},
                    put(jnp.asarray(toks)), model.first_input,
                    model.ends_dims)
    for i, (kind, _) in enumerate(model.layers):
        x, _ = _block_up(x, _layer_of(params, model, i, dev), np.float32(0),
                         model.kinds[kind][1], model.dims, precision)
    out = _head_rows(x[0, jnp.asarray(rows)],
                     {nm: put(params[nm]) for nm in head}, model.logits,
                     model.ends_dims, precision)
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


@functools.partial(jax.jit, static_argnames=("block", "dims", "precision"))
def _block_bwd(x, w, g, g_aux, block, dims, precision):
    """Gradients of a block's input and leaves from ``g``, the gradient
    of its output, and ``g_aux``, the weight of its own term in the
    step's loss."""
    _, vjp = jax.vjp(lambda x, w: block(x, w, dims, precision), x, w)
    return vjp((g, g_aux))


@functools.partial(jax.jit, static_argnames=("logits", "dims", "precision",
                                             "total"))
def _head_loss(x, top, targets, logits, dims, precision, total):
    """Sum of the rows' NLL over ``total`` tokens, and its gradients
    with respect to the last block's output and the top leaves the
    family's ``logits`` reads (``top`` holds those alone)."""
    def f(x, top):
        lg = logits(top, x, dims, precision)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        tgt = jnp.take_along_axis(lg, targets[..., None], -1)[..., 0]
        return jnp.sum(lse - tgt) / total
    return jax.value_and_grad(f, argnums=(0, 1))(x, top)


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("first_input", "dims"))
def _first_bwd(acc, top, ids, gx, first_input, dims):
    """``acc`` plus the gradient of the top leaves the family's
    ``first_input`` reads, from ``gx``, the gradient of the first
    block's input.  A lookup's share is a scatter-add, which the
    compiler makes straight into ``acc``."""
    _, vjp = jax.vjp(lambda t: first_input(t, ids, dims), top)
    return jax.tree_util.tree_map(jnp.add, acc, vjp(gx)[0])


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
    path such as ``("blocks", <a leaf of the family's block>)``,
    ``("blocks", <kind>, <leaf>)`` where the family states kinds, or
    ``("embed",)``; a stacked block leaf is split into its layers'
    arrays so that a layer's gradient can be accumulated and applied
    alone."""

    def __init__(self, blk, conf, leaf, job, precision="f32"):
        self.model = model = Model(blk, conf)
        self.job, self.precision, self.leaf = job, precision, leaf
        self.layers = [dict() for _ in model.layers]
        for kind, nm, path in model.block_paths():
            stacked = leaf(path)
            for w, (k, j) in zip(self.layers, model.layers):
                if k == kind:
                    w[nm] = stacked[j]
            del stacked
        self.top = {nm: leaf((nm,)) for nm in model.top_leaves}
        self.reads = model.reads(self.top)
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
        model = self.model
        acts, aux = [x], np.float32(0)
        for w, (kind, _) in zip(self.layers, model.layers):
            y, aux = _block_up(acts[-1], w, aux, model.kinds[kind][1],
                               model.dims, self.precision)
            acts.append(y)
        return acts, aux

    def step(self, tokens) -> float:
        """One training step on ``tokens`` [B, S+1]; returns its loss:
        the tokens' mean NLL plus every block's own term, each weighted
        by its rows' share of the batch.
        Rows go forward in blocks; the backward pass then walks the
        layers from the top, sums a layer's gradient over the blocks,
        applies it and frees it before the next layer, so that only one
        layer's gradient is alive at a time.  A top leaf's gradient is
        the sum over every use of it: a tied table gets the head's
        product and the lookup's scatter-add."""
        tokens = np.asarray(tokens)
        B, S1 = tokens.shape
        total, model = B * (S1 - 1), self.model
        first = self.t == 0
        self.t += 1
        lr, wd = float(self.job["lr"]), float(self.job["weight_decay"])
        t = jnp.asarray(self.t, F32)
        sq = lambda a: float(jnp.sum(jnp.square(a)))
        norms = {}
        top_in = {nm: self.top[nm] for nm in self.reads[0]}
        top_out = {nm: self.top[nm] for nm in self.reads[1]}

        xs, gxs, inps, shares, g_top, loss = [], [], [], [], None, 0.0
        for r0 in range(0, B, ROW_BLOCK):
            tok = jnp.asarray(tokens[r0:r0 + ROW_BLOCK])
            inp, tgt = tok[:, :-1], tok[:, 1:]
            share = np.float32(tok.shape[0] / B)
            acts, aux = self._blocks_up(_first_rows(
                top_in, inp, model.first_input, model.ends_dims))
            part, (gx, gt) = _head_loss(
                acts.pop(), top_out, tgt, model.logits, model.ends_dims,
                self.precision, total)
            loss += float(part) + float(share) * float(aux)
            shares.append(share)
            g_top = gt if g_top is None else _acc(g_top, gt)
            xs.append(acts)
            gxs.append(gx)
            inps.append(inp)

        for i in reversed(range(len(self.layers))):
            kind, _ = model.layers[i]
            gw = None
            for b in range(len(xs)):
                gxs[b], g = _block_bwd(xs[b].pop(), self.layers[i], gxs[b],
                                       shares[b], model.kinds[kind][1],
                                       model.dims, self.precision)
                gw = g if gw is None else _acc(gw, g)
            for nm in list(self.layers[i]):
                g = gw.pop(nm)
                if first:
                    path = model.path(kind, nm)
                    norms[path] = norms.get(path, 0.0) + sq(g)
                self.layers[i][nm], self.opt_layers[i][nm] = \
                    _adafactor_leaf(self.layers[i][nm], g,
                                    self.opt_layers[i][nm], t, lr, wd)
        g_in = {nm: g_top.pop(nm) if nm in g_top
                else jnp.zeros_like(self.top[nm]) for nm in top_in}
        for inp, gx in zip(inps, gxs):
            g_in = _first_bwd(g_in, top_in, inp, gx, model.first_input,
                              model.ends_dims)
        g_top.update(g_in)
        del g_in        # or the table's gradient outlives its update
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
        for kind, nm, path in self.model.block_paths():
            p0 = self.leaf(path)
            out[path] = math.sqrt(sum(
                sq(w[nm] - p0[j])
                for w, (k, j) in zip(self.layers, self.model.layers)
                if k == kind))
            del p0
        for nm in self.top:
            out[(nm,)] = math.sqrt(sq(self.top[nm] - self.leaf((nm,))))
        return out
