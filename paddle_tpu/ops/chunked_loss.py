"""Chunked softmax cross-entropy: the LM loss head without the [B,S,V]
fp32 round-trip.

The straightforward head (reference: ParallelCrossEntropy and
softmax_with_cross_entropy, /root/reference/python/paddle/nn/functional/loss.py)
materialises fp32 logits [B,S,V], log_softmax's them (another full
read+write) and keeps them as residuals for backward — at B=8, S=2047,
V=32000 that is ~2.1 GB per pass of pure HBM traffic and the same again in
residency.

TPU-native design: a ``jax.custom_vjp`` over the tokens flattened to [T,H]
and cut into chunks, one ``lax.scan`` either way.

  * Not differentiated (an evaluation loop): per chunk one bf16 MXU matmul
    accumulated in fp32 (``preferred_element_type``), reduced at once to
    (logsumexp, target logit) — the [C,V] block dies in VMEM/local HBM
    instead of being written back.
  * Differentiated: the loss is a scalar, so the cotangent that reaches
    the head is a scalar, and the gradients are formed IN THE FORWARD scan
    and scaled by that scalar afterwards.  Per chunk: the same logits
    matmul, one max/exp/sum pass that gives both the logsumexp and the
    softmax, d_logits = (softmax - onehot) / T (the one-hot is an iota
    comparison XLA fuses into the subtraction) rounded to the compute
    dtype, dx_chunk = d_logits @ W^T and dW += x_chunk^T @ d_logits in an
    fp32 carry beside the loss sum: three matmuls and one exp pass a
    chunk, where a backward scan of its own would need the logits (and the
    exp) a second time.  The backward rule is two scalar multiplies.

Nothing [T,V]-shaped is ever a residual.  The residuals are dx ([B,S,H],
x's dtype) and dW ([H,V], W's dtype); they live only between the head's
forward and its backward, which are adjacent in a train step (the head is
the last thing forward and the first thing backward), and are the arrays
the backward hands on anyway.  x, W and the targets are not saved.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _chunk_logits(xc, w, dt, scale=1.0, table=False):
    # bf16 inputs on the MXU, fp32 accumulation/output.  ``table``: w is
    # the [V, H] embedding table (a tied head), contracted over its
    # columns where it lies; ``scale`` multiplies the fp32 logits.
    logits = jax.lax.dot_general(
        xc.astype(dt), w.astype(dt),
        dimension_numbers=(((1,), (1 if table else 0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return logits if scale == 1.0 else logits * scale


def _flatten(x, targets, num_chunks):
    H = x.shape[-1]
    xf = x.reshape(-1, H)
    tf = targets.reshape(-1)
    T = xf.shape[0]
    if T % num_chunks:
        raise ValueError(
            f"token count {T} not divisible by loss chunk count {num_chunks}")
    C = T // num_chunks
    return xf.reshape(num_chunks, C, H), tf.reshape(num_chunks, C), T


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def chunked_softmax_cross_entropy(x, w, targets, num_chunks: int = 8,
                                  compute_dtype=jnp.bfloat16,
                                  logits_scale: float = 1.0,
                                  table: bool = False):
    """Mean NLL of ``softmax(logits_scale * x @ w)`` at ``targets``
    without materialising the full logits tensor.

    x: [..., H] activations (any float dtype), w: [H, V] unembedding —
    or, with ``table``, the [V, H] embedding table of a tied head, read
    where it lies: dW then has the table's layout, and the caller's
    autodiff adds the lookup's share to it —, targets: [...] int labels;
    the leading dims are flattened and must be divisible by
    ``num_chunks``.
    """
    xs, ts, T = _flatten(x, targets, num_chunks)

    def step(acc, inp):
        xc, tc = inp
        logits = _chunk_logits(xc, w, compute_dtype, logits_scale,
                               table)                            # [C,V] f32
        lse = jax.scipy.special.logsumexp(logits, axis=-1)       # [C]
        tgt = jnp.take_along_axis(logits, tc[:, None], -1)[:, 0]
        return acc + jnp.sum(lse - tgt), None

    total, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), (xs, ts))
    return total / T


def _ce_fwd(x, w, targets, num_chunks, dt, logits_scale=1.0, table=False):
    V = w.shape[0 if table else 1]
    xs, ts, T = _flatten(x, targets, num_chunks)
    # d(loss)/d(x @ w): the mean's 1/T and the logits' own scale
    scale = jnp.float32(logits_scale) / T

    def step(carry, inp):
        total, dw_acc = carry
        xc, tc = inp
        logits = _chunk_logits(xc, w, dt, logits_scale, table)   # [C,V] f32
        m = jnp.max(logits, axis=-1, keepdims=True)
        e = jnp.exp(logits - m)
        s = jnp.sum(e, axis=-1, keepdims=True)
        lse = (jnp.log(s) + m)[:, 0]                             # [C]
        tgt = jnp.take_along_axis(logits, tc[:, None], -1)[:, 0]
        p = e / s                                                # softmax
        d_logits = (p - jax.nn.one_hot(tc, V, dtype=p.dtype)) * scale
        d_logits_c = d_logits.astype(dt)
        dxc = jax.lax.dot_general(                               # [C,H]
            d_logits_c, w.astype(dt),
            dimension_numbers=(((1,), (0 if table else 1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dwc = jax.lax.dot_general(                       # [H,V]; [V,H]
            *((d_logits_c, xc.astype(dt)) if table
              else (xc.astype(dt), d_logits_c)),
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return ((total + jnp.sum(lse - tgt), dw_acc + dwc),
                dxc.astype(x.dtype))

    init = (jnp.zeros((), jnp.float32), jnp.zeros(w.shape, jnp.float32))
    (total, dw), dxs = jax.lax.scan(step, init, (xs, ts))
    return total / T, (dxs.reshape(x.shape), dw.astype(w.dtype))


def _ce_bwd(num_chunks, dt, logits_scale, table, res, g):
    dx, dw = res
    return (g * dx).astype(dx.dtype), (g * dw).astype(dw.dtype), None


chunked_softmax_cross_entropy.defvjp(_ce_fwd, _ce_bwd)
