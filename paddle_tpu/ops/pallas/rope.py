"""Rotary position embedding Pallas kernel (fwd + bwd).

Replacement for the reference's fused rotary CUDA op
(/root/reference/python/paddle/incubate/nn/functional/
fused_rotary_position_embedding.py, phi/kernels/fusion/gpu/
fused_rope_*.cu).  Applies the rotate-half form to q and k in one VMEM
pass per (batch, head) tile:

    out[..., :d/2] = x1 * cos - x2 * sin
    out[..., d/2:] = x2 * cos + x1 * sin

cos/sin are [S, d/2] tables computed once outside (tiny).  The backward
is the inverse rotation (sin -> -sin) — no residuals beyond the tables.
XLA can fuse the composite form into the surrounding projections; the
flagship trunk takes the kernel where it measured ahead (head dim % 128
== 0, FLAGS_pallas_rope; PERF.md), other head dims keep the composite.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _common
from ._common import idx32

__all__ = ["fused_rope", "rope_tables", "rope_inv_freq"]


def rope_inv_freq(head_dim: int, theta: float = 10000.0):
    """RoPE inverse frequencies [d/2] — the ONE source of the formula
    (rope_tables, the position_ids lane of incubate fused_rope, and
    decode's single-position rotation all derive from this)."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def rope_tables(seq_len: int, head_dim: int, theta: float = 10000.0,
                dtype=jnp.float32):
    """cos/sin tables [S, d/2] for :func:`fused_rope`."""
    inv = rope_inv_freq(head_dim, theta)
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def _rotate(x, cos, sin):
    """Rotate-half on the last axis of fp32 ``x``; cos/sin broadcast
    against its halves."""
    h = x.shape[-1] // 2
    x1 = x[..., :h]
    x2 = x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref, *, neg_sin: bool):
    # x: [1, S_blk, N*d], heads side by side on lanes where the
    # projection wrote them, each a static, tile-aligned lane slice;
    # cos/sin: [S_blk, d/2], the same for every head
    d = 2 * cos_ref.shape[-1]
    cos = cos_ref[:].astype(jnp.float32)
    sin = sin_ref[:].astype(jnp.float32)
    if neg_sin:
        sin = -sin
    for j in range(x_ref.shape[-1] // d):
        head = (0, slice(None), slice(j * d, (j + 1) * d))
        o_ref[head] = _rotate(x_ref[head].astype(jnp.float32), cos,
                              sin).astype(o_ref.dtype)


def _composite(x, cos, sin, neg_sin: bool):
    """Plain-XLA rotate-half (the fallback for shapes the kernel does
    not address: a head that is not whole lane tiles, d % 128 != 0, and
    odd sequence lengths where no 8-aligned block divides S; Mosaic
    requires sublane blocks divisible by 8)."""
    d = x.shape[-1]
    h = d // 2
    c = cos.astype(jnp.float32)[None, :, None, :]
    sn = sin.astype(jnp.float32)[None, :, None, :]
    if neg_sin:
        sn = -sn
    x1 = x[..., :h].astype(jnp.float32)
    x2 = x[..., h:].astype(jnp.float32)
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn],
                           -1).astype(x.dtype)


def _pick_block(s, n, d):
    # budget: the kernel holds ~5 f32 copies of the block (cast, halves,
    # rotated halves) double-buffered; keep the raw block under 1 MiB.
    # Blocks must be 8-aligned on the sublane dim (or equal to S) for
    # the [S, d/2] table operand.
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if s % cand == 0 and cand * n * d * 4 <= (1 << 20):
            return cand
    if s * n * d * 4 <= (1 << 20):
        return s
    return None


def _apply(x, cos, sin, neg_sin: bool):
    b, s, n, d = x.shape
    bs = _pick_block(s, n, d)
    if bs is None or d % 128 != 0:
        return _composite(x, cos, sin, neg_sin)
    # a head of whole lane tiles is addressed where the projection
    # wrote it: [b, s, n, d] -> [b, s, n*d] is a bitcast of the matmul's
    # output, while a [.., n, d] operand puts the HEADS on sublanes and
    # costs a relayout on each side of the kernel
    spec = pl.BlockSpec((1, bs, n * d), lambda bi, si: idx32(bi, si, 0))
    table = pl.BlockSpec((bs, d // 2), lambda bi, si: idx32(si, 0))
    # executed: 3 operations an element (two products and an add or a
    # subtraction a half); x in and out once, the two tables again for
    # every batch row (their block turns with the row block; one block
    # stays)
    cost = pl.CostEstimate(
        flops=3 * x.size, transcendentals=0,
        bytes_accessed=2 * _common.nbytes(x.shape, x.dtype)
        + (b if s > bs else 1) * (_common.nbytes(cos.shape, cos.dtype)
                                  + _common.nbytes(sin.shape, sin.dtype)))
    return pl.pallas_call(
        functools.partial(_rope_kernel, neg_sin=neg_sin),
        out_shape=jax.ShapeDtypeStruct((b, s, n * d), x.dtype),
        grid=(b, s // bs),
        in_specs=[spec, table, table],
        out_specs=spec,
        name="rope",
        cost_estimate=cost,
        interpret=_common.interpret(),
    )(x.reshape(b, s, n * d), cos, sin).reshape(x.shape)


@jax.custom_vjp
def fused_rope(x, cos, sin):
    """Rotate-half RoPE on [B, S, N, D] with [S, D/2] tables."""
    return _apply(x, cos, sin, neg_sin=False)


def _vjp_fwd(x, cos, sin):
    return _apply(x, cos, sin, neg_sin=False), (cos, sin)


def _vjp_bwd(res, dout):
    cos, sin = res
    # rotation is orthonormal: the vjp is the inverse rotation
    return _apply(dout, cos, sin, neg_sin=True), None, None


fused_rope.defvjp(_vjp_fwd, _vjp_bwd)
