"""Fused AdamW Pallas kernel.

Replacement for the reference's fused adamw CUDA kernels
(paddle/phi/kernels/gpu/adamw_kernel.cu, fused multi-tensor variants).
One VMEM pass updates param + both moments with decoupled weight decay —
no intermediate HBM round-trips between the moment updates."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _common
from ._common import idx32
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_adamw"]


def _kernel(p_ref, g_ref, m_ref, v_ref, lr_ref, c1_ref, c2_ref,
            o_p, o_m, o_v, *, b1: float, b2: float, eps: float,
            wd: float):
    # c1/c2 = 1 - beta**t bias corrections, computed OUTSIDE the kernel:
    # Mosaic cannot legalize powf on a traced scalar exponent.
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    m = m_ref[:]
    v = v_ref[:]
    lr = lr_ref[0]
    c1 = c1_ref[0]
    c2 = c2_ref[0]
    m_new = jnp.float32(b1) * m + jnp.float32(1.0 - b1) * g
    v_new = jnp.float32(b2) * v + jnp.float32(1.0 - b2) * g * g
    mhat = m_new / c1
    vhat = v_new / c2
    p_new = (p * (jnp.float32(1.0) - lr * jnp.float32(wd)) -
             lr * mhat / (jnp.sqrt(vhat) + jnp.float32(eps)))
    o_p[:] = p_new.astype(o_p.dtype)
    o_m[:] = m_new
    o_v[:] = v_new


def fused_adamw(p, g, m, v, t, lr, b1=0.9, b2=0.95, eps=1e-8,
                weight_decay=0.1):
    """Returns (new_p, {"m": new_m, "v": new_v}) — slot-in for the
    llama_pretrain adamw_update rule."""
    shape = p.shape
    flat_n = int(p.size)
    # always lay out as [rows, 128]: a [N, 1] fallback would be tiled
    # (8, 128) by the TPU memory system — a 128x padded-HBM blowup.
    # Indivisible sizes get zero-padded to a whole number of rows (the
    # padded tail updates zeros against zero grads: wasted lanes only).
    h = 128
    pad = (-flat_n) % (8 * h)  # whole (8, 128) tiles: sublane x lane
    rows = (flat_n + pad) // h
    br = rows
    for cand in (1024, 512, 256, 128, 64, 32, 16, 8):
        if rows % cand == 0:
            br = cand
            break

    def flat2(x, dt=None):
        x = x.reshape(-1)
        if pad:
            x = jnp.pad(x, (0, pad))
        x = x.reshape(rows, h)
        return x if dt is None else x.astype(dt)

    lr_arr = jnp.asarray([lr], jnp.float32)
    tf = jnp.asarray(t, jnp.float32)
    c1_arr = (1.0 - jnp.float32(b1) ** tf).reshape(1)
    c2_arr = (1.0 - jnp.float32(b2) ** tf).reshape(1)
    # an element of the padded [rows, 128]: 3 for m, 4 for v, the two
    # corrections, 5 for p (decay, step, eps, quotient, difference), one
    # sqrt; p, g, m, v in (g, m, v fp32) and p, m, v out once
    cost = pl.CostEstimate(
        flops=14 * rows * h, transcendentals=rows * h,
        bytes_accessed=rows * h * (2 * p.dtype.itemsize + 5 * 4))
    new_p, new_m, new_v = pl.pallas_call(
        functools.partial(_kernel, b1=b1, b2=b2, eps=eps,
                          wd=weight_decay),
        out_shape=(jax.ShapeDtypeStruct((rows, h), p.dtype),
                   jax.ShapeDtypeStruct((rows, h), jnp.float32),
                   jax.ShapeDtypeStruct((rows, h), jnp.float32)),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: idx32(i, 0)),
            pl.BlockSpec((br, h), lambda i: idx32(i, 0)),
            pl.BlockSpec((br, h), lambda i: idx32(i, 0)),
            pl.BlockSpec((br, h), lambda i: idx32(i, 0)),
            # explicit index maps: the default map emits i64 literals
            # under x64, which Mosaic cannot legalize
            pl.BlockSpec((1,), lambda i: idx32(0),
                         memory_space=pltpu.SMEM),  # lr scalar
            pl.BlockSpec((1,), lambda i: idx32(0),
                         memory_space=pltpu.SMEM),  # 1-b1**t
            pl.BlockSpec((1,), lambda i: idx32(0),
                         memory_space=pltpu.SMEM),  # 1-b2**t
        ],
        out_specs=(pl.BlockSpec((br, h), lambda i: idx32(i, 0)),
                   pl.BlockSpec((br, h), lambda i: idx32(i, 0)),
                   pl.BlockSpec((br, h), lambda i: idx32(i, 0))),
        name="fused_adamw",
        cost_estimate=cost,
        interpret=_common.interpret(),
    )(flat2(p), flat2(g, jnp.float32), flat2(m, jnp.float32),
      flat2(v, jnp.float32), lr_arr, c1_arr, c2_arr)

    def unflat(x):
        x = x.reshape(-1)
        if pad:
            x = x[:flat_n]
        return x.reshape(shape)

    return (unflat(new_p), {"m": unflat(new_m), "v": unflat(new_v)})
