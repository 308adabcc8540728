"""Fused RMSNorm -> matmul Pallas kernel (PERF.md "remaining levers
beyond 45%": the block-entry fusion).

``out = rms_norm(x, wl) @ W`` in ONE kernel pass: each [bm, bn] grid
cell loads its x rows and W columns, accumulates the matmul partial in
f32, computes the row sum-of-squares from the SAME resident x block,
and scales the accumulator at the end — ``diag(rstd)`` commutes with
the contraction, so the normalised ``[M, H]`` activation is never
materialised in HBM.  (The standalone rms_norm kernel measured -11%
at 1.3B because it broke XLA's norm-into-matmul fusion — this kernel
IS that fusion, done by hand; whether it beats XLA's is a
measurement, gated off by default until the chip says so.)

Reference analog: fused_rms_norm + the matmul it feeds
(python/paddle/incubate/nn/functional/fused_rms_norm.py).

Backward is XLA (jnp) recompute — the fwd is the HBM-bound hot path;
bwd reuses the standard rms_norm/matmul cotangent algebra and lets
XLA fuse it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _common
from ._common import idx32

__all__ = ["rmsnorm_matmul"]


def _kernel(x_ref, wl_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)                 # [bm, H]
    wl = wl_ref[:].astype(jnp.float32)               # [1, H]
    sumsq = jnp.sum(x * x, axis=-1, keepdims=True)   # [bm, 1]
    rstd = jax.lax.rsqrt(sumsq / jnp.float32(x.shape[-1])
                         + jnp.float32(eps))
    acc = jax.lax.dot_general(
        (x * wl).astype(x_ref.dtype), w_ref[:],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [bm, bn]
    o_ref[:] = (acc * rstd).astype(o_ref.dtype)


def _pick(n, choices):
    for b in choices:
        if n % b == 0:
            return b
    return None


def _xla_ref(x, wl, w, eps):
    xf = x.astype(jnp.float32)
    rstd = jax.lax.rsqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    y = (xf * rstd * wl.astype(jnp.float32)).astype(x.dtype)
    return jax.lax.dot_general(
        y, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def rmsnorm_matmul(x, wl, w, eps: float = 1e-6):
    """``rms_norm(x, wl) @ w`` fused.  x [..., H], wl [H], w [H, N]
    -> [..., N] in x.dtype (f32 accumulation inside)."""
    return _fwd(x, wl, w, eps)[0]


def _fwd(x, wl, w, eps):
    H = x.shape[-1]
    N = w.shape[-1]
    lead = x.shape[:-1]
    M = 1
    for s in lead:
        M *= s
    xr = x.reshape(M, H)
    bm = _pick(M, (256, 128, 64, 32, 16, 8))
    bn = _pick(N, (512, 256, 128))
    # Mosaic tiling: last-2 block dims must divide (8, 128) or equal
    # the array dims — fall back to the XLA composite otherwise
    if bm is None or bn is None or H % 128:
        return _xla_ref(x, wl, w, eps), (x, wl, w)
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, H), lambda i, j: idx32(i, 0)),
            pl.BlockSpec((1, H), lambda i, j: idx32(0, 0)),
            pl.BlockSpec((H, bn), lambda i, j: idx32(0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: idx32(i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        name="rmsnorm_matmul",
        interpret=_common.interpret(),
    )(xr, wl.reshape(1, H), w)
    return out.reshape(*lead, N), (x, wl, w)


def _fwd_vjp(x, wl, w, eps):
    out, res = _fwd(x, wl, w, eps)
    return out, res


def _bwd_vjp(eps, res, dout):
    x, wl, w = res
    xf = x.astype(jnp.float32)
    rstd = jax.lax.rsqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    xhat = xf * rstd
    wlf = wl.astype(jnp.float32)
    y = xhat * wlf                                     # normalised acts
    do = dout.astype(jnp.float32)
    nd = x.ndim - 1
    batch = tuple(range(nd))
    # dW = y^T @ do (contract every leading dim)
    dw = jax.lax.dot_general(
        y, do, ((batch, batch), ((), ())),
        preferred_element_type=jnp.float32)
    # dy = do @ W^T
    dy = jax.lax.dot_general(
        do, w.astype(jnp.float32), (((nd,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dwl = jnp.sum(xhat * dy, axis=batch)
    wdy = wlf * dy
    c = jnp.mean(xhat * wdy, axis=-1, keepdims=True)
    dx = (wdy - xhat * c) * rstd
    return (dx.astype(x.dtype), dwl.astype(wl.dtype),
            dw.astype(w.dtype))


rmsnorm_matmul.defvjp(_fwd_vjp, _bwd_vjp)
