"""RMSNorm Pallas kernel (fwd + bwd).

Replacement for the reference's fused_rms_norm CUDA kernel
(python/paddle/incubate/nn/functional/fused_rms_norm.py).  One VMEM pass:
fp32 accumulation, fused scale."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _common
from ._common import idx32

__all__ = ["rms_norm"]


def _fwd_kernel(x_ref, w_ref, o_ref, rstd_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + jnp.float32(eps))
    o_ref[:] = (x * rstd * w_ref[:].astype(jnp.float32)).astype(
        o_ref.dtype)
    rstd_ref[:] = rstd


def _bwd_kernel(x_ref, w_ref, rstd_ref, do_ref, dx_ref, dwp_ref):
    from jax.experimental import pallas as pl
    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    do = do_ref[:].astype(jnp.float32)
    xhat = x * rstd
    wdo = w * do
    c = jnp.mean(xhat * wdo, axis=-1, keepdims=True)
    dx = (wdo - xhat * c) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)
    # dw accumulates in ONE (8, h) output block (constant index map:
    # TPU grids run sequentially, so the block stays resident in VMEM
    # across iterations).  A (1, h) per-block output would violate
    # Mosaic's (8, 128) tiling whenever the grid has >1 block.
    rowsum = jnp.sum(xhat * do, axis=0, keepdims=True)       # [1, h]
    slab = jnp.pad(rowsum, ((0, 7), (0, 0)))

    @pl.when(pl.program_id(0) == 0)
    def _init():
        # analysis: ignore[trace-impure] reason=Pallas Ref store IS the kernel's output path (pl.when branches write the grid-resident accumulator), not trace-time state capture
        dwp_ref[:] = slab

    @pl.when(pl.program_id(0) != 0)
    def _accum():
        # analysis: ignore[trace-impure] reason=Pallas Ref store IS the kernel's output path (pl.when branches write the grid-resident accumulator), not trace-time state capture
        dwp_ref[:] = dwp_ref[:] + slab


def _rows(x):
    return x.reshape(-1, x.shape[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, w, eps: float = 1e-6):
    out, _ = _fwd(x, w, eps)
    return out


def _block_rows(n):
    for b in (256, 128, 64, 32, 16, 8, 4, 2, 1):
        if n % b == 0:
            return b
    return 1


def _fwd(x, w, eps):
    orig_shape = x.shape
    xr = _rows(x)
    n, h = xr.shape
    br = _block_rows(n)
    # match the composite path's dtype semantics: norm(x).astype(x.dtype)
    # * w promotes to the weight dtype (master-weight setups pass f32 w
    # with bf16 x and expect f32 out)
    out_dtype = jnp.promote_types(x.dtype, w.dtype)
    # an element: x^2 into the mean, then the two products; a row: the
    # mean's scale and eps, one rsqrt.  x in, out and rstd once, w once
    # (its index never turns)
    cost = pl.CostEstimate(
        flops=4 * n * h + 2 * n, transcendentals=n,
        bytes_accessed=_common.nbytes((n, h), xr.dtype)
        + _common.nbytes((n, h), out_dtype) + _common.nbytes((h,), w.dtype)
        + 4 * n)
    out, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        out_shape=(jax.ShapeDtypeStruct((n, h), out_dtype),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32)),
        grid=(n // br,),
        in_specs=[pl.BlockSpec((br, h), lambda i: idx32(i, 0)),
                  pl.BlockSpec((1, h), lambda i: idx32(0, 0))],
        out_specs=(pl.BlockSpec((br, h), lambda i: idx32(i, 0)),
                   pl.BlockSpec((br, 1), lambda i: idx32(i, 0))),
        name="rms_norm",
        cost_estimate=cost,
        interpret=_common.interpret(),
    )(xr, w.reshape(1, -1))
    return out.reshape(orig_shape), (xr, w, rstd, orig_shape)


def _fwd_vjp(x, w, eps):
    return _fwd(x, w, eps)


def _bwd_vjp(eps, res, dout):
    xr, w, rstd, orig_shape = res
    n, h = xr.shape
    br = _block_rows(n)
    do = dout.reshape(n, h)
    # an element: xhat, w dO, their product into the row's mean, dx's
    # three, and xhat dO into the column sum: 9.  x, dO in and dx out
    # once, rstd; w in and the [8, h] fp32 sums out once
    cost = pl.CostEstimate(
        flops=9 * n * h, transcendentals=0,
        bytes_accessed=2 * _common.nbytes((n, h), xr.dtype)
        + _common.nbytes((n, h), do.dtype) + _common.nbytes((h,), w.dtype)
        + 4 * n + 4 * 8 * h)
    dx, dw_partial = pl.pallas_call(
        _bwd_kernel,
        out_shape=(jax.ShapeDtypeStruct((n, h), xr.dtype),
                   jax.ShapeDtypeStruct((8, h), jnp.float32)),
        grid=(n // br,),
        in_specs=[pl.BlockSpec((br, h), lambda i: idx32(i, 0)),
                  pl.BlockSpec((1, h), lambda i: idx32(0, 0)),
                  pl.BlockSpec((br, 1), lambda i: idx32(i, 0)),
                  pl.BlockSpec((br, h), lambda i: idx32(i, 0))],
        out_specs=(pl.BlockSpec((br, h), lambda i: idx32(i, 0)),
                   pl.BlockSpec((8, h), lambda i: idx32(0, 0))),
        name="rms_norm_bwd",
        cost_estimate=cost,
        interpret=_common.interpret(),
    )(xr, w.reshape(1, -1), rstd, do)
    dw = jnp.sum(dw_partial, axis=0).astype(w.dtype)
    return dx.reshape(orig_shape), dw


rms_norm.defvjp(_fwd_vjp, _bwd_vjp)
