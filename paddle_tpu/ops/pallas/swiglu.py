"""SwiGLU Pallas kernel (fwd + bwd): ``silu(gate) * up`` in one VMEM
pass.

Replacement for the reference's fused swiglu op
(/root/reference/python/paddle/incubate/nn/functional/swiglu.py, CUDA
kernel under phi/kernels/fusion/gpu/fused_swiglu_kernel.cu).  On TPU the
XLA fusion engine usually folds this pattern into its matmul neighbours
already — the kernel exists for the cases where the pattern sits at a
fusion boundary (and to keep the incubate API a real fused op); the
bench keeps whichever path measures faster (see PERF.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _common
from ._common import idx32

__all__ = ["swiglu"]


def _fwd_kernel(g_ref, u_ref, o_ref):
    g = g_ref[:].astype(jnp.float32)
    u = u_ref[:].astype(jnp.float32)
    s = g * jax.nn.sigmoid(g)
    o_ref[:] = (s * u).astype(o_ref.dtype)


def _bwd_kernel(g_ref, u_ref, do_ref, dg_ref, du_ref):
    g = g_ref[:].astype(jnp.float32)
    u = u_ref[:].astype(jnp.float32)
    do = do_ref[:].astype(jnp.float32)
    sig = jax.nn.sigmoid(g)
    silu = g * sig
    # d silu(g)/dg = sig * (1 + g * (1 - sig))
    dg_ref[:] = (do * u * sig * (1.0 + g * (1.0 - sig))).astype(
        dg_ref.dtype)
    du_ref[:] = (do * silu).astype(du_ref.dtype)


def _blocks(n, h):
    for br in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        if n % br == 0 and br * h * 4 <= (1 << 21):
            return br
    return 1


@jax.custom_vjp
def swiglu(gate, up):
    """``silu(gate) * up`` with gate/up of identical shape [..., H]."""
    out, _ = _fwd(gate, up)
    return out


def _fwd(gate, up):
    shape = gate.shape
    g = gate.reshape(-1, shape[-1])
    u = up.reshape(-1, shape[-1])
    n, h = g.shape
    br = _blocks(n, h)
    out = pl.pallas_call(
        _fwd_kernel,
        out_shape=jax.ShapeDtypeStruct((n, h), gate.dtype),
        grid=(n // br,),
        in_specs=[pl.BlockSpec((br, h), lambda i: idx32(i, 0)),
                  pl.BlockSpec((br, h), lambda i: idx32(i, 0))],
        out_specs=pl.BlockSpec((br, h), lambda i: idx32(i, 0)),
        name="swiglu",
        interpret=_common.interpret(),
    )(g, u)
    return out.reshape(shape), (gate, up)


def _fwd_vjp(gate, up):
    return _fwd(gate, up)


def _bwd_vjp(res, dout):
    gate, up = res
    shape = gate.shape
    g = gate.reshape(-1, shape[-1])
    u = up.reshape(-1, shape[-1])
    do = dout.reshape(-1, shape[-1])
    n, h = g.shape
    br = _blocks(n, h)
    dg, du = pl.pallas_call(
        _bwd_kernel,
        out_shape=(jax.ShapeDtypeStruct((n, h), gate.dtype),
                   jax.ShapeDtypeStruct((n, h), up.dtype)),
        grid=(n // br,),
        in_specs=[pl.BlockSpec((br, h), lambda i: idx32(i, 0)),
                  pl.BlockSpec((br, h), lambda i: idx32(i, 0)),
                  pl.BlockSpec((br, h), lambda i: idx32(i, 0))],
        out_specs=(pl.BlockSpec((br, h), lambda i: idx32(i, 0)),
                   pl.BlockSpec((br, h), lambda i: idx32(i, 0))),
        name="swiglu_bwd",
        interpret=_common.interpret(),
    )(g, u, do)
    return dg.reshape(shape), du.reshape(shape)


swiglu.defvjp(_fwd_vjp, _bwd_vjp)
