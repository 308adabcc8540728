"""Weight-only int8 matmul Pallas kernel (decode serving).

Reference role: the weight-only-quantized GEMMs the reference serves
with (paddle/phi/kernels/fusion/cutlass weight-only kernels;
python/paddle/nn/quant/weight_quantize API).

Decode is HBM-bound: every generated token re-reads all weights, so
halving weight bytes ~doubles the serving roofline.  The kernel reads
the int8 weight block, dequantises in VMEM (int8 -> bf16, then a
per-output-channel fp32 scale applied to the fp32 accumulator), and
runs the MXU dot — the bf16 weight tensor never exists in HBM, which
is the whole point (an XLA dequant-then-matmul writes the bf16 copy
back to HBM first and loses the bandwidth win).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _common
from ._common import idx32

__all__ = ["int8_matmul", "quantize_int8"]


def quantize_int8(w):
    """Per-output-channel symmetric int8 quantisation of [K, N] -> dict
    {"q": int8 [K, N], "s": f32 [N]} (absmax / 127 scales)."""
    wf = jnp.asarray(w, jnp.float32)
    s = jnp.max(jnp.abs(wf), axis=0) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    q = jnp.clip(jnp.round(wf / s[None, :]), -127, 127).astype(jnp.int8)
    return {"q": q, "s": s}


def _kernel(x_ref, w_ref, s_ref, o_ref):
    x = x_ref[:]                                    # [M, K] bf16
    w = w_ref[:].astype(jnp.bfloat16)               # int8 -> bf16 VMEM
    acc = jax.lax.dot(x, w, preferred_element_type=jnp.float32)
    o_ref[:] = (acc * s_ref[0][None, :]).astype(o_ref.dtype)


def _block_n(K, N, enforce_vmem=True):
    # whole-K weight blocks; <= 2 MiB int8 per block (4 MiB measured
    # no faster on the 1.3B decode and squeezes VMEM)
    for bn in (512, 256, 128):
        if N % bn == 0 and K * bn <= (1 << 21):
            return bn
    # fallback keeps a hard cap: the int8 block plus its bf16 dequant
    # copy (3x the int8 bytes) must stay inside scoped VMEM, or Mosaic
    # fails at run time with an opaque OOM.  4 MiB int8 (12 MiB total)
    # is the ceiling; beyond that the kernel needs a K-split it does
    # not have, so refuse loudly — except in interpret mode, where
    # there is no VMEM to blow.
    for bn in (512, 256, 128):
        if N % bn == 0 and (not enforce_vmem or K * bn <= (1 << 22)):
            return bn
    if enforce_vmem and K * N > (1 << 22):  # no divisor -> whole-N block
        raise ValueError(
            f"int8_matmul: no weight block fits VMEM for K={K}, N={N} "
            "(whole-K blocks only).  Split K on the caller side or use "
            "the XLA dequant-then-matmul path.")
    return N


def _block_m(Mp, K):
    # activation blocks <= ~2 MiB bf16 (prefill runs B*S rows through
    # the same kernel; whole-M there blows scoped VMEM)
    for bm in (512, 256, 128, 64, 32, 16, 8):
        if Mp % bm == 0 and bm * K * 2 <= (1 << 21):
            return bm
    return 8


def int8_matmul(x, wq, scale, out_dtype=None):
    """``x [M, K] @ dequant(wq [K, N], scale [N]) -> [M, N]``.

    M is padded up to the 8-row sublane tile; K and N must be multiples
    of 128 (the caller's weights are transformer matrices, which are).
    """
    M, K = x.shape
    K2, N = wq.shape
    assert K == K2, (x.shape, wq.shape)
    out_dtype = out_dtype or x.dtype
    pad_m = (-M) % 8
    if pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
    Mp = M + pad_m
    interp = _common.interpret()
    bn = _block_n(K, N, enforce_vmem=not interp)
    bm = _block_m(Mp, K)
    # the product of the padded rows and the scale on its result; x a
    # row block once (its index holds over the columns), the int8
    # weights and their scales again for every row block unless there is
    # one column block, the result out once
    refetch = Mp // bm if N // bn > 1 else 1
    cost = pl.CostEstimate(
        flops=2 * Mp * K * N + Mp * N, transcendentals=0,
        bytes_accessed=2 * Mp * K + refetch * (K * N + 4 * N)
        + _common.nbytes((Mp, N), out_dtype))
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
        grid=(Mp // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: idx32(i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: idx32(0, j)),
            # scales as [1, N]: a 1-D operand's XLA layout need not
            # match Mosaic's 1-D tiling (layout-verify failure on
            # large N)
            pl.BlockSpec((1, bn), lambda i, j: idx32(0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: idx32(i, j)),
        name="int8_matmul",
        cost_estimate=cost,
        interpret=interp,
    )(x.astype(jnp.bfloat16), wq,
      scale.astype(jnp.float32).reshape(1, -1))
    return out[:M] if pad_m else out
