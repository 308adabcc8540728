"""Flash attention (fwd + bwd) as Pallas TPU kernels.

TPU-native replacement for the reference's CUDA flashattn integration
(/root/reference/paddle/phi/kernels/gpu/flash_attn_kernel.cu, Python API
python/paddle/nn/functional/flash_attention.py:147).

FlashAttention-2 style: online-softmax forward saving per-row logsumexp;
backward recomputes per-block probabilities and accumulates dQ/dK/dV —
O(S) memory, blocked to MXU-friendly (128, head_dim) tiles.

Public layout matches the framework's sdpa: [batch, seq, heads, dim].
Kernels run per (batch*heads) with K/V resident in VMEM (seq*dim*2B ≤
~1MB at seq 4k, d 128 — well within the 16MB budget).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _common
from ._common import idx32
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal: bool,
                sm_scale: float, block_k: int):
    # q_ref: [Bq, d]; k_ref/v_ref: [S, d]; o_ref: [Bq, d]; lse_ref: [Bq, 1]
    # MXU dots run on the native (bf16) inputs with fp32 accumulation —
    # v5e's fp32 matmul rate is ~1/4 of bf16, so upcasting the operands
    # would quarter kernel throughput for no accuracy gain.
    qi = pl.program_id(1)
    Bq, d = q_ref.shape
    S = k_ref.shape[0]
    q = q_ref[:]

    num_k = jnp.int32(S // block_k)

    def body(ki, carry, masked):
        m_prev, l_prev, acc = carry
        k = k_ref[pl.ds(ki * block_k, block_k), :]
        v = v_ref[pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * jnp.float32(sm_scale)
        if masked:
            # only the diagonal block pays for the mask (iota+cmp+select
            # are pure VPU work; off-diagonal causal blocks are all-visible
            # because the loop bound below already excludes future blocks)
            q_pos = qi * Bq + jax.lax.broadcasted_iota(
                jnp.int32, (Bq, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (Bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(NEG_INF))
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((Bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Bq, 1), jnp.float32)
    acc0 = jnp.zeros((Bq, d), jnp.float32)
    init = (m0, l0, acc0)
    assert not causal or Bq == block_k, \
        "_pick_blocks guarantees square blocks; causal masking relies on it"
    if causal:
        # blocks [0, qi) are fully visible; block qi is the masked diagonal
        carry = jax.lax.fori_loop(
            jnp.int32(0), qi.astype(jnp.int32),
            lambda ki, c: body(ki, c, masked=False), init)
        m, l, acc = body(qi.astype(jnp.int32), carry, masked=True)
    else:
        m, l, acc = jax.lax.fori_loop(
            jnp.int32(0), num_k,
            lambda ki, c: body(ki, c, masked=False), init)
    l_safe = jnp.maximum(l, jnp.float32(1e-30))
    o_ref[:] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[:] = (m + jnp.log(l_safe)).astype(jnp.float32)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, causal: bool, sm_scale: float, block_k: int):
    qi = pl.program_id(1)
    Bq, d = q_ref.shape
    S = k_ref.shape[0]
    q = q_ref[:]
    do = do_ref[:]
    lse = lse_ref[:]            # [Bq, 1]
    delta = delta_ref[:]        # [Bq, 1]

    num_k = jnp.int32(S // block_k)

    def body(ki, dq, masked):
        k = k_ref[pl.ds(ki * block_k, block_k), :]
        v = v_ref[pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * jnp.float32(sm_scale)
        if masked:
            q_pos = qi * Bq + jax.lax.broadcasted_iota(
                jnp.int32, (Bq, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (Bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(NEG_INF))
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * jnp.float32(sm_scale)
        dq = dq + jax.lax.dot_general(ds.astype(k.dtype), k,
                                      (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dq

    dq0 = jnp.zeros((Bq, d), jnp.float32)
    assert not causal or Bq == block_k, \
        "_pick_blocks guarantees square blocks; causal masking relies on it"
    if causal:
        dq = jax.lax.fori_loop(
            jnp.int32(0), qi.astype(jnp.int32),
            lambda ki, c: body(ki, c, masked=False), dq0)
        dq = body(qi.astype(jnp.int32), dq, masked=True)
    else:
        dq = jax.lax.fori_loop(
            jnp.int32(0), num_k,
            lambda ki, c: body(ki, c, masked=False), dq0)
    dq_ref[:] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, causal: bool, sm_scale: float,
                    block_q: int):
    ki = pl.program_id(1)
    Bk, d = k_ref.shape
    S = q_ref.shape[0]
    k = k_ref[:]
    v = v_ref[:]

    num_q = jnp.int32(S // block_q)

    def body(qi, carry, masked):
        dk, dv = carry
        q = q_ref[pl.ds(qi * block_q, block_q), :]
        do = do_ref[pl.ds(qi * block_q, block_q), :]
        lse = lse_ref[pl.ds(qi * block_q, block_q), :]
        delta = delta_ref[pl.ds(qi * block_q, block_q), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * jnp.float32(sm_scale)
        if masked:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, Bk), 0)
            k_pos = ki * Bk + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, Bk), 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(NEG_INF))
        p = jnp.exp(s - lse)
        pb = p.astype(do.dtype)
        dv = dv + jax.lax.dot_general(pb, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * jnp.float32(sm_scale)
        dk = dk + jax.lax.dot_general(ds.astype(q.dtype), q,
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    dk0 = jnp.zeros((Bk, d), jnp.float32)
    dv0 = jnp.zeros((Bk, d), jnp.float32)
    assert not causal or Bk == block_q, \
        "_pick_blocks guarantees square blocks; causal masking relies on it"
    if causal:
        # diagonal block qi == ki is masked; strictly-later q blocks see
        # this k block in full
        carry = body(ki.astype(jnp.int32), (dk0, dv0), masked=True)
        dk, dv = jax.lax.fori_loop(
            ki.astype(jnp.int32) + 1, num_q,
            lambda qi, c: body(qi, c, masked=False), carry)
    else:
        dk, dv = jax.lax.fori_loop(
            jnp.int32(0), num_q,
            lambda qi, c: body(qi, c, masked=False), (dk0, dv0))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _pick_blocks(S: int):
    """Largest power-of-two block <= 512 that divides S, or None when no
    block >= 8 divides S (caller must fall back to the XLA path — a
    non-dividing block floor-truncates the grid and leaves rows
    uninitialized).

    512 measured fastest on v5e at S=2048/d=64: grid-step overhead
    dominates below 256, VMEM pressure caps above 512 (see BENCH notes)."""
    for b in (512, 256, 128, 64, 32, 16, 8):
        if S % b == 0:
            return b, b
    return None


def causal_mask(q_len: int, k_len: int):
    """Boolean [q_len, k_len] causal mask with the diagonal aligned to
    the END of the kv sequence, so a 1-token decode query attends to the
    whole cache.  Single source of truth — the sdpa composite in
    nn.functional and the XLA fallback here both use it.

    Raises when q_len > k_len: end-aligned causal would fully mask the
    leading rows and softmax would silently return uniform garbage."""
    if q_len > k_len:
        raise ValueError(
            f"causal attention requires q_len <= kv_len, got "
            f"q_len={q_len} kv_len={k_len}")
    q_pos = jnp.arange(q_len)[:, None] + (k_len - q_len)
    k_pos = jnp.arange(k_len)[None, :]
    return q_pos >= k_pos


def _xla_sdpa(q, k, v, causal):
    """Reference XLA attention — fallback for shapes the Pallas kernel
    does not support (indivisible S, decode q_len != kv_len).  XLA fuses
    this well; autodiff is native."""
    d = q.shape[-1]
    qf = q.astype(jnp.float32) / math.sqrt(d)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32))
    if causal:
        s = jnp.where(causal_mask(q.shape[1], k.shape[1]), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def flash_attention(q, k, v, causal: bool = False):
    """q/k/v: [b, s, h, d] -> out [b, s, h, d].

    Routes to the Pallas kernel when the (static) shapes fit its blocking
    (q_len == kv_len, a power-of-two block >= 8 divides S); otherwise
    falls back to a fused XLA attention (decode shapes, odd lengths)."""
    if q.shape[1] == k.shape[1] and _pick_blocks(q.shape[1]) is not None:
        return _flash_pallas(q, k, v, causal)
    return _xla_sdpa(q, k, v, causal)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_pallas(q, k, v, causal: bool = False):
    out, _ = _flash_fwd(q, k, v, causal)
    return out


def _reshape_in(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _reshape_out(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _flash_fwd(q, k, v, causal):
    b, s, h, d = q.shape
    sm_scale = 1.0 / math.sqrt(d)
    qr, kr, vr = _reshape_in(q), _reshape_in(k), _reshape_in(v)
    bq, bk = _pick_blocks(s)
    grid = (b * h, s // bq)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, sm_scale=sm_scale,
                          block_k=bk),
        out_shape=(jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, s, 1), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda i, j: idx32(i, j, 0)),
            pl.BlockSpec((None, s, d), lambda i, j: idx32(i, 0, 0)),
            pl.BlockSpec((None, s, d), lambda i, j: idx32(i, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((None, bq, d), lambda i, j: idx32(i, j, 0)),
            pl.BlockSpec((None, bq, 1), lambda i, j: idx32(i, j, 0)),
        ),
        name="flash_fwd",
        interpret=_common.interpret(),
    )(qr, kr, vr)
    return _reshape_out(out, b, h), (qr, kr, vr, out, lse, b, h, s, d)


def _flash_fwd_vjp(q, k, v, causal):
    out, res = _flash_fwd(q, k, v, causal)
    return out, res


def _flash_bwd_vjp(causal, res, dout):
    qr, kr, vr, out, lse, b, h, s, d = res
    sm_scale = 1.0 / math.sqrt(d)
    do = _reshape_in(dout)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    bq, bk = _pick_blocks(s)
    interp = _common.interpret()

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal,
                          sm_scale=sm_scale, block_k=bk),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), qr.dtype),
        grid=(b * h, s // bq),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda i, j: idx32(i, j, 0)),
            pl.BlockSpec((None, s, d), lambda i, j: idx32(i, 0, 0)),
            pl.BlockSpec((None, s, d), lambda i, j: idx32(i, 0, 0)),
            pl.BlockSpec((None, bq, d), lambda i, j: idx32(i, j, 0)),
            pl.BlockSpec((None, bq, 1), lambda i, j: idx32(i, j, 0)),
            pl.BlockSpec((None, bq, 1), lambda i, j: idx32(i, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, d), lambda i, j: idx32(i, j, 0)),
        name="flash_bwd_dq",
        interpret=interp,
    )(qr, kr, vr, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal,
                          sm_scale=sm_scale, block_q=bq),
        out_shape=(jax.ShapeDtypeStruct((b * h, s, d), kr.dtype),
                   jax.ShapeDtypeStruct((b * h, s, d), vr.dtype)),
        grid=(b * h, s // bk),
        in_specs=[
            pl.BlockSpec((None, s, d), lambda i, j: idx32(i, 0, 0)),
            pl.BlockSpec((None, bk, d), lambda i, j: idx32(i, j, 0)),
            pl.BlockSpec((None, bk, d), lambda i, j: idx32(i, j, 0)),
            pl.BlockSpec((None, s, d), lambda i, j: idx32(i, 0, 0)),
            pl.BlockSpec((None, s, 1), lambda i, j: idx32(i, 0, 0)),
            pl.BlockSpec((None, s, 1), lambda i, j: idx32(i, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((None, bk, d), lambda i, j: idx32(i, j, 0)),
            pl.BlockSpec((None, bk, d), lambda i, j: idx32(i, j, 0)),
        ),
        name="flash_bwd_dkv",
        interpret=interp,
    )(qr, kr, vr, do, lse, delta)

    return (_reshape_out(dq, b, h), _reshape_out(dk, b, h),
            _reshape_out(dv, b, h))


_flash_pallas.defvjp(_flash_fwd_vjp, _flash_bwd_vjp)
