"""Paged-KV decode attention (block-table cache) Pallas kernel.

Reference role: the reference's paged/continuous-batching serving
attention — ``incubate.nn.functional.block_multihead_attention``
(/root/reference/python/paddle/incubate/nn/functional/
block_multihead_attention.py) over its CUDA block-cache kernels.

TPU-native design: the KV cache is a POOL of fixed-size pages
``[num_pages, nkv, page, d]`` shared by all requests; each request owns
an int32 block table (page indices) and a context length.  The decode
kernel runs one grid step per (batch row x kv head x page): the page to
DMA is chosen by the BLOCK TABLE through a scalar-prefetch index map —
Mosaic fetches exactly the pages a row actually uses, so attention HBM
traffic scales with the row's real length, not the batch-wide maximum
(the dense ``[B, S_max]`` cache reads everything and masks).  Pages
past ``ceil(len/page)`` are skipped with ``pl.when``; online-softmax
state lives in VMEM scratch across the sequential page loop.

This is the serving-side analog of the varlen training kernel
(flash_varlen.py): same "only touch the blocks that matter" idea, block
tables instead of segment boundaries.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _common
from ._common import idx32
from .flash_attention import NEG_INF

__all__ = ["paged_decode_attention", "paged_decode_attention_xla",
           "paged_decode_attention_q8", "quantize_kv_token"]


def _i32(x):
    return jnp.int32(x)


def _kernel_q8(tables_ref, lens_ref, q_ref, kp_ref, vp_ref, ks_ref,
               vs_ref, o_ref, m_ref, l_ref, acc_ref, *, page: int,
               nkv: int, pages_max: int, sm_scale: float):
    """int8-KV variant: pages carry int8 K/V plus per-(head, slot) f32
    scales — HALF the cache HBM traffic of bf16 pages, which is the
    binding resource in the large-batch decode regime (PERF.md).
    Dequant happens in VMEM after the DMA (the bf16 copy never exists
    in HBM — same trade as the weight-only int8 matmul kernel)."""
    b = pl.program_id(0).astype(jnp.int32)
    j = pl.program_id(1).astype(jnp.int32)
    n, d = q_ref.shape
    g = n // nkv
    ln = lens_ref[b]
    used = (ln + _i32(page) - _i32(1)) // _i32(page)

    @pl.when(j == _i32(0))
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(j < used)
    def _page():
        q = q_ref[:].reshape(nkv, g, d)
        ks = ks_ref[:]                          # [nkv, page] f32
        vs = vs_ref[:]
        # the int8 pages feed the MXU directly as bf16 (the
        # int8_matmul pattern); the per-(head, slot) scales fold into
        # the LOGITS and the PROBABILITIES instead — both are [.., page]
        # with page on the minor dim, so no d-axis dequant broadcast:
        #   q·(k_q·ks) == (q·k_q)·ks   and   Σ p·(v_q·vs) == Σ (p·vs)·v_q
        k = kp_ref[:].astype(jnp.bfloat16)
        v = vp_ref[:].astype(jnp.bfloat16)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        s = s * ks[:, None, :] * jnp.float32(sm_scale)
        pos = j * _i32(page) + jax.lax.broadcasted_iota(
            jnp.int32, (nkv, g, page), 2)
        valid = pos < ln
        s = jnp.where(valid, s, jnp.float32(NEG_INF))
        m_prev = m_ref[:].reshape(nkv, g, 128)[:, :, :1]
        l_prev = l_ref[:].reshape(nkv, g, 128)[:, :, :1]
        m_cur = jnp.max(s, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(valid, jnp.exp(s - m_new), jnp.float32(0.0))
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
        l_ref[:] = jnp.broadcast_to(l_new, (nkv, g, 128)).reshape(n, 128)
        m_ref[:] = jnp.broadcast_to(m_new, (nkv, g, 128)).reshape(n, 128)
        pv = jax.lax.dot_general(
            (p * vs[:, None, :]).astype(v.dtype), v,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha.reshape(n, 1) + pv.reshape(n, d)

    l_safe = jnp.maximum(l_ref[:, :1], jnp.float32(1e-30))
    o_ref[:] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def _kernel(tables_ref, lens_ref, q_ref, kp_ref, vp_ref, o_ref,
            m_ref, l_ref, acc_ref, *, page: int, nkv: int,
            pages_max: int, sm_scale: float):
    # grid (B, pages): ONE step covers all heads of a (row, page) —
    # the page DMA is [nkv, page, d] (hundreds of KB, not the per-head
    # [page, d] sliver a (B*nkv, pages) grid would fetch; measured 2.3x
    # on the 1.3B decode)
    b = pl.program_id(0).astype(jnp.int32)
    j = pl.program_id(1).astype(jnp.int32)      # page slot in the table
    n, d = q_ref.shape
    g = n // nkv
    ln = lens_ref[b]
    used = (ln + _i32(page) - _i32(1)) // _i32(page)

    @pl.when(j == _i32(0))
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(j < used)
    def _page():
        q = q_ref[:].reshape(nkv, g, d)         # heads-major rows
        k = kp_ref[:]                           # [nkv, page, d]
        v = vp_ref[:]
        # batched-over-heads q @ k^T: [nkv, g, page]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        s = s * jnp.float32(sm_scale)
        pos = j * _i32(page) + jax.lax.broadcasted_iota(
            jnp.int32, (nkv, g, page), 2)
        valid = pos < ln
        s = jnp.where(valid, s, jnp.float32(NEG_INF))
        m_prev = m_ref[:].reshape(nkv, g, 128)[:, :, :1]
        l_prev = l_ref[:].reshape(nkv, g, 128)[:, :, :1]
        m_cur = jnp.max(s, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(valid, jnp.exp(s - m_new), jnp.float32(0.0))
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
        l_ref[:] = jnp.broadcast_to(l_new, (nkv, g, 128)).reshape(n, 128)
        m_ref[:] = jnp.broadcast_to(m_new, (nkv, g, 128)).reshape(n, 128)
        # [nkv, g, page] @ [nkv, page, d] -> [nkv, g, d]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha.reshape(n, 1) + pv.reshape(n, d)

    # EVERY grid step writes its output block (last write wins) —
    # cheaper to keep the block unconditionally written than to rely
    # on revisit semantics for a block only the final j touches
    l_safe = jnp.maximum(l_ref[:, :1], jnp.float32(1e-30))
    o_ref[:] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def _cost(B, n, d, nkv, page, pages_max, kv_itemsize, q_itemsize,
          q8: bool):
    """The bound a decode step is launched at: every row at
    ``pages_max`` pages (how many a row uses is data).  A page: q k^T
    and p v for all heads, four passes over the ``[n, page]`` scores
    (scale, max, s - m, row sum; int8 pools: two more for the scales),
    exp of the scores and of the max's step; K's and V's page fetched
    (int8: their fp32 scales too), q in and out once a row."""
    steps = B * pages_max
    return pl.CostEstimate(
        flops=steps * n * page * (4 * d + (6 if q8 else 4)),
        transcendentals=steps * n * (page + 1),
        bytes_accessed=steps * 2 * nkv * page * (d * kv_itemsize
                                                 + (4 if q8 else 0))
        + 2 * B * n * d * q_itemsize)


def paged_decode_attention_xla(q, kpool, vpool, block_tables,
                               context_lens, sm_scale=None):
    """Pure-XLA reference: gather each row's pages and run masked
    attention.  Used (a) as the parity oracle in tests and (b) as the
    execution path OFF-TPU, where interpreting the kernel per decode
    step is pointless overhead — the kernel's block-table DMA exists
    for TPU HBM traffic, which XLA:CPU does not model."""
    B, n, d = q.shape
    num_pages, nkv, page, _ = kpool.shape
    pages_max = block_tables.shape[1]
    g = n // nkv
    sm_scale = sm_scale or (1.0 / math.sqrt(d))
    tables = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(context_lens, jnp.int32)
    # [B, pages_max, nkv, page, d] -> [B, nkv, S, d]
    kg = jnp.take(kpool, tables, axis=0).transpose(0, 2, 1, 3, 4)
    vg = jnp.take(vpool, tables, axis=0).transpose(0, 2, 1, 3, 4)
    S = pages_max * page
    kg = kg.reshape(B, nkv, S, d)
    vg = vg.reshape(B, nkv, S, d)
    q5 = q.reshape(B, nkv, g, d)
    s = jnp.einsum("bhgd,bhkd->bhgk", q5.astype(jnp.float32),
                   kg.astype(jnp.float32)) * sm_scale
    valid = (jnp.arange(S)[None] < lens[:, None])[:, None, None, :]
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, vg.astype(jnp.float32))
    return out.reshape(B, n, d).astype(q.dtype)


def paged_decode_attention(q, kpool, vpool, block_tables, context_lens,
                           sm_scale=None, force_kernel=False):
    """One decode step of attention against a paged KV cache.

    q:             [B, n, d]        (single new token per row)
    kpool/vpool:   [num_pages, nkv, page, d]
    block_tables:  [B, pages_max] int32 — page ids per row (entries past
                   the row's length must still be VALID ids, e.g. 0;
                   they are skipped, not read... fetched but masked)
    context_lens:  [B] int32 — valid kv entries per row (including the
                   current token, whose k/v must already be written)
    -> [B, n, d]
    """
    B, n, d = q.shape
    num_pages, nkv, page, _ = kpool.shape
    pages_max = block_tables.shape[1]
    g = n // nkv
    sm_scale = sm_scale or (1.0 / math.sqrt(d))
    if _common.interpret() and not force_kernel:
        return paged_decode_attention_xla(q, kpool, vpool, block_tables,
                                          context_lens, sm_scale)
    tables = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(context_lens, jnp.int32)

    out = pl.pallas_call(
        functools.partial(_kernel, page=page, nkv=nkv,
                          pages_max=pages_max, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, pages_max),
            in_specs=[
                pl.BlockSpec((None, n, d),
                             lambda b, j, *_: idx32(b, 0, 0)),
                pl.BlockSpec(
                    (None, nkv, page, d),
                    lambda b, j, tables, lens: idx32(
                        tables[b, j], 0, 0, 0)),
                pl.BlockSpec(
                    (None, nkv, page, d),
                    lambda b, j, tables, lens: idx32(
                        tables[b, j], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, n, d),
                                   lambda b, j, *_: idx32(b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((n, 128), jnp.float32),     # m
                pltpu.VMEM((n, 128), jnp.float32),     # l
                pltpu.VMEM((n, d), jnp.float32),       # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, n, d), q.dtype),
        name="paged_attn",
        cost_estimate=_cost(B, n, d, nkv, page, pages_max,
                            kpool.dtype.itemsize, q.dtype.itemsize,
                            q8=False),
        interpret=_common.interpret(),
    )(tables, lens, q, kpool, vpool)
    return out


def quantize_kv_token(k):
    """Per-(row, head) symmetric int8 quantisation of one token's K or
    V [B, nkv, d] -> (int8 [B, nkv, d], f32 scale [B, nkv])."""
    kf = k.astype(jnp.float32)
    s = jnp.max(jnp.abs(kf), axis=-1) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    q = jnp.clip(jnp.round(kf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def paged_decode_attention_q8_xla(q, kpool, vpool, kscale, vscale,
                                  block_tables, context_lens,
                                  sm_scale=None):
    """XLA oracle/off-TPU path for the int8-KV pools: dequantise the
    gathered pages and reuse the fp reference."""
    tables = jnp.asarray(block_tables, jnp.int32)
    kg = jnp.take(kpool, tables, axis=0).astype(jnp.float32)
    vg = jnp.take(vpool, tables, axis=0).astype(jnp.float32)
    ksg = jnp.take(kscale, tables, axis=0)      # [B, pm, nkv, page]
    vsg = jnp.take(vscale, tables, axis=0)
    kg = (kg * ksg[..., None]).astype(q.dtype)
    vg = (vg * vsg[..., None]).astype(q.dtype)
    B, pm, nkv, page, d = kg.shape
    # re-pack as bf16 pools indexed by identity tables
    ident = jnp.arange(B * pm, dtype=jnp.int32).reshape(B, pm)
    return paged_decode_attention_xla(
        q, kg.reshape(B * pm, nkv, page, d),
        vg.reshape(B * pm, nkv, page, d), ident, context_lens, sm_scale)


def paged_decode_attention_q8(q, kpool, vpool, kscale, vscale,
                              block_tables, context_lens,
                              sm_scale=None, force_kernel=False):
    """int8-KV paged decode attention.

    kpool/vpool:    [num_pages, nkv, page, d] int8
    kscale/vscale:  [num_pages, nkv, page] f32 (per head x slot)
    Other args/semantics as :func:`paged_decode_attention`.
    """
    B, n, d = q.shape
    num_pages, nkv, page, _ = kpool.shape
    pages_max = block_tables.shape[1]
    sm_scale = sm_scale or (1.0 / math.sqrt(d))
    if _common.interpret() and not force_kernel:
        return paged_decode_attention_q8_xla(
            q, kpool, vpool, kscale, vscale, block_tables,
            context_lens, sm_scale)
    tables = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(context_lens, jnp.int32)
    out = pl.pallas_call(
        functools.partial(_kernel_q8, page=page, nkv=nkv,
                          pages_max=pages_max, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, pages_max),
            in_specs=[
                pl.BlockSpec((None, n, d),
                             lambda b, j, *_: idx32(b, 0, 0)),
                pl.BlockSpec(
                    (None, nkv, page, d),
                    lambda b, j, tables, lens: idx32(
                        tables[b, j], 0, 0, 0)),
                pl.BlockSpec(
                    (None, nkv, page, d),
                    lambda b, j, tables, lens: idx32(
                        tables[b, j], 0, 0, 0)),
                pl.BlockSpec(
                    (None, nkv, page),
                    lambda b, j, tables, lens: idx32(
                        tables[b, j], 0, 0)),
                pl.BlockSpec(
                    (None, nkv, page),
                    lambda b, j, tables, lens: idx32(
                        tables[b, j], 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, n, d),
                                   lambda b, j, *_: idx32(b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((n, 128), jnp.float32),     # m
                pltpu.VMEM((n, 128), jnp.float32),     # l
                pltpu.VMEM((n, d), jnp.float32),       # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, n, d), q.dtype),
        name="paged_attn_q8",
        cost_estimate=_cost(B, n, d, nkv, page, pages_max, 1,
                            q.dtype.itemsize, q8=True),
        interpret=_common.interpret(),
    )(tables, lens, q, kpool, vpool, kscale, vscale)
    return out
