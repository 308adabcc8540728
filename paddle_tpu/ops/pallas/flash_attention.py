"""Flash attention (fwd + bwd) as Pallas TPU kernels.

TPU-native replacement for the reference's CUDA flashattn integration
(/root/reference/paddle/phi/kernels/gpu/flash_attn_kernel.cu, Python API
python/paddle/nn/functional/flash_attention.py:147).

FlashAttention-2 style: online-softmax forward saving per-row logsumexp;
backward recomputes per-block probabilities and accumulates dQ/dK/dV —
O(S) memory, blocked to MXU-friendly (block, head_dim) tiles.

Public layout matches the framework's sdpa: q ``[batch, seq, heads,
dim]``, k/v ``[batch, seq, kv_heads, dim]`` with ``kv_heads`` dividing
``heads``.  GQA is NATIVE: a query head's k/v index maps take ``head //
group``, so no repeated K/V exists anywhere and a K/V block is fetched
once a group; ``flash_bwd_dkv`` sums a group's query heads into the
shared dK/dV block in fp32 (innermost grid axis) and casts once.

The backward visits a (q block, k block) pair ONCE where it can, by a
rule of the shapes alone (``_flash_bwd_vjp``; no flag) — two budgets of
fp32 sums that wait in VMEM across grid steps, three outcomes:

(a) ``group*S*d*4 B <= ONE_PASS_DQ_BYTES`` (4 MiB) — KEY-major:
    ``flash_bwd_dkv`` holds dS^T for every pair it visits, so it adds
    dS K to the fp32 dQ of the KV head's whole group in VMEM scratch as
    well, forms delta = rowsum(dO*O) itself from ``o``, and returns (dk,
    dv, dq) — five block products and one exp pass a pair;
    ``flash_bwd_dq`` does not run and no delta array exists.  The SPLIT
    form (:func:`flash_attention_split`) goes by this rule too: in one
    pass ``flash_bwd_dkv`` sums dS k2 into a second fp32 scratch beside
    dQ's and returns (dk, dv, dk2, dq, dq2) — eight products, three of
    them d2 deep.  The dense cell (S 2,048, a group of two: 2 MiB) and
    the 8k split cell (S 8,192, a group of one: 4 MiB) sit here.
(b) else, by what waits within ``ONE_PASS_DKV_BYTES`` (16 MiB;
    ``lanes(d)`` is d rounded up to 128), each form in the loop order
    whose body it has:
    the SPLIT form, ``group*S*(lanes(d) + lanes(d2))*4 B`` — the SAME
    key-major pass as (a), the group's fp32 dQ and dQ2 counted as they
    lie in VMEM.  The plain-MLA cell sits here (S 16,384, d 128, d2 64,
    a group of one: 16 MiB exactly; 64 MiB of whole rows and sums
    resident, 72 asked; PERF.md §6, PR 57);
    the dense and windowed forms, ``2*S*lanes(d)*4 B`` — QUERY-major, on
    ``flash_bwd_dq``'s call site, grid and NAME: a q block's dQ is whole
    inside one grid step (delta stays in the step), and what waits
    across steps is the fp32 dK and dV of ONE KV head, two ``[S, d]``
    scratches whatever the group, zeroed at the KV head's first step and
    cast into the whole-row ``dk`` / ``dv`` blocks at its last.  The
    same five products and one exp pass a pair; ``flash_bwd_dkv`` does
    not run.  VMEM, asked by the shapes: K and V, the dk and dv blocks
    (each pair twice, the pipeline's buffers) and the two sums — 48 MiB
    at S 16,384, d 128, + 8 for the tiles.  The window cell's two forms
    (S 16,384), the hybrid, convolution and delta-rule cells' attention
    layers (S 8,192, groups of 4 and 8) sit here.
(c) else the two kernels: ``flash_bwd_dq`` (S, dP, dQ, and delta) runs
    first and ``flash_bwd_dkv`` forms dV and dK — seven products and two
    exp passes a pair, eleven in the split form.  No cell sits here (a
    row of 32,768 would, in either form).

So ``flash_bwd_dkv`` names three amounts of work (two kernels, one pass,
one pass split) and ``flash_bwd_dq`` two (the first of two kernels, the
query-major pass) until a ``benchmark`` PR renames (ROADMAP D14): the
readers sum a form's three names, and a kernel that does not run counts
0.  One body a call site either way.

The kernels work on ONE head's ``[rows, dim]`` tiles with that head's
K/V (forward, dq; in the query-major pass beside the KV head's dk and dv)
or Q/dO (dkv; in one pass also O, beside the group's dq block) resident
in VMEM (seq*dim*2B <= ~1MB at seq 4k, d 128; a call
whose resident operands pass Mosaic's 16 MiB asks for its sum).  How a
tile is ADDRESSED depends on ``dim`` alone (``_to_kernel``): with ``dim
% 128 == 0`` the operands stay where the projections wrote them — ``[b,
s, h, d]`` -> ``[b, s, h*d]`` is a bitcast, and a ``(rows, d)`` block
at (batch, row block, head) on the last axis is a legal Mosaic block —
so nothing is transposed on the way in or out; any other ``dim`` (a
lane slice of 32 or 64 is not a block Mosaic takes) goes through one
``[b, h, s, d]`` transpose an operand.  Same kernel bodies either way.

The per-row statistics (logsumexp, and delta where it is an array:
``flash_bwd_dq`` forms it from the tiles it already holds) live as
``[b, h, s/block, 1, block]``: a block's positions on LANES, the blocks
on an untiled axis, so a kernel takes one block by its index whatever
the block's size.  A ``[.., s, 1]`` array is 128x padded in the tiled
layout and was re-laid out by XLA between the kernels.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _common
from ._common import idx32

__all__ = ["flash_attention", "flash_attention_split"]

NEG_INF = -1e30


def _scores(a, b):
    """``a @ b.T`` with fp32 accumulation.  MXU dots run on the native
    (bf16) inputs — v5e's fp32 matmul rate is ~1/4 of bf16, so upcasting
    the operands would quarter kernel throughput for no accuracy gain."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _matmul(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _matmul_t(a, b):
    """``a.T @ b``: the product contracts the LEFT operand's rows."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _visible(q0, k0, shape, q_axis, window=None):
    """Visibility inside a block that is masked: ``shape`` holds query
    positions from ``q0`` along ``q_axis``, key positions from ``k0``
    along the other axis.  The diagonal block (``window`` None) is cut
    by the causal rule, key <= query; the window's EDGE block, ``window
    / block`` blocks below the diagonal, by the window's far end, key >
    query - window."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    if window is None:
        return q_pos >= k_pos
    return k_pos > q_pos - jnp.int32(window)


def _once_if(cond, at, body, carry):
    """``body(at, carry)`` where ``cond`` holds, else ``carry``: a loop
    of one trip or none."""
    return jax.lax.fori_loop(at, at + cond.astype(jnp.int32), body, carry)


def _over_keys(qi, n_window, body, init):
    """A query block's key blocks in order, for ``flash_fwd`` and
    ``flash_bwd_dq``: blocks [0, qi) whole and the diagonal masked — or,
    under a window of ``n_window`` blocks, the edge block ``qi -
    n_window`` masked where there is one, the blocks between whole, the
    diagonal masked: ``n_window + 1`` blocks at most, the rest of the row
    is never visited.  ``body(ki, carry, mask)``, mask None | "causal" |
    "window"."""
    if n_window is None:
        carry = jax.lax.fori_loop(
            jnp.int32(0), qi, lambda ki, c: body(ki, c, None), init)
        return body(qi, carry, "causal")
    edge = qi - jnp.int32(n_window)
    carry = _once_if(edge >= 0, edge,
                     lambda ki, c: body(ki, c, "window"), init)
    carry = jax.lax.fori_loop(
        jnp.maximum(edge + 1, 0), qi, lambda ki, c: body(ki, c, None), carry)
    return body(qi, carry, "causal")


def _masked(s, mask, q0, k0, q_axis, window):
    """Scores ``s`` with what ``mask`` hides at NEG_INF.  Only the
    diagonal block and a window's edge block pay for it (iota + cmp +
    select are pure VPU work); the blocks between are all-visible because
    the loop bounds exclude every other."""
    if mask is None:
        return s
    seen = _visible(q0, k0, s.shape, q_axis,
                    window if mask == "window" else None)
    return jnp.where(seen, s, jnp.float32(NEG_INF))


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, causal: bool,
                sm_scale: float, block_k: int, window=None):
    # q_ref/o_ref: [Bq, d]; k_ref/v_ref: [S, d]; lse_ref: [1, Bq].
    # SPLIT scores (:func:`flash_attention_split`): q2_ref [Bq, d2] and
    # k2_ref [S, d2] come before the outputs, and their product is added
    # to q k^T in fp32 before the one exp pass
    *second, o_ref, lse_ref = refs
    qi = pl.program_id(2).astype(jnp.int32)
    Bq, d = o_ref.shape
    S = k_ref.shape[0]
    q = q_ref[:]
    q2 = second[0][:] if second else None

    def body(ki, carry, mask):
        m_prev, l_prev, acc = carry
        k = k_ref[pl.ds(ki * block_k, block_k), :]
        v = v_ref[pl.ds(ki * block_k, block_k), :]
        s = _scores(q, k)
        if second:
            s = s + _scores(q2, second[1][pl.ds(ki * block_k, block_k), :])
        s = s * jnp.float32(sm_scale)
        s = _masked(s, mask, qi * Bq, ki * block_k, 0, window)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + _matmul(p.astype(v.dtype), v)
        return m_new, l_new, acc

    init = (jnp.full((Bq, 1), NEG_INF, jnp.float32),
            jnp.zeros((Bq, 1), jnp.float32),
            jnp.zeros((Bq, d), jnp.float32))
    assert not causal or Bq == block_k, \
        "_pick_blocks guarantees square blocks; causal masking relies on it"
    if causal:
        m, l, acc = _over_keys(qi, window and window // block_k, body, init)
    else:
        m, l, acc = jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(S // block_k),
            lambda ki, c: body(ki, c, None), init)
    l_safe = jnp.maximum(l, jnp.float32(1e-30))
    o_ref[:] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[:] = (m + jnp.log(l_safe)).T


def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *refs,
                   causal: bool, sm_scale: float, block_k: int, window=None,
                   group=None):
    # q/o/do/dq: [Bq, d]; k/v: [S, d]; lse_ref (in), delta_ref (out):
    # [1, Bq] — rows here are queries, so both turn once a grid step.
    # Split scores: q2_ref [Bq, d2], k2_ref [S, d2] before the outputs
    # and dq2_ref [Bq, d2] after them.
    #
    # ONE pass, query-major (``group`` given; dense and windowed forms):
    # ``refs`` is dq, dk, dv then the fp32 scratch dk_acc, dv_acc, all
    # four ``[S, d]`` of the KV head.  Every pair adds P^T dO and dS^T q
    # at its key block's rows; the KV head's first grid step (first head
    # of its group, q block 0) zeroes the sums and its last casts them
    # into dk_ref / dv_ref, whose block index holds over those steps.
    # delta stays in the step, and ``flash_bwd_dkv`` does not run
    second = ()
    if group is not None:
        dq_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs
    elif len(refs) == 2:
        dq_ref, delta_ref = refs
    else:
        *second, dq_ref, delta_ref, dq2_ref = refs
    qi = pl.program_id(2).astype(jnp.int32)
    Bq, d = q_ref.shape
    S = k_ref.shape[0]
    q = q_ref[:]
    q2 = second[0][:] if second else None
    do = do_ref[:]
    lse = lse_ref[:].T          # [Bq, 1]
    delta = jnp.sum(do.astype(jnp.float32) * o_ref[:].astype(jnp.float32),
                    axis=1, keepdims=True)
    delta_t = delta.T           # [1, Bq]: as it leaves, or as one pass reads
    n_k = jnp.int32(S // block_k)

    def over_key_rows(fn):
        def step(ki, _):
            fn(pl.ds(ki * block_k, block_k))
            return _
        jax.lax.fori_loop(jnp.int32(0), n_k, step, None)

    if group is not None:
        head = jax.lax.rem(pl.program_id(1).astype(jnp.int32),
                           jnp.int32(group))

        @pl.when((head == 0) & (qi == 0))
        def _start():
            def zero(rows):
                dk_acc[rows, :] = jnp.zeros((block_k, d), jnp.float32)
                dv_acc[rows, :] = jnp.zeros((block_k, d), jnp.float32)
            over_key_rows(zero)

    def body(ki, dqs, mask):
        rows = pl.ds(ki * block_k, block_k)
        k = k_ref[rows, :]
        v = v_ref[rows, :]
        if group is not None:
            # keys on rows, as ``_bwd_dkv_kernel`` forms its scores: dV
            # and dK are plain products, dQ the one with its left operand
            # turned (PERF.md §6, PR 45: timed against q k^T)
            st = _scores(k, q) * jnp.float32(sm_scale)      # [Bk, Bq]
            st = _masked(st, mask, qi * Bq, ki * block_k, 1, window)
            pt = jnp.exp(st - lse_ref[:])
            dv_acc[rows, :] += _matmul(pt.astype(do.dtype), do)
            dst = (pt * (_scores(v, do) - delta_t)
                   * jnp.float32(sm_scale)).astype(q.dtype)
            dk_acc[rows, :] += _matmul(dst, q)
            return (dqs[0] + _matmul_t(dst, k),)
        s = _scores(q, k)
        if second:
            k2 = second[1][rows, :]
            s = s + _scores(q2, k2)
        s = s * jnp.float32(sm_scale)
        s = _masked(s, mask, qi * Bq, ki * block_k, 0, window)
        p = jnp.exp(s - lse)
        ds = p * (_scores(do, v) - delta) * jnp.float32(sm_scale)
        dq = dqs[0] + _matmul(ds.astype(k.dtype), k)
        if second:
            return dq, dqs[1] + _matmul(ds.astype(k2.dtype), k2)
        return (dq,)

    dq0 = (jnp.zeros((Bq, d), jnp.float32),)
    if second:
        dq0 += (jnp.zeros(q2.shape, jnp.float32),)
    assert not causal or Bq == block_k, \
        "_pick_blocks guarantees square blocks; causal masking relies on it"
    if causal:
        dq = _over_keys(qi, window and window // block_k, body, dq0)
    else:
        dq = jax.lax.fori_loop(
            jnp.int32(0), n_k, lambda ki, c: body(ki, c, None), dq0)
    dq_ref[:] = dq[0].astype(dq_ref.dtype)
    if second:
        dq2_ref[:] = dq[1].astype(dq2_ref.dtype)
    if group is None:
        delta_ref[:] = delta_t
        return

    @pl.when((head == group - 1) & (qi == pl.num_programs(2) - 1))
    def _finish():
        def cast(rows):
            dk_ref[rows, :] = dk_acc[rows, :].astype(dk_ref.dtype)
            dv_ref[rows, :] = dv_acc[rows, :].astype(dv_ref.dtype)
        over_key_rows(cast)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, last_ref, *refs,
                    causal: bool, sm_scale: float, block_q: int,
                    also_dq: bool, split: bool = False, window=None):
    # k/v/dk/dv: [Bk, d] of one KV head; q/do: [S, d], lse: [S/Bq, 1, Bq]
    # of ONE query head of its group (grid axis 3, innermost).  Scores
    # are formed TRANSPOSED, keys on rows: a block's [1, Bq] statistics
    # then broadcast along sublanes as they lie, and dV = P^T dO,
    # dK = dS^T Q are plain products.
    #
    # Two kernels (``also_dq`` false): last_ref is the head's delta,
    # [S/Bq, 1, Bq], and dQ is ``flash_bwd_dq``'s.  ONE pass: last_ref is
    # the head's ``o`` [S, d], from which the head's first grid step
    # forms delta; every block pair adds dS K to the fp32 dQ of the whole
    # GROUP in scratch (a head's dQ is not revisited on consecutive grid
    # steps, so it cannot be an output block of its own), and the KV
    # head's last grid step casts it into dq_ref, the group's
    # [S, group*d] (flat) or [group, S, d] block.
    #
    # Split scores: q2_ref [S, d2] and k2_ref [Bk, d2] lead ``refs`` and
    # dk2_ref [Bk, d2] follows dv_ref: THIS head's part of the gradient
    # of a k2 that every head shares.  In one pass dS k2 is summed into
    # a second fp32 scratch beside dQ's and leaves as dq2_ref after
    # dq_ref, the group's block of q2's layout.
    #
    # ``refs``: (q2, k2,) dk, dv, (dk2,) [dq, (dq2,)] then the scratch
    # dk_acc, dv_acc, [dq_acc, (dq2_acc,) delta] — () split, [] one pass.
    ki = pl.program_id(2).astype(jnp.int32)
    g = pl.program_id(3).astype(jnp.int32)
    Bk, d = k_ref.shape
    S = q_ref.shape[0]
    k = k_ref[:]
    v = v_ref[:]
    if split:
        q2_ref, k2_ref, dk_ref, dv_ref, dk2_ref, *refs = refs
        k2 = k2_ref[:]
    else:
        dk_ref, dv_ref, *refs = refs
    # dQ (| dQ2): the key each is a product with, where it leaves, its sum
    keys = ((k, k2) if split else (k,)) if also_dq else ()
    dq_refs, (dk_acc, dv_acc, *dq_accs) = refs[:len(keys)], refs[len(keys):]
    if also_dq:
        delta_ref = dq_accs.pop()
        stat0 = g * (S // block_q)      # the head's blocks of the group's

        @pl.when(ki == 0)
        def _first_visit():
            # the first k block meets every q block of the head
            for acc in dq_accs:
                acc[g] = jnp.zeros(acc.shape[1:], jnp.float32)

            def form_delta(qi, _):
                rows = pl.ds(qi * block_q, block_q)
                delta_ref[stat0 + qi] = jnp.sum(
                    do_ref[rows, :].astype(jnp.float32)
                    * last_ref[rows, :].astype(jnp.float32),
                    axis=1, keepdims=True).T
                return _
            jax.lax.fori_loop(jnp.int32(0), jnp.int32(S // block_q),
                              form_delta, None)
    else:
        delta_ref, stat0 = last_ref, jnp.int32(0)

    def body(qi, carry, mask):
        dk, dv, *dk2 = carry
        rows = pl.ds(qi * block_q, block_q)
        q = q_ref[rows, :]
        do = do_ref[rows, :]
        lse = lse_ref[qi]
        delta = delta_ref[stat0 + qi]
        st = _scores(k, q)                                  # [Bk, Bq]
        if split:
            q2 = q2_ref[rows, :]
            st = st + _scores(k2, q2)
        st = st * jnp.float32(sm_scale)
        st = _masked(st, mask, qi * block_q, ki * Bk, 1, window)
        pt = jnp.exp(st - lse)
        dv = dv + _matmul(pt.astype(do.dtype), do)
        dst = (pt * (_scores(v, do) - delta)
               * jnp.float32(sm_scale)).astype(q.dtype)
        dk = dk + _matmul(dst, q)
        for acc, key in zip(dq_accs, keys):
            acc[g, rows, :] += _matmul_t(dst, key)
        if split:
            return dk, dv, dk2[0] + _matmul(dst, q2)
        return dk, dv

    # the group's first query head starts the fp32 sums, the others add
    # to them: consecutive grid steps revisit the same dK/dV block
    @pl.when(g == 0)
    def _start():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    carry = (dk_acc[:], dv_acc[:])
    if split:
        carry += (jnp.zeros(k2.shape, jnp.float32),)
    assert not causal or Bk == block_q, \
        "_pick_blocks guarantees square blocks; causal masking relies on it"
    n_q = jnp.int32(S // block_q)
    if causal and window:
        # the diagonal masked, then the q blocks that see this k block in
        # full, then the window's edge block ``ki + window / block``
        # masked where the row has one: the later q blocks never come
        edge = ki + jnp.int32(window // block_q)
        carry = body(ki, carry, "causal")
        carry = jax.lax.fori_loop(
            ki + 1, jnp.minimum(edge, n_q),
            lambda qi, c: body(qi, c, None), carry)
        dk, dv, *dk2 = _once_if(
            edge < n_q, edge, lambda qi, c: body(qi, c, "window"), carry)
    elif causal:
        # diagonal block qi == ki is masked; strictly-later q blocks see
        # this k block in full
        carry = body(ki, carry, "causal")
        dk, dv, *dk2 = jax.lax.fori_loop(
            ki + 1, n_q, lambda qi, c: body(qi, c, None), carry)
    else:
        dk, dv, *dk2 = jax.lax.fori_loop(
            jnp.int32(0), n_q, lambda qi, c: body(qi, c, None), carry)
    dk_acc[:] = dk
    dv_acc[:] = dv
    if split:
        dk2_ref[:] = dk2[0].astype(dk2_ref.dtype)

    last_head = g == pl.num_programs(3) - 1

    @pl.when(last_head)
    def _finish():
        dk_ref[:] = dk.astype(dk_ref.dtype)
        dv_ref[:] = dv.astype(dv_ref.dtype)

    if also_dq:
        @pl.when(last_head & (ki == pl.num_programs(2) - 1))
        def _finish_dq():
            for acc, ref in zip(dq_accs, dq_refs):
                if len(ref.shape) == 3:
                    ref[:] = acc[:].astype(ref.dtype)
                    continue
                # flat: a head is its lanes of the block, at a static offset
                width = acc.shape[2]
                for h in range(acc.shape[0]):
                    ref[:, h * width:(h + 1) * width] = \
                        acc[h].astype(ref.dtype)


# The backward runs in ONE pass (``flash_bwd_dkv`` sums dQ too and
# ``flash_bwd_dq`` does not run) where the fp32 dQ of one KV head's
# group, group*S*d*4 B, is at most this much VMEM scratch — rule (a),
# dense and split form alike.  Measured at 2 MiB (S 2048, group 2, d 128:
# 74.1 -> 53.2 ms a step) and, by the host's clock, at 4 MiB (S 4096:
# 71.5 -> 49.6); compiled for a described v5e at 4 MiB with S 8192
# (PERF.md §6, PR 30).  The split form at S 8192, a group of one, sits on
# it (PERF.md §6, PR 42).
ONE_PASS_DQ_BYTES = 4 << 20
# Past it a form still runs ONE pass where the fp32 sums that wait across
# ITS pass's grid steps are at most this much VMEM scratch — rule (b).
# The dense and the windowed form: the dK and dV of ONE KV head,
# 2*S*lanes(d)*4 B whatever the group — the QUERY-major pass, under the
# name ``flash_bwd_dq`` (its call site and grid), and ``flash_bwd_dkv``
# does not run; the window cell sits on it (S 16,384, d 128; PERF.md §6,
# PR 45).  The split form: the dQ and dQ2 of the group,
# group*S*(lanes(d) + lanes(d2))*4 B — rule (a)'s key-major pass at a
# longer row; the plain-MLA cell sits on it (S 16,384, d 128, d2 64, a
# group of one: exactly this; PERF.md §6, PR 57)
ONE_PASS_DKV_BYTES = 16 << 20
# what Mosaic gives a kernel unless the call asks for more (v5e: of 128 MiB)
_DEFAULT_VMEM_LIMIT = 16 << 20
# ``flash_fwd``'s two results under ``jax.ad_checkpoint.checkpoint_name``:
# a checkpoint boundary whose policy keeps BOTH runs the forward kernel
# once (``models/llama_pretrain._remat_wrap`` decides, by their bytes)
FWD_OUTPUT_NAMES = ("flash_out", "flash_lse")


def _pick_blocks(S: int, window=None):
    """Largest power-of-two block <= 512 that divides S — and the window,
    where there is one: its edge then lies in ONE block — or None when no
    block >= 8 does (caller must fall back to the XLA path — a
    non-dividing block floor-truncates the grid and leaves rows
    uninitialized).

    512 is the only block measured at head dim 128 (PERF.md §6, PR 30;
    v5e, 8 rows of S=2048, 16 query / 8 KV heads, 18 layers a step):
    ``flash_fwd`` 27.3 ms a run, the one-pass ``flash_bwd_dkv`` 53.2
    (PR 28's two kernels: ``flash_bwd_dq`` 32.8 + ``flash_bwd_dkv``
    41.3) — 34.9 % of the bf16 peak together.  Smaller blocks pay more
    grid steps a row, larger ones more VMEM a step."""
    for b in (512, 256, 128, 64, 32, 16, 8):
        if S % b == 0 and (window or b) % b == 0:
            return b, b
    return None


def _pairs(s: int, block: int, causal: bool, window=None) -> int:
    """(q block, k block) pairs one head's kernel EXECUTES: causal, the
    loop bounds skip the pairs above the diagonal and run the diagonal's
    whole, masked; under a window they skip the pairs below its edge
    block too and run that whole, masked — a q block meets ``window /
    block + 1`` k blocks at most (252 pairs a head at S 16,384, window
    4,096, block 512, where 528 are causal and 224 the visible keys'
    worth).  Each counts as a pair in a ``cost_estimate``."""
    nb = s // block
    if not causal:
        return nb * nb
    reach = nb if window is None else min(window // block + 1, nb)
    return reach * (reach + 1) // 2 + (nb - reach) * reach


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


def _xla_sdpa(q, k, v, causal, window=None):
    """Reference XLA attention — fallback for shapes the Pallas kernel
    does not support (indivisible S, decode q_len != kv_len).  XLA fuses
    this well; autodiff is native.  GQA repeats K/V here: this is the
    fallback, not the fast path."""
    d = q.shape[-1]
    if k.shape[2] != q.shape[2]:
        k = jnp.repeat(k, q.shape[2] // k.shape[2], axis=2)
        v = jnp.repeat(v, q.shape[2] // v.shape[2], axis=2)
    qf = q.astype(jnp.float32) / math.sqrt(d)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32))
    if causal:
        seen = causal_mask(q.shape[1], k.shape[1])
        if window is not None:
            # end-aligned like the causal rule: the last ``window`` keys
            seen &= ~jnp.tril(jnp.ones_like(seen),
                              k.shape[1] - q.shape[1] - window)
        s = jnp.where(seen, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def flash_attention(q, k, v, causal: bool = False, window=None):
    """q: [b, s, h, d], k/v: [b, s, nkv, d] with nkv dividing h (GQA
    native) -> out [b, s, h, d].

    ``window`` (causal only): key j is visible to query i iff ``i -
    window < j <= i``.  A window the row does not outgrow hides nothing
    and is the dense form; a shorter one is the WINDOWED form — the same
    kernel bodies on fewer block pairs, under the names ``flash_win_*``.

    Routes to the Pallas kernel when the (static) shapes fit its blocking
    (q_len == kv_len, a block :func:`_pick_blocks` offers divides
    S and the window); otherwise falls back to a fused XLA attention
    (decode shapes, odd lengths)."""
    if q.shape[2] % k.shape[2] != 0:
        raise ValueError(
            f"q heads {q.shape[2]} must be a multiple of kv heads "
            f"{k.shape[2]}")
    if window is not None:
        if not causal or window < 1:
            raise ValueError(f"a window ({window}) is causal and >= 1")
        if window >= k.shape[1]:
            window = None
    if q.shape[1] == k.shape[1] and \
            _pick_blocks(q.shape[1], window) is not None:
        return _flash_pallas(q, k, v, None, None, causal,
                             1.0 / math.sqrt(q.shape[-1]), window)
    return _xla_sdpa(q, k, v, causal, window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_pallas(q, k, v, q2, k2, causal: bool, sm_scale: float,
                  window=None):
    """One entry for every form: ``q2`` / ``k2`` None is the dense one,
    else the scores are split (:func:`flash_attention_split`); ``window``
    an int is the windowed one."""
    return _flash_fwd(q, k, v, q2, k2, causal, sm_scale, window)[0]


def _to_kernel(x):
    """``[b, s, n, d]`` as the kernels address it: where it lies when a
    head is a whole number of lane tiles (a bitcast), else heads before
    the sequence (a transpose)."""
    b, s, n, d = x.shape
    if d % 128 == 0:
        return x.reshape(b, s, n * d)
    return x.transpose(0, 2, 1, 3)


def _from_kernel(x, n):
    """:func:`_to_kernel` undone, for an array of ``n`` heads."""
    if x.ndim == 3:
        b, s, nd = x.shape
        return x.reshape(b, s, n, nd // n)
    return x.transpose(0, 2, 1, 3)


def _tile_spec(rows, d, at):
    """BlockSpec of ``rows`` positions of one head of a ``_to_kernel``
    array: a ``[rows, d]`` ref.  ``at(*grid) -> (batch, head, block)``."""
    if d % 128 == 0:
        def index(*g):
            b, h, r = at(*g)
            return idx32(b, r, h)
        return pl.BlockSpec((None, rows, d), index)
    return pl.BlockSpec((None, None, rows, d),
                        lambda *g: idx32(*at(*g), 0))


def _stat_spec(blocks, block, at):
    """BlockSpec over a ``[b, h, s/block, 1, block]`` statistics array
    of one head: ONE block, a ``[1, block]`` ref, or (``blocks`` given)
    all of them, ``[blocks, 1, block]``.  The last two axes are whole
    either way, so Mosaic takes any block size."""
    def index(*g):
        b, h, r = at(*g)
        return idx32(b, h, r, 0, 0)
    return pl.BlockSpec((None, None, blocks, 1, block), index)


def _by_query_head(group):
    """Index maps of the (batch, query head, q block) grid that
    ``flash_fwd`` and ``flash_bwd_dq`` share: the block's own tile, and
    the whole K/V of the head's group — its index holds over the group's
    heads and their q blocks, consecutive grid steps, so the query-major
    pass's dk / dv blocks stay in VMEM until the KV head is done and
    leave once.  BOTH operands int32 before
    dividing: under jax_enable_x64 the grid indices trace as i64, and
    Mosaic's floor_divide lowering recurses on a scalar that is not
    int32."""
    def tile(i, j, r):
        return i, j, r

    def kv(i, j, r):
        return i, jnp.int32(j) // jnp.int32(group), 0
    return tile, kv


def _by_kv_head(group):
    """Index maps of ``flash_bwd_dkv``'s (batch, kv head, k block, query
    head of the group) grid: the K/V block's own tile, and the whole
    Q/dO/statistics of one query head.  The group is INNERMOST, so its
    heads accumulate into one resident dK/dV block."""
    def tile(i, j, r, g):
        return i, j, r

    def head(i, j, r, g):
        return i, jnp.int32(j) * group + jnp.int32(g), 0
    return tile, head


def _shared_spec(rows, d2, at):
    """BlockSpec of ``rows`` positions of the key ``[b, s, d2]`` that
    all heads share.  ``at`` as in :func:`_tile_spec`."""
    def index(*g):
        b, _, r = at(*g)
        return idx32(b, r, 0)
    return pl.BlockSpec((None, rows, d2), index)


def _lanes(d: int) -> int:
    """``d`` rounded up to whole lane tiles: a row's width in VMEM."""
    return -(-d // 128) * 128


def _vmem_for(resident: int):
    """The compiler parameters of a call whose whole-row operands take
    ``resident`` bytes of VMEM, the pipeline's two buffers counted: None
    (Mosaic's own limit) where they fit it beside 8 MiB for the tiles and
    the block products, else a limit of that sum — by the shapes alone."""
    resident += 8 << 20
    if resident <= _DEFAULT_VMEM_LIMIT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=resident)


def _row_vmem(s, d, itemsize, rows: int = 2):
    """What ``flash_fwd`` and the two-kernel backward ask of VMEM: a
    head's ``rows`` whole-row operands — K and V, or q and dO; five at
    most in the SPLIT form —, each row a whole number of lane tiles.
    The dense form's stay within Mosaic's own limit up to S 8,192 at d
    128 and ask past it (S 16,384: 16 MiB of K and V, 24 asked).  The
    one-pass backward asks for its own sum (``_flash_bwd_vjp``)."""
    return _vmem_for(2 * rows * s * _lanes(d) * itemsize)


def _flash_fwd(q, k, v, q2, k2, causal, sm_scale, window=None):
    b, s, h, d = q.shape
    qr, kr, vr = _to_kernel(q), _to_kernel(k), _to_kernel(v)
    bq, bk = _pick_blocks(s, window)
    tile, kv = _by_query_head(h // k.shape[2])
    second, second_specs = (), []
    if q2 is not None:
        second = (_to_kernel(q2), k2)
        second_specs = [_tile_spec(bq, k2.shape[2], tile),
                        _shared_spec(s, k2.shape[2], kv)]
    params = _row_vmem(s, d, q.dtype.itemsize, 5 if second else 2)
    nkv, d2 = k.shape[2], 0 if q2 is None else k2.shape[2]
    pairs = b * h * _pairs(s, bq, causal, window)
    it = q.dtype.itemsize
    cost = pl.CostEstimate(
        # a pair: q k^T (+ q2 k2^T) and p v; on its [bq, bk] scores the
        # scale, the running max, s - m and the row sum; exp of the
        # scores and of the max's step, a log a row
        flops=pairs * bq * bk * (2 * (2 * d + d2) + 4),
        transcendentals=pairs * bq * (bk + 1) + b * h * s,
        # q (q2) in and o out a tile a grid step; K and V a whole row
        # once a GROUP (the index holds over its heads), k2 once a batch
        # row; lse fp32
        bytes_accessed=it * b * s * (2 * h * d + 2 * nkv * d
                                     + (h + 1) * d2) + 4 * b * h * s)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, sm_scale=sm_scale,
                          block_k=bk, window=window),
        out_shape=(jax.ShapeDtypeStruct(qr.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, s // bq, 1, bq),
                                        jnp.float32)),
        grid=(b, h, s // bq),
        in_specs=[_tile_spec(bq, d, tile), _tile_spec(s, d, kv),
                  _tile_spec(s, d, kv)] + second_specs,
        out_specs=(_tile_spec(bq, d, tile), _stat_spec(None, bq, tile)),
        compiler_params=params,
        name="flash_win_fwd" if window else "flash_fwd",
        cost_estimate=cost,
        interpret=_common.interpret(),
    )(qr, kr, vr, *second)
    out = checkpoint_name(out, FWD_OUTPUT_NAMES[0])
    lse = checkpoint_name(lse, FWD_OUTPUT_NAMES[1])
    return _from_kernel(out, h), (qr, kr, vr, second, out, lse)


def _group_spec(s, group, d):
    """BlockSpec of ALL positions of the ``group`` query heads of one KV
    head of a ``_to_kernel`` array, on ``flash_bwd_dkv``'s grid: ``[s,
    group*d]`` (flat) or ``[group, s, d]``.  Its index holds over the
    k blocks and the group, so the block stays in VMEM until the KV head
    is done."""
    if d % 128 == 0:
        return pl.BlockSpec((None, s, group * d),
                            lambda i, j, r, g: idx32(i, 0, j))
    return pl.BlockSpec((None, group, s, d),
                        lambda i, j, r, g: idx32(i, j, 0, 0))


def _flash_bwd_vjp(causal, sm_scale, window, res, dout):
    qr, kr, vr, second, out, lse = res
    b, s, h, d = dout.shape
    nkv = kr.size // (b * s * d)
    group = h // nkv
    do = _to_kernel(dout)
    bq, bk = _pick_blocks(s, window)
    interp = _common.interpret()
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    d2 = second[1].shape[2] if second else 0

    tile, head = _by_kv_head(group)
    out_shape = [like(kr), like(vr)]
    out_specs = [_tile_spec(bk, d, tile), _tile_spec(bk, d, tile)]
    scratch = [pltpu.VMEM((bk, d), jnp.float32),
               pltpu.VMEM((bk, d), jnp.float32)]
    second_in = []
    if second:
        # a head's part of dk2 leaves in fp32, after dk and dv
        second_in = [_tile_spec(s, d2, head), _shared_spec(bk, d2, tile)]
        out_shape.append(jax.ShapeDtypeStruct(second[0].shape, jnp.float32))
        out_specs.append(_tile_spec(bk, d2, tile))
    params = _row_vmem(s, d, qr.dtype.itemsize, 5 if second else 2)
    # (a) by the fp32 dQ of a KV head's group, dense and split form alike
    # (the split form's dQ2 follows from the shapes and is asked of VMEM
    # below); (b) past it by the fp32 sums that wait across the grid steps
    # of the pass the form has past (a): the split form's key-major one —
    # the group's dQ AND dQ2, each row whole lane tiles —, the dense and
    # windowed forms' query-major one — dK and dV of ONE KV head
    waits = group * s * (_lanes(d) + _lanes(d2)) * 4 if second \
        else 2 * s * _lanes(d) * 4
    one_pass = group * s * d * 4 <= ONE_PASS_DQ_BYTES \
        or bool(second) and waits <= ONE_PASS_DKV_BYTES
    by_query = not one_pass and waits <= ONE_PASS_DKV_BYTES
    pairs = b * h * _pairs(s, bq, causal, window)
    it = qr.dtype.itemsize
    # on a pair's scores, either kernel: the scale, s - lse, dP - delta
    # and the two products that give dS
    on_scores = 5
    if one_pass:
        # o in delta's place; dq (| dq2) the last outputs, the group's
        # blocks; the group's fp32 dQ (| dQ2) and delta in scratch
        last, last_spec = out, _tile_spec(s, d, head)
        for x, width in [(qr, d)] + ([(second[0], d2)] if second else []):
            out_shape.append(like(x))
            out_specs.append(_group_spec(s, group, width))
            scratch.append(pltpu.VMEM((group, s, width), jnp.float32))
        scratch.append(pltpu.VMEM((group * (s // bq), 1, bq), jnp.float32))
        # q, do, o and the group's dq block twice (the pipeline's two
        # buffers) beside the fp32 dQ, and so q2 and the group's dq2 block
        # beside the fp32 dQ2, each row of them a whole lane tile; the
        # rest read 4.8 MiB at most (AOT for a described v5e, S 4096 and
        # 8192)
        params = _vmem_for(((2 * (3 + group) * it + 4 * group) * d
                            + (2 * (1 + group) * it + 4 * group) * _lanes(d2))
                           * s)
    else:
        by_q, kv = _by_query_head(group)
        second_dq = [_tile_spec(bq, d2, by_q), _shared_spec(s, d2, kv)] \
            if second else []
        if by_query:
            # dk, dv in delta's place: the KV head's whole rows, beside
            # their fp32 sums; K, V and the two twice (the pipeline's two
            # buffers)
            dq_out = [like(kr), like(vr)]
            dq_specs = [_tile_spec(s, d, kv), _tile_spec(s, d, kv)]
            dq_scratch = [pltpu.VMEM((s, d), jnp.float32)] * 2
            dq_params = _vmem_for((2 * 4 * it + 2 * 4) * s * _lanes(d))
        else:
            dq_out = [jax.ShapeDtypeStruct(lse.shape, jnp.float32)] \
                + [like(x) for x in second[:1]]
            dq_specs = [_stat_spec(None, bq, by_q)] + second_dq[:1]
            dq_scratch, dq_params = [], params
        cost = pl.CostEstimate(
            # a pair: q k^T (+ q2 k2^T), dO v^T, dS k (+ dS k2) — in one
            # pass P^T dO and dS^T q too; delta a row once
            flops=pairs * bq * bk * (2 * ((5 if by_query else 3) * d
                                          + 2 * d2) + on_scores)
            + 2 * b * h * s * d,
            transcendentals=pairs * bq * bk,
            # q, o, dO (q2) in and dq (dq2) out a tile a grid step, K, V
            # and k2 as the forward fetches them, lse in and delta out —
            # or, in one pass, dk and dv out once a KV head
            bytes_accessed=it * b * s * (4 * h * d + 2 * nkv * d
                                         + (2 * h + 1) * d2
                                         + (2 * nkv * d if by_query else 0))
            + (1 if by_query else 2) * 4 * b * h * s)
        dq, *rest = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, causal=causal,
                              sm_scale=sm_scale, block_k=bk, window=window,
                              group=group if by_query else None),
            out_shape=[like(qr)] + dq_out,
            grid=(b, h, s // bq),
            in_specs=[_tile_spec(bq, d, by_q), _tile_spec(s, d, kv),
                      _tile_spec(s, d, kv), _tile_spec(bq, d, by_q),
                      _tile_spec(bq, d, by_q), _stat_spec(None, bq, by_q)]
            + second_dq,
            out_specs=[_tile_spec(bq, d, by_q)] + dq_specs,
            scratch_shapes=dq_scratch,
            compiler_params=dq_params,
            name="flash_win_bwd_dq" if window else "flash_bwd_dq",
            cost_estimate=cost,
            interpret=interp,
        )(qr, kr, vr, out, do, lse, *second)
        if by_query:
            return (_from_kernel(dq, h), _from_kernel(rest[0], nkv),
                    _from_kernel(rest[1], nkv), None, None)
        last, *dq2_kernel = rest
        last_spec = _stat_spec(s // bq, bq, head)

    # a query head's whole-row operands turn with the INNERMOST axis:
    # with more than one head a group they are fetched again at every
    # grid step, alone they stay while the head's k blocks pass
    visits = b * nkv * (s // bk) * group if group > 1 else b * nkv
    cost = pl.CostEstimate(
        # a pair: k q^T (+ k2 q2^T), P^T dO, v dO^T, dS^T q (+ dS^T q2),
        # in one pass dS k (+ dS k2) too, and delta a row once
        flops=pairs * bq * bk * (2 * (4 * d + 2 * d2
                                      + (d + d2 if one_pass else 0))
                                 + on_scores)
        + (2 * b * h * s * d if one_pass else 0),
        transcendentals=pairs * bq * bk,
        # a head's q, dO (q2), lse and o (one pass) or delta a visit; K,
        # V (k2) a tile a grid step; dk, dv (dq, dq2 in one pass) out
        # once, a head's part of dk2 in fp32
        bytes_accessed=visits * s * (
            it * (2 * d + d2 + (d if one_pass else 0))
            + 4 * (1 if one_pass else 2))
        + it * b * s * (4 * nkv * d + h * d2
                        + (h * (d + d2) if one_pass else 0))
        + 4 * b * s * h * d2)
    grads = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal,
                          sm_scale=sm_scale, block_q=bq, also_dq=one_pass,
                          split=bool(second), window=window),
        out_shape=out_shape,
        grid=(b, nkv, s // bk, group),
        in_specs=[_tile_spec(s, d, head), _tile_spec(bk, d, tile),
                  _tile_spec(bk, d, tile), _tile_spec(s, d, head),
                  _stat_spec(s // bq, bq, head), last_spec] + second_in,
        out_specs=out_specs,
        scratch_shapes=scratch,
        compiler_params=params,
        name="flash_win_bwd_dkv" if window else "flash_bwd_dkv",
        cost_estimate=cost,
        interpret=interp,
    )(qr, kr, vr, do, lse, last, *second)
    dk, dv = grads[:2]
    if one_pass:
        dq, *dq2_kernel = grads[3 if second else 2:]
    dq2 = dk2 = None
    if second:
        # [b, h, s, d2] or [b, s, h*d2]: the heads' sum is the shared key's
        dq2 = _from_kernel(dq2_kernel[0], h)
        dk2 = _from_kernel(grads[2], h).sum(axis=2).astype(second[1].dtype)

    return (_from_kernel(dq, h), _from_kernel(dk, nkv), _from_kernel(dv, nkv),
            dq2, dk2)


_flash_pallas.defvjp(_flash_fwd, _flash_bwd_vjp)


# ---------------------------------------------------------------------------
# scores from TWO operand pairs: latent attention's 128-wide per-head
# part and its 64-wide rotated part, whose key every head shares
# ---------------------------------------------------------------------------
def flash_attention_split(q, q2, k, k2, v, sm_scale: float):
    """Causal attention whose scores are the sum of two products:

        S = (q k^T + q2 k2^T) * sm_scale,   out = softmax(S) v

    q, k, v ``[b, s, h, d]`` with ``d % 128 == 0`` — read where they lie,
    as the dense entry reads them; q2 ``[b, s, h, d2]``; k2 ``[b, s,
    d2]``, ONE key a token for all heads (MLA's rotated part: d 128, d2
    64).  Both products are summed in fp32 before the one exp pass; no
    ``[.., d + d2]`` operand and no h-fold copy of k2 is made.  The
    backward returns five gradients, in ONE key-major pass
    (``flash_bwd_dkv`` sums dQ and dQ2 too) where the group's fp32 dQ is
    within ``ONE_PASS_DQ_BYTES`` — the dense form's rule: the 8k cell —
    or its dQ and dQ2 together, each row whole lane tiles, within
    ``ONE_PASS_DKV_BYTES`` — the plain-MLA cell's row of 16,384 —, from
    the two kernels past both; a head's part of dk2 leaves the kernel in
    fp32 and the heads are summed outside it.  Same kernel
    bodies, names and blocks as :func:`flash_attention`."""
    b, s, h, d = q.shape
    if d % 128 or k.shape != q.shape or v.shape != q.shape or \
            q2.shape[:3] != (b, s, h) or k2.shape != (b, s, q2.shape[3]):
        raise ValueError(
            f"flash_attention_split: q {q.shape}, q2 {q2.shape}, k "
            f"{k.shape}, k2 {k2.shape}, v {v.shape}")
    if _pick_blocks(s) is None:
        raise ValueError(f"flash_attention_split: no block divides S={s}")
    return _flash_pallas(q, k, v, q2, k2, True, float(sm_scale), None)
