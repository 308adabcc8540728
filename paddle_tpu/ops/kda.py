"""Kimi Delta Attention (Kimi Linear, arXiv:2510.26692): a delta rule
whose decay is a VECTOR a head.  A head's state ``S`` is ``[K, V]``
(fp32, zero before the row); a position decays every key channel by its
own factor, reads what the decayed state holds for its key and writes
beta times the difference to its value:

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with ``g <= 0`` ``[K]`` the log of the decay and q, k l2-normed a head
(q also scaled by ``K ** -0.5``) — the norms are part of the operator
here, so that it reads q, k and v raw, side by side in the ONE array
``[b, s, 3 H K]`` a convolution leaves them in (``K = V``).

The chunked form (:func:`chunk_step`: ``Q`` positions at a time, ``cum``
the running sum of g inside the chunk — g times a triangle of ones —,
``Gamma = exp(cum)``, S the state that enters):

    A_ij = beta_i sum_d k_id k_jd exp(cum_id - cum_jd)    j < i, else 0
    P_ij = sum_d q_id k_jd exp(cum_id - cum_jd)           j <= i, else 0
    T = (I + A)^-1;  W = T (beta K Gamma);  U = T (beta V)
    N = U - W S;     o = (Q Gamma) S + P N
    S_end = Diag(Gamma_Q) S + (K Gamma_Q / Gamma)^T N

Every exponent is a difference ``cum_i - cum_j`` with ``j <= i`` (<= 0),
formed BEFORE ``exp``: ``1 / Gamma_j`` overflows under a strong decay.
The pair sums A and P are no product of two ``[Q, K]`` factors for that
reason; they are taken by sub-blocks of ``SUB`` positions: a pair in two
different sub-blocks through an ANCHOR, the later sub-block's first row
a (``exp(cum_i - a)`` and ``exp(a - cum_j)`` are both <= 1: a matrix
product), a pair inside one sub-block channel by channel.  ``(I + A)^-1``
is taken in fp32: the diagonal sub-blocks by elimination, the rest by a
series that ends after as many terms as there are sub-blocks
(:func:`_unit_lower_inverse` says why no shorter way is stable).

Where the shapes fit their tiles the chunks are walked by the Pallas
kernels of ``ops/pallas/kda_chunk.py`` (forward and backward, a
``custom_vjp``: the ``[Q, Q]`` matrices and the ``[Q, K]`` decays live
and die in VMEM, every head's state rides in scratch along the row);
else by :func:`kda_chunked_xla`, the same :func:`chunk_step` under
``vmap`` / ``lax.scan`` and jax's autodiff — chosen from shapes alone
(:func:`kda_chunk`).  A row that is no whole number of chunks is padded
at its end with ``g = 0``, ``beta = 0`` (no decay, no write), which
changes no earlier output.  :func:`kda_recurrence` is the recurrence
itself, position by position in fp32: what the tests hold both to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["kda_chunk", "kda_chunked_xla", "kda_recurrence", "chunk_step",
           "l2norm", "CHUNK"]

F32 = jnp.float32
L2_EPS = 1e-6
CHUNK = 64          # positions a chunk
SUB = 16            # positions a sub-block of the pair sums

_FORMS = {"nn": ((1,), (0,)), "nt": ((1,), (1,)), "tn": ((0,), (0,))}


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _mm(a, b, form: str, dt):
    """``a . b`` (``nn``), ``a . b^T`` (``nt``) or ``a^T . b`` (``tn``)
    on fp32 matrices with operands in ``dt`` and fp32 sums; fp32 operands
    at full precision.  Its derivative is written in the same three forms
    (autodiff's own transposes an array, which a kernel's body does not
    lower)."""
    hi = jax.lax.Precision.HIGHEST if dt == F32 else None
    return jax.lax.dot_general(a.astype(dt), b.astype(dt),
                               (_FORMS[form], ((), ())), precision=hi,
                               preferred_element_type=F32)


def _mm_fwd(a, b, form, dt):
    return _mm(a, b, form, dt), (a, b)


def _mm_bwd(form, dt, res, g):
    a, b = res
    if form == "nn":
        return _mm(g, b, "nt", dt), _mm(a, g, "tn", dt)
    if form == "nt":
        return _mm(g, b, "nn", dt), _mm(g, a, "tn", dt)
    return _mm(b, g, "nt", dt), _mm(a, g, "nn", dt)


_mm.defvjp(_mm_fwd, _mm_bwd)


def l2norm(x, eps: float = L2_EPS):
    """x over its last axis, ``x / sqrt(sum x^2 + eps)``, in fp32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _row_of(a, i: int):
    """Row ``i`` of ``a`` ``[Q, K]`` as ``[1, K]`` (a masked sum: no
    slice for autodiff to pad back)."""
    return jnp.sum(jnp.where(_iota(a.shape, 0) == i, a, 0.0), axis=0,
                   keepdims=True)


def _as_column(row):
    """``row`` ``[1, K]`` as ``[K, 1]``, without a transposition."""
    k = row.shape[1]
    eye = _iota((k, k), 0) == _iota((k, k), 1)
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (k, k)), 0.0),
                   axis=1, keepdims=True)


def _decay(x):
    """``exp(x)`` where ``x <= 0``, else 1: an entry whose difference is
    positive is masked by its user.  (Not ``minimum(x, 0)``: at a tie —
    two positions whose running sums round to the same number — that
    hands each side half the derivative.)"""
    return jnp.exp(jnp.where(x <= 0.0, x, 0.0))


def _pair_sums(q, k, cum):
    """``sum_d x_id k_jd exp(cum_id - cum_jd)`` for x = k and x = q,
    ``[Q, Q]`` each, right wherever ``j <= i`` (the rest is for the
    caller's masks): every exponent a difference that is <= 0.  And the
    first again for the pairs inside one sub-block, ``[sub-blocks, i,
    j]``: the diagonal blocks as the inverse takes them."""
    n, width = k.shape
    sub = SUB if n % SUB == 0 else n
    blocks = n // sub
    row, col = _iota((n, n), 0), _iota((n, n), 1)
    pos = _iota((n, width), 0)
    kk = jnp.zeros((n, n), F32)
    qk = jnp.zeros((n, n), F32)
    # two sub-blocks: through the later one's first row
    for blk in range(1, blocks):
        first = blk * sub
        anchor = _row_of(cum, first)
        later = jnp.logical_and(pos >= first, pos < first + sub)
        fall = jnp.where(later, _decay(cum - anchor), 0.0)
        rise = jnp.where(pos < first,
                         _decay(anchor - cum), 0.0)
        kj = k * rise
        kk += _mm(k * fall, kj, "nt", F32)
        qk += _mm(q * fall, kj, "nt", F32)
    # one sub-block: position jj of every sub-block against its rows,
    # channel by channel
    cut = lambda a: a.reshape(blocks, sub, width)
    q3, k3, c3 = cut(q), cut(k), cut(cum)
    at = _iota((blocks, sub, width), 1)
    # sub-block and place in it, by shifts and masks (SUB is a power of
    # two; a chunk that is no whole number of them is one sub-block)
    if blocks == 1:
        same, within = row >= 0, col
    else:
        bits = sub.bit_length() - 1
        same = jnp.right_shift(row, bits) == jnp.right_shift(col, bits)
        within = jnp.bitwise_and(col, sub - 1)
    kk3 = jnp.zeros((blocks, sub, sub), F32)
    lane3 = _iota(kk3.shape, 2)
    for jj in range(sub):
        pick = lambda a: jnp.sum(jnp.where(at == jj, a, 0.0), axis=1,
                                 keepdims=True)              # [blocks, 1, K]
        kj = pick(k3) * _decay(c3 - pick(c3))
        here = jnp.logical_and(same, within == jj)
        column = lambda x3: jnp.sum(x3 * kj, axis=2, keepdims=True)
        kk3 = jnp.where(lane3 == jj, column(k3), kk3)
        kk = jnp.where(here, column(k3).reshape(n, 1), kk)
        qk = jnp.where(here, column(q3).reshape(n, 1), qk)
    return kk, qk, kk3


def _unit_lower_inverse(a, a3):
    """``(I + a)^-1`` of a strictly lower-triangular ``a`` ``[Q, Q]``
    whose diagonal sub-blocks are also given as ``a3`` ``[blocks, SUB,
    SUB]``.  The blocks by ELIMINATION, a column at a time (``X <- X - a3[:,
    j] X[j, :]``: the one stable way — the keys of neighbouring positions
    point the same way, a's entries are then all of one sign and size, and
    the series ``sum_m (-a)^m`` has terms of 1e9 that cancel to 1e-19),
    every block at once on the VPU.  Across the blocks the series is
    short: with D the blocks' inverses side by side and F what of a lies
    under them, ``(I + a)^-1 = (I + D F)^-1 D`` and ``(D F)^blocks = 0``,
    so ``(I + G)^-1 = (I - G) (I + G^2) ...`` up to the power ``blocks /
    2`` — three terms at four blocks."""
    blocks, sub, _ = a3.shape
    n = blocks * sub
    i3, j3 = _iota(a3.shape, 1), _iota(a3.shape, 2)
    x = (i3 == j3).astype(F32)
    for j in range(sub - 1):
        column = jnp.sum(jnp.where(j3 == j, a3, 0.0), axis=2, keepdims=True)
        done = jnp.sum(jnp.where(i3 == j, x, 0.0), axis=1, keepdims=True)
        x = x - column * done
    if blocks == 1:
        return x.reshape(n, n)
    row, col = _iota((n, n), 0), _iota((n, n), 1)
    bits = sub.bit_length() - 1
    same = jnp.right_shift(row, bits) == jnp.right_shift(col, bits)
    # [Q, SUB] to the block diagonal of [Q, Q]: every entry times a one
    spread = (_iota((sub, n), 0) == jnp.bitwise_and(
        _iota((sub, n), 1), sub - 1)).astype(F32)
    inv = jnp.where(same, _mm(x.reshape(n, sub), spread, "nn", F32), 0.0)
    g = _mm(inv, jnp.where(same, 0.0, a), "nn", F32)
    series, power, reach = (row == col).astype(F32) - g, g, 2
    while reach < blocks:
        power = _mm(power, power, "nn", F32)
        series = series + _mm(series, power, "nn", F32)
        reach *= 2
    return _mm(series, inv, "nn", F32)


def chunk_step(q, k, v, g, beta, state, dt=jnp.bfloat16):
    """One head, one chunk.  q, k ``[Q, K]`` and v ``[Q, V]`` raw (any
    float type), g ``[Q, K]`` fp32 (the log decay), beta ``[Q, 1]`` fp32,
    ``state`` ``[K, V]`` fp32 the state that enters -> o ``[Q, V]`` fp32
    and the state that leaves.  ``dt``: the operand type of the four
    products with state-sized operands (fp32 sums); the running sum of g
    (a product with a triangle of ones) and the in-chunk matrices are
    formed, inverted and applied in fp32."""
    n, width = k.shape
    q = l2norm(q) * (width ** -0.5)
    k, v = l2norm(k), v.astype(F32)
    row, col = _iota((n, n), 0), _iota((n, n), 1)
    cum = _mm((row >= col).astype(F32), g, "nn", F32)
    kk, qk, kk3 = _pair_sums(q, k, cum)
    a = jnp.where(row > col, beta * kk, 0.0)
    p = jnp.where(row >= col, qk, 0.0)
    a3 = jnp.where(_iota(kk3.shape, 1) > _iota(kk3.shape, 2),
                   beta.reshape(kk3.shape[0], -1, 1) * kk3, 0.0)
    t = _unit_lower_inverse(a, a3)
    gamma = jnp.exp(cum)
    w = _mm(t, beta * (k * gamma), "nn", F32)
    u = _mm(t, beta * v, "nn", F32)
    new = u - _mm(w, state, "nn", dt)
    o = _mm(q * gamma, state, "nn", dt) + _mm(p, new, "nn", dt)
    end = _row_of(cum, n - 1)
    leaving = _as_column(jnp.exp(end)) * state \
        + _mm(k * jnp.exp(end - cum), new, "tn", dt)
    return o, leaving


def _heads(qkv, heads: int):
    """q, k, v ``[b, s, H, K]`` each out of ``[b, s, 3 H K]``."""
    b, s, _ = qkv.shape
    return tuple(jnp.moveaxis(qkv.reshape(b, s, 3, heads, -1), 2, 0))


def kda_recurrence(qkv, g, beta, heads: int):
    """The recurrence itself, position by position in fp32.  qkv ``[b, s,
    3 H K]`` (q | k | v, raw), g ``[b, s, H K]`` (<= 0), beta ``[b, s,
    H]`` -> o ``[b, s, H K]`` fp32."""
    b, s, _ = qkv.shape
    q, k, v = _heads(qkv.astype(F32), heads)
    width = q.shape[-1]
    q, k = l2norm(q) * (width ** -0.5), l2norm(k)
    g = g.astype(F32).reshape(b, s, heads, width)
    hi = jax.lax.Precision.HIGHEST

    def step(state, inp):                       # state [b, H, K, V]
        qt, kt, vt, gt, bt = inp
        state = state * jnp.exp(gt)[..., None]
        held = jnp.einsum("bhk,bhkv->bhv", kt, state, precision=hi)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", kt * bt[..., None], vt - held, precision=hi)
        return state, jnp.einsum("bhk,bhkv->bhv", qt, state, precision=hi)
    _, o = jax.lax.scan(
        step, jnp.zeros((b, heads, width, width), F32),
        tuple(jnp.moveaxis(a, 1, 0)
              for a in (q, k, v, g, beta.astype(F32))))
    return jnp.moveaxis(o, 0, 1).reshape(b, s, heads * width)


def kda_chunked_xla(qkv, g, beta, heads: int, chunk: int = CHUNK):
    """The chunked form in plain ``jnp`` under autodiff — the fallback,
    and the kernels' yardstick.  Shapes as :func:`kda_recurrence`, s a
    whole number of chunks -> o in qkv's dtype."""
    b, s, _ = qkv.shape
    q, k, v = _heads(qkv, heads)
    width = q.shape[-1]
    g = g.astype(F32).reshape(b, s, heads, width)
    # [chunks, b, H, Q, .]: the chunks in turn, rows and heads side by side
    cut = lambda a: jnp.transpose(
        a.reshape(b, s // chunk, chunk, heads, -1), (1, 0, 3, 2, 4))
    step = jax.vmap(jax.vmap(functools.partial(chunk_step, dt=qkv.dtype)))

    def walk(state, inp):
        o, state = step(*inp, state)
        return state, o
    _, o = jax.lax.scan(
        walk, jnp.zeros((b, heads, width, width), F32),
        (cut(q), cut(k), cut(v), cut(g), cut(beta.astype(F32))))
    return jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(
        b, s, heads * width).astype(qkv.dtype)


def kda_chunk(qkv, g, beta, heads: int, chunk: int = CHUNK):
    """qkv ``[b, s, 3 H K]`` (q | k | v as the convolution leaves them),
    g ``[b, s, H K]`` fp32 (the log of every key channel's decay, <= 0),
    beta ``[b, s, H]`` fp32 -> o ``[b, s, H K]`` in qkv's dtype: the
    kernels where they take the shapes, else :func:`kda_chunked_xla`."""
    from .pallas import kda_chunk as kernel
    b, s, _ = qkv.shape
    takes = kernel.takes(qkv, heads, chunk)
    whole = kernel.block_rows(s, chunk) if takes else chunk
    pad = -s % whole
    if pad:
        ext = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        qkv, g, beta = ext(qkv), ext(g), ext(beta)
    g, beta = g.astype(F32), beta.astype(F32)
    if takes:
        o = kernel.kda_chunked(qkv, g, beta, chunk)
    else:
        o = kda_chunked_xla(qkv, g, beta, heads, chunk)
    return o[:, :s]
