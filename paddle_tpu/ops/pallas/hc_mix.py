"""Pallas TPU kernels for the mixers of ``n`` residual streams
(manifold-constrained hyper-connections): the passes over the streams
``X [T, n C]`` (stream i is columns ``i C .. (i + 1) C``) of one sublayer

    h  = sum_i H_pre[i] X_i                       y = F(h)
    X'_j = sum_i H_res[j][i] X_i + H_post[j] y

each read the streams from HBM ONCE.  XLA's own fusions of the same
passes run near the HBM's rate already but move each stream several
times (the sum of squares, the product with phi and the weighted sum
are three reads of X; the backward's sums over streams as many again).

``hc_pre_fwd``   a token tile's whole row of X in VMEM: sum of squares,
                 ``X . phi`` (the compute dtype in, fp32 sums, scaled by
                 1 / rms AFTER the product), ``H_pre`` and h.  Writes h
                 and the fp32 maps' input m (with 1 / rms beside it).
``hc_post_fwd``  X' from X, y and the maps.
``hc_post_bwd``  from dX', X, y: dy and the per-token ``<dX'_j, y>``,
                 ``<dX'_j, X_i>``, what the maps' gradients are made of.
``hc_pre_bwd``   from dX', X, dh and the maps' gradient dm: ``<dh, X_i>``
                 and ITS share of dm in the tile, then dX whole — the
                 H_res path of dX', the H_pre path of dh, the phi path
                 ``(dm / rms) . phi^T`` and the norm's correction — and
                 phi's gradient summed over the token grid.

What is a few numbers a token — H_post, the clip / exp and Sinkhorn's
rounds on H_res — stays XLA's, under jax's own autodiff
(``models/hybrid_trunk.py``).  Those numbers reach the kernels with the
TOKENS ON THE SUBLANES, ``[T, 128]`` fp32 with the numbers in the first
lanes: a map is then a column, broadcast along a stream's lanes.  Every
sum over streams is fp32 and cast once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _common
from ._common import idx32

__all__ = ["takes", "LANES", "hc_pre_fwd", "hc_post_fwd", "hc_post_bwd",
           "hc_pre_bwd"]

F32, I32 = jnp.float32, jnp.int32
LANES = 128
# rows of a tile the kernels walk at a time: one packed bf16 tile
ROWS = 16
# tokens a grid step, where they divide the token count (else the next
# power of two down to ROWS).  Timed on a v5e at 16,384 x 4 x 3,584, each
# kernel alone at 64 / 128 / 256 tokens: 1.04 / 1.00 / 0.98, 1.68 / 1.67
# / 1.67, 1.74 / 1.72 / 1.72, 2.52 / 2.49 / 2.48 ms — the products with
# phi load a weight tile for every 128 rows at most; 256 would ask for
# 84 MiB of VMEM in ``hc_pre_bwd``
TOKENS = 128
# what the kernels ask for, and what of it is left to Mosaic's own
# scratch beside the blocks ``takes`` counts
_VMEM_LIMIT, _HEADROOM = 56 << 20, 8 << 20


def _tokens(t: int) -> int:
    tile = TOKENS
    while tile > ROWS and t % tile:
        tile //= 2
    return tile


def _pre_bwd_vmem(tile: int, n: int, c: int, itemsize: int) -> int:
    """What the largest kernel holds: dX', X and dX whole rows and dh,
    each twice (the pipeline), phi^T twice, the fp32 phi path and phi's
    gradient."""
    row = n * c
    return 2 * tile * (3 * row + c) * itemsize + 2 * LANES * row * itemsize \
        + tile * row * 4 + 2 * _k_rows(n) * row * 4


def _k_rows(n: int) -> int:
    """Rows of phi's transposed gradient: n^2 + 2 n up to whole packed
    tiles of the compute dtype (the operand that is turned).  32 for 24:
    the rows past phi's are cut off outside, which also keeps XLA from
    writing the kernel's result into the layers' stacked gradient in
    place — as one fusion with the custom call it asked for the
    kernel's VMEM beside its own and did not compile."""
    return -(-(n * n + 2 * n) // 16) * 16


def takes(x, n: int, c: int) -> bool:
    """Whether the kernels take the streams x ``[..., n c]``: a stream a
    whole number of lane tiles, the tokens a whole number of row blocks,
    the maps' numbers and 1 / rms within one lane tile, a token tile's
    rows within the VMEM the kernels ask for."""
    t = x.size // (n * c)
    return (x.shape[-1] == n * c and c % LANES == 0 and t % ROWS == 0
            and n * n + 2 * n < LANES
            and _pre_bwd_vmem(_tokens(t), n, c, x.dtype.itemsize)
            <= _VMEM_LIMIT - _HEADROOM)


def _precision(dtype):
    # fp32 streams (the tests'): no bf16 pass may round them
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _column(v, k: int, lanes: int = LANES):
    """Lane k of v [rows, 128], along ``lanes`` lanes."""
    return jnp.broadcast_to(v[:, k:k + 1], (v.shape[0], lanes))


def _lane(rows: int):
    return jax.lax.broadcasted_iota(I32, (rows, LANES), 1)


def _row_blocks(tile: int, body):
    def step(r, carry):
        body(pl.ds(pl.multiple_of(r * I32(ROWS), ROWS), ROWS))
        return carry
    jax.lax.fori_loop(I32(0), I32(tile // ROWS), step, I32(0))


def _chunk(c: int, most: int = 512) -> int:
    """Lanes of a stream a loop step takes: the widest of 512, 256, 128
    (at most ``most``) that divides it."""
    return next(w for w in (512, 256, 128) if w <= most and c % w == 0)


def _lane_chunks(width: int, chunk: int, body, carry=None):
    """``carry = body(lanes_at, carry)`` over ``width`` lanes, ``chunk``
    at a time, as a ROLLED loop: ``lanes_at(base)`` is the slice of
    ``chunk`` lanes at ``base`` + the step's offset.  (Unrolled over
    the 28 to 112 lane tiles of a row, the four kernels were ~1,500
    equations each, 22 instances a train step: 12 s of every set-up
    spent tracing and lowering them.)"""
    def step(j, carry):
        off = j * I32(chunk)
        return body(lambda base=0: pl.ds(
            pl.multiple_of(off + I32(base), LANES), chunk), carry)
    return jax.lax.fori_loop(I32(0), I32(width // chunk), step,
                             I32(0) if carry is None else carry)


def _fold(a):
    """[rows, k 128] -> [rows, 128]: the lane tiles added up."""
    out = a[:, :LANES]
    for at in range(LANES, a.shape[1], LANES):
        out += a[:, at:at + LANES]
    return out


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _tile(tile: int, width: int = LANES):
    """A token tile's rows of an array ``[T, width]``."""
    return pl.BlockSpec((tile, width), lambda t: idx32(t, 0))


def _whole(shape):
    return pl.BlockSpec(shape, lambda t: idx32(0, 0))


# ---------------------------------------------------------------------------
# hc_pre_fwd
# ---------------------------------------------------------------------------
def _pre_fwd_kernel(x_ref, phi_ref, coef_ref, h_ref, mr_ref, sq_ref, hp_ref,
                    *, n, c, eps):
    tile, row = x_ref.shape
    k = n * n + 2 * n

    wide = _chunk(c)

    def squares(rows):
        def add(at, acc):
            v = x_ref[rows, at()].astype(F32)
            return acc + v * v
        sq_ref[rows, :] = _fold(_lane_chunks(
            row, wide, add, jnp.zeros((ROWS, wide), F32)))
    _row_blocks(tile, squares)
    var = jnp.sum(sq_ref[...], axis=-1, keepdims=True) / row
    rstd = jax.lax.rsqrt(var + eps)
    m = jnp.dot(x_ref[...], phi_ref[...], preferred_element_type=F32,
                precision=_precision(x_ref.dtype)) * rstd
    mr_ref[...] = jnp.where(_lane(tile) == k, rstd, m)
    hp_ref[...] = jax.nn.sigmoid(coef_ref[0:1, :] * m + coef_ref[1:2, :])

    def mix(rows):
        hp = hp_ref[rows, :]
        w = [_column(hp, i, wide) for i in range(n)]

        def some(at, carry):
            acc = w[0] * x_ref[rows, at()].astype(F32)
            for i in range(1, n):
                acc += w[i] * x_ref[rows, at(i * c)].astype(F32)
            h_ref[rows, at()] = acc.astype(h_ref.dtype)
            return carry
        _lane_chunks(c, wide, some)
    _row_blocks(tile, mix)


def _coef(alpha0, bias, n):
    """Rows 0 and 1 of an [8, 128] fp32 table: alpha_1 and b[:n] in the
    first n lanes, zeros after."""
    first = jnp.zeros((8, LANES), F32)
    first = first.at[0, :n].set(alpha0.astype(F32))
    return first.at[1, :n].set(bias[:n].astype(F32))


def _phi_lanes(phi, dtype):
    """phi [n C, n^2 + 2 n] in the compute dtype, zero columns up to a
    lane tile."""
    return jnp.pad(phi.astype(dtype), ((0, 0), (0, LANES - phi.shape[1])))


def hc_pre_fwd(x, phi, alpha0, bias, n: int, eps: float):
    """x [T, n C], phi [n C, n^2 + 2 n], alpha0 a scalar, bias [n^2 + 2
    n] -> h [T, C] in x's dtype, and mr [T, 128] fp32: m = (x . phi) /
    rms(x) in lanes ``: n^2 + 2 n``, 1 / rms in the lane after, zeros
    beyond."""
    t, row = x.shape
    c, tile = row // n, _tokens(t)
    size = x.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_pre_fwd_kernel, n=n, c=c, eps=eps),
        out_shape=(jax.ShapeDtypeStruct((t, c), x.dtype),
                   jax.ShapeDtypeStruct((t, LANES), F32)),
        grid=(t // tile,),
        in_specs=[_tile(tile, row), _whole((row, LANES)),
                  _whole((8, LANES))],
        out_specs=(_tile(tile, c), _tile(tile)),
        scratch_shapes=[pltpu.VMEM((tile, LANES), F32),
                        pltpu.VMEM((tile, LANES), F32)],
        compiler_params=_params("parallel"),
        name="hc_pre_fwd",
        # an element of X: its square summed (2), its product with a
        # lane tile of phi (2 x 128), its share of h (2); a token: the
        # scale, the sigmoid's 4 around its exp.  X and h once, phi and
        # the table once, mr
        cost_estimate=pl.CostEstimate(
            flops=t * row * (4 + 2 * LANES) + t * LANES * 8,
            transcendentals=t * (LANES + 1),
            bytes_accessed=(t * row + t * c + row * LANES) * size
            + (t + 8) * LANES * 4),
        interpret=_common.interpret(),
    )(x, _phi_lanes(phi, x.dtype), _coef(alpha0, bias, n))


# ---------------------------------------------------------------------------
# hc_post_fwd, hc_post_bwd
# ---------------------------------------------------------------------------
def _post_fwd_kernel(x_ref, y_ref, maps_ref, o_ref, *, n, c):
    wide = _chunk(c)

    def mix(rows):
        maps = maps_ref[rows, :]
        res = [[_column(maps, j * n + i, wide) for i in range(n)]
               for j in range(n)]
        post = [_column(maps, n * n + j, wide) for j in range(n)]

        def some(at, carry):
            xs = [x_ref[rows, at(i * c)].astype(F32) for i in range(n)]
            y = y_ref[rows, at()].astype(F32)
            for j in range(n):
                acc = res[j][0] * xs[0]
                for i in range(1, n):
                    acc += res[j][i] * xs[i]
                acc += post[j] * y
                o_ref[rows, at(j * c)] = acc.astype(o_ref.dtype)
            return carry
        _lane_chunks(c, wide, some)
    _row_blocks(x_ref.shape[0], mix)


def hc_post_fwd(x, y, maps, n: int):
    """x [T, n C], y [T, C], maps [T, 128] fp32 (H_res row-major in the
    first n^2 lanes — row j: what stream j of the output takes —, H_post
    in the n after) -> x' [T, n C] in x's dtype."""
    t, row = x.shape
    c, tile = row // n, _tokens(t)
    wide = _tile(tile, row)
    return pl.pallas_call(
        functools.partial(_post_fwd_kernel, n=n, c=c),
        out_shape=jax.ShapeDtypeStruct((t, row), x.dtype),
        grid=(t // tile,),
        in_specs=[wide, _tile(tile, c), _tile(tile)],
        out_specs=wide,
        compiler_params=_params("parallel"),
        name="hc_post_fwd",
        # an element of X': n + 1 products summed.  X in, X' out, y, maps
        cost_estimate=pl.CostEstimate(
            flops=t * row * 2 * (n + 1), transcendentals=0,
            bytes_accessed=(2 * t * row + t * c) * x.dtype.itemsize
            + t * LANES * 4),
        interpret=_common.interpret(),
    )(x, y, maps)


def _post_bwd_kernel(g_ref, x_ref, y_ref, maps_ref, dy_ref, dmaps_ref,
                     *, n, c):
    lane = _lane(ROWS)
    # n^2 + n sums ride the loop, a lane tile each: two tiles a step,
    # folded into them
    wide = _chunk(c, 256)

    def pull(rows):
        maps = maps_ref[rows, :]
        post = [_column(maps, n * n + j, wide) for j in range(n)]
        zero = jnp.zeros((ROWS, LANES), F32)

        def some(at, sums):
            gs = [g_ref[rows, at(j * c)].astype(F32) for j in range(n)]
            xs = [x_ref[rows, at(i * c)].astype(F32) for i in range(n)]
            y = y_ref[rows, at()].astype(F32)
            dy = post[0] * gs[0]
            for j in range(1, n):
                dy += post[j] * gs[j]
            dy_ref[rows, at()] = dy.astype(dy_ref.dtype)
            return tuple(
                sums[j * (n + 1) + i] + _fold(
                    gs[j] * (xs[i] if i < n else y))
                for j in range(n) for i in range(n + 1))
        sums = _lane_chunks(c, wide, some, (zero,) * (n * n + n))
        out = zero
        total = lambda a: jnp.sum(a, axis=-1, keepdims=True)
        for j in range(n):
            for i in range(n + 1):
                at = j * n + i if i < n else n * n + j
                out = jnp.where(lane == at, total(sums[j * (n + 1) + i]),
                                out)
        dmaps_ref[rows, :] = out
    _row_blocks(g_ref.shape[0], pull)


def hc_post_bwd(g, x, y, maps, n: int):
    """g = dX' [T, n C] -> dy [T, C] in y's dtype and dmaps [T, 128]
    fp32, in ``maps``' lanes: ``<g_j, x_i>`` for H_res[j][i], ``<g_j,
    y>`` for H_post[j], zeros beyond."""
    t, row = x.shape
    c, tile = row // n, _tokens(t)
    wide, narrow = _tile(tile, row), _tile(tile, c)
    return pl.pallas_call(
        functools.partial(_post_bwd_kernel, n=n, c=c),
        out_shape=(jax.ShapeDtypeStruct((t, c), y.dtype),
                   jax.ShapeDtypeStruct((t, LANES), F32)),
        grid=(t // tile,),
        in_specs=[wide, wide, narrow, _tile(tile)],
        out_specs=(narrow, _tile(tile)),
        compiler_params=_params("parallel"),
        name="hc_post_bwd",
        # an element of dX': its share of dy (2) and its n + 1 products
        # with the X_i and y, summed (2 each).  dX', X, y in, dy out,
        # maps in, dmaps out
        cost_estimate=pl.CostEstimate(
            flops=t * row * 2 * (n + 2), transcendentals=0,
            bytes_accessed=(2 * t * row + 2 * t * c) * x.dtype.itemsize
            + 2 * t * LANES * 4),
        interpret=_common.interpret(),
    )(g, x, y, maps)


# ---------------------------------------------------------------------------
# hc_pre_bwd
# ---------------------------------------------------------------------------
def _pre_bwd_kernel(g_ref, x_ref, dh_ref, mr_ref, dmr_ref, maps_ref,
                    phit_ref, coef_ref, dx_ref, dz_ref, dphit_ref,
                    path_ref, cols_ref, *, n, c):
    tile, row = x_ref.shape
    k, k_rows = n * n + 2 * n, dphit_ref.shape[0]
    lane = _lane(ROWS)

    chunk = _chunk(c)

    def to_h_pre(rows):
        def some(at, acc):
            dh = dh_ref[rows, at()].astype(F32)
            return tuple(acc[i] + dh * x_ref[rows, at(i * c)].astype(F32)
                         for i in range(n))
        acc = _lane_chunks(c, chunk, some,
                           (jnp.zeros((ROWS, chunk), F32),) * n)
        out = jnp.zeros((ROWS, LANES), F32)
        for i in range(n):
            out = jnp.where(lane == i,
                            jnp.sum(acc[i], axis=-1, keepdims=True), out)
        cols_ref[rows, :] = out
    _row_blocks(tile, to_h_pre)

    wide = _lane(tile)
    mr = mr_ref[...]
    rstd = jnp.sum(jnp.where(wide == k, mr, 0.0), axis=-1, keepdims=True)
    m = jnp.where(wide < k, mr, 0.0)
    alpha0 = coef_ref[0:1, :]                   # zeros past the n lanes
    hp = jax.nn.sigmoid(alpha0 * m + coef_ref[1:2, :])
    dz = jnp.where(wide < n, cols_ref[...] * hp * (1.0 - hp), 0.0)
    dz_ref[...] = dz
    dm = jnp.where(wide < k, dmr_ref[...], 0.0) + alpha0 * dz
    # the norm: d(1 / rms) = <dm, x . phi>, and rms' own gradient is
    # -x / (N rms^3): dx takes -<dm, m> / (N rms^2) of x
    norm = -jnp.sum(dm * m, axis=-1, keepdims=True) * rstd * rstd / row
    cols_ref[...] = jnp.where(wide == n, norm, jnp.where(wide < n, hp, 0.0))
    dp = (dm * rstd).astype(x_ref.dtype)
    precision = _precision(x_ref.dtype)
    path_ref[...] = jnp.dot(dp, phit_ref[...], preferred_element_type=F32,
                            precision=precision)

    @pl.when(pl.program_id(0) == 0)
    def _start():
        dphit_ref[...] = jnp.zeros_like(dphit_ref)
    dphit_ref[...] += jax.lax.dot_general(
        dp[:, :k_rows], x_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=F32, precision=precision)

    def to_x(rows):
        maps, cols = maps_ref[rows, :], cols_ref[rows, :]
        res = [[_column(maps, j * n + i, chunk) for i in range(n)]
               for j in range(n)]
        hp_i = [_column(cols, i, chunk) for i in range(n)]
        norm_b = _column(cols, n, chunk)

        def some(at, carry):
            gs = [g_ref[rows, at(j * c)].astype(F32) for j in range(n)]
            dh = dh_ref[rows, at()].astype(F32)
            for i in range(n):
                acc = res[0][i] * gs[0]
                for j in range(1, n):
                    acc += res[j][i] * gs[j]
                acc += hp_i[i] * dh
                acc += path_ref[rows, at(i * c)]
                acc += norm_b * x_ref[rows, at(i * c)].astype(F32)
                dx_ref[rows, at(i * c)] = acc.astype(dx_ref.dtype)
            return carry
        _lane_chunks(c, chunk, some)
    _row_blocks(tile, to_x)


def hc_pre_bwd(g, x, dh, mr, dmr, maps, phi, alpha0, bias, n: int):
    """g = dX' [T, n C] (what the output's streams hand back, NOT yet
    taken through H_res), x, dh [T, C], mr as :func:`hc_pre_fwd` wrote
    it, dmr [T, 128] the gradient that the maps computed outside hand
    back for m, maps as :func:`hc_post_fwd` reads them ->

    dx [T, n C] whole, dz [T, 128] fp32 (lanes ``: n``: the gradient of
    H_pre's pre-activations ``alpha0 m + bias``, from which alpha0's and
    the bias's follow), and phi's gradient TRANSPOSED, fp32 ``[rows >=
    n^2 + 2 n, n C]`` summed over the tokens."""
    t, row = x.shape
    c, tile = row // n, _tokens(t)
    size, k_rows = x.dtype.itemsize, _k_rows(n)
    wide, small = _tile(tile, row), _tile(tile)
    return pl.pallas_call(
        functools.partial(_pre_bwd_kernel, n=n, c=c),
        out_shape=(jax.ShapeDtypeStruct((t, row), x.dtype),
                   jax.ShapeDtypeStruct((t, LANES), F32),
                   jax.ShapeDtypeStruct((k_rows, row), F32)),
        grid=(t // tile,),
        in_specs=[wide, wide, _tile(tile, c), small, small, small,
                  _whole((LANES, row)), _whole((8, LANES))],
        out_specs=(wide, small, _whole((k_rows, row))),
        scratch_shapes=[pltpu.VMEM((tile, row), F32),
                        pltpu.VMEM((tile, LANES), F32)],
        compiler_params=_params("arbitrary"),
        name="hc_pre_bwd",
        # an element of X: its product with dh (2), the n + 3 terms of
        # dX summed (2 each), a lane tile of phi^T (2 x 128) and
        # ``k_rows`` rows of phi's gradient (2 each); a token: H_pre and
        # its gradient, dm, the norm's term.  dX', X in and dX out, dh,
        # phi^T and the table once, four [T, 128] in and one out, phi's
        # gradient
        cost_estimate=pl.CostEstimate(
            flops=t * row * (2 + 2 * (n + 3) + 2 * LANES + 2 * k_rows)
            + t * LANES * 16,
            transcendentals=t * LANES,
            bytes_accessed=(3 * t * row + t * c + LANES * row) * size
            + (4 * t + 8) * LANES * 4 + k_rows * row * 4),
        interpret=_common.interpret(),
    )(g, x, dh, mr, dmr, maps, _phi_lanes(phi, x.dtype).T,
      _coef(alpha0, bias, n))
