"""Pallas kernels for the causal depthwise convolution of a state-space
mixer, with its SiLU: over the last ``K`` positions of every channel,
zeros before the row,

    out[t, c] = silu(bias[c] + sum_k w[c, k] x[t - (K-1) + k, c])

x is read once and out ``[b, s, C]`` written once, a ``[rows, 128]``
tile at a time; the ``K - 1`` positions before a tile come from a second
8-row view of the same array (zeroed before the row's start), so the
tiles are independent.  The taps are sublane rotations of the tile in
VMEM: XLA's own form pads the row in fp32 and reads it ``K`` times.
The backward forms the pre-activation again (also on the 8 positions
after the tile, whose gradient reaches back into it), and gives dx, and
dw and dbias summed over a row's tiles.

x's ``C`` channels are read WHERE THEY LIE: the array may be wider (a
projection that holds other pieces beside them) and the channels start
``offset`` into its last axis, a whole number of lane tiles that the
index maps of x's three views add.  A kernel is a custom call and takes
whole arrays, so a slice handed to it would be written out first.

The second pair of kernels is the GATED SHORT CONVOLUTION of a
convolution layer (LFM2's ``Lfm2ShortConv`` between its two
projections): no bias, no activation, ``K`` taps from the configuration,
and three operands that lie side by side in ONE array, the
in-projection's ``[b, s, 3 C]`` = ``B | Cg | X``,

    out[t, c] = Cg[t, c] * sum_k w[c, k] (B X)[t - (K-1) + k, c]

on the same tiles, halo views and rotations.  The forward reads the
three groups at lane offsets 0, C and 2 C and writes ``[b, s, C]``; the
backward forms the convolution again on a tile's own rows, reads g and
Cg on the 8 positions after them, and writes dB | dCg | dX into ONE
``[b, s, 3 C]`` array — the cotangent of the projection's output as its
backward products read it — and dw summed over a row's tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _common
from ._common import idx32

__all__ = ["causal_conv_silu", "takes", "causal_conv_silu_xla",
           "short_conv_gated", "takes_gated", "short_conv_gated_xla"]

F32 = jnp.float32
LANES, HALO = 128, 8
# the channel tile of each kernel, in lanes, where it divides the channels
# and their offset (else 128).  Timed on a v5e at 2 x 8192 x 4352: the
# forward takes 0.94 ms a run with 128 lanes and 0.80 with 256, the
# backward 1.35 and 1.48
FWD_TILE, BWD_TILE = 256, 128


def causal_conv_silu_xla(x, w, bias):
    """The same in plain ``jnp`` (fp32 inside, x's dtype out): the
    fallback, and the kernels' yardstick.  w ``[C, K]``, bias ``[C]``."""
    k, s = w.shape[1], x.shape[1]
    xp = jnp.pad(x.astype(F32), ((0, 0), (k - 1, 0), (0, 0)))
    out = bias.astype(F32)
    for i in range(k):
        out = out + xp[:, i:i + s, :] * w[:, i].astype(F32)
    return jax.nn.silu(out).astype(x.dtype)


def _rows(s: int):
    for rows in (512, 256, 128, 64, 32, 16):
        if s % rows == 0:
            return rows
    return None


def takes(x, w, offset: int = 0) -> bool:
    """Whether the kernels read w's channels out of x at ``offset``:
    whole lane tiles of them, starting on one and inside x; a row tile
    that divides the row; taps within the halo (and a row to spare for
    dbias)."""
    c = w.shape[0]
    return (c % LANES == 0 and offset % LANES == 0
            and offset + c <= x.shape[-1]
            and _rows(x.shape[1]) is not None and w.shape[1] < HALO)


def _lanes(c: int, offset: int, tile: int) -> int:
    return tile if c % tile == 0 and offset % tile == 0 else LANES


def _roll(a, shift: int):
    """Rows moved down by ``shift``, around the end.  The shift is pinned
    to int32: under jax_enable_x64 a Python int lowers as i64, which
    Mosaic's rotate refuses."""
    return pltpu.roll(a, jnp.int32(shift), 0)


def _taps(ext, w_ref, taps, start=None):
    """(start +) sum_k w_k x[t - (K-1) + k] on every position of ``ext``
    but its first 8 (the halo before, which only feeds the taps)."""
    acc = w_ref[taps - 1:taps, :] * ext[HALO:]
    if start is not None:
        acc = start + acc
    for d in range(1, taps):
        acc += w_ref[taps - 1 - d:taps - d, :] * \
            _roll(ext, d)[HALO:]
    return acc


def _pre(ext, w_ref, b_ref, taps):
    """The pre-activation: bias + the taps."""
    return _taps(ext, w_ref, taps, b_ref[...])


def _back_taps(g, w_ref, taps, rows):
    """sum_d w[K-1-d] g[t + d] on the first ``rows`` positions of g,
    which holds the 8 after them too: what the taps hand back to their
    input."""
    acc = w_ref[taps - 1:taps, :] * g[:rows]
    for d in range(1, taps):
        acc += w_ref[taps - 1 - d:taps - d, :] * \
            _roll(g, rows + HALO - d)[:rows]
    return acc


def _tap_sums(dw_ref, mine, ext, taps, rows):
    """dw's rows += the column sums of ``mine`` [rows] times each tap's
    view of ``ext`` (the 8 before, then the tile)."""
    col = lambda a: jnp.sum(a, axis=0, keepdims=True)
    dw_ref[taps - 1:taps, :] += col(mine * ext[HALO:HALO + rows])
    for d in range(1, taps):
        dw_ref[taps - 1 - d:taps - d, :] += col(
            mine * _roll(ext, d)[HALO:HALO + rows])


def _fwd_kernel(x_ref, before_ref, w_ref, b_ref, o_ref, *, taps: int):
    first = pl.program_id(2) == 0
    before = jnp.where(first, 0.0, before_ref[...].astype(F32))
    ext = jnp.concatenate([before, x_ref[...].astype(F32)], axis=0)
    o_ref[...] = jax.nn.silu(_pre(ext, w_ref, b_ref, taps)).astype(
        o_ref.dtype)


def _bwd_kernel(x_ref, before_ref, after_ref, g_ref, gafter_ref, w_ref,
                b_ref, dx_ref, dwb_ref, *, taps: int):
    blk = pl.program_id(2)
    first, last = blk == 0, blk == pl.num_programs(2) - 1
    rows = x_ref.shape[0]
    before = jnp.where(first, 0.0, before_ref[...].astype(F32))
    ext = jnp.concatenate([before, x_ref[...].astype(F32),
                           after_ref[...].astype(F32)], axis=0)
    pre = _pre(ext, w_ref, b_ref, taps)                 # [rows + 8, lanes]
    sig = jax.nn.sigmoid(pre)
    dout = jnp.concatenate(
        [g_ref[...].astype(F32),
         jnp.where(last, 0.0, gafter_ref[...].astype(F32))], axis=0)
    g = dout * sig * (1.0 + pre * (1.0 - sig))          # d silu
    # dx[t] = sum_d w[K-1-d] g[t + d]: the tile's own and the 8 after
    dx_ref[...] = _back_taps(g, w_ref, taps, rows).astype(dx_ref.dtype)

    @pl.when(first)
    def _start():
        dwb_ref[...] = jnp.zeros_like(dwb_ref)
    mine = g[:rows]
    _tap_sums(dwb_ref, mine, ext, taps, rows)
    dwb_ref[taps:taps + 1, :] += jnp.sum(mine, axis=0, keepdims=True)


def _specs(rows, s, lanes, at=0):
    """A tile of ``lanes`` channels, the 8 positions before and after
    it, on the (row, channel tile, row tile) grid; the channels start
    ``at`` channel tiles into the array's last axis."""
    per = rows // HALO
    blocks = s // HALO
    tile = pl.BlockSpec((None, rows, lanes),
                        lambda i, c, r, *_: idx32(i, r, at + c))
    before = pl.BlockSpec(
        (None, HALO, lanes),
        lambda i, c, r, *_: idx32(
            i, jnp.maximum(jnp.int32(r) * per - 1, 0), at + c))
    after = pl.BlockSpec(
        (None, HALO, lanes),
        lambda i, c, r, *_: idx32(
            i, jnp.minimum((jnp.int32(r) + 1) * per, blocks - 1), at + c))
    return tile, before, after


def _table_specs(lanes):
    return (pl.BlockSpec((HALO, lanes), lambda i, c, r, *_: idx32(0, c)),
            pl.BlockSpec((1, lanes), lambda i, c, r, *_: idx32(0, c)))


def _bytes(dtype, b, s, c, rows, lanes, backward: bool) -> int:
    """HBM bytes by the BlockSpecs.  Forward: x in and out once, the
    8-row halo before a tile at every grid step.  Backward: x and g in,
    dx out, three halos (before and after x's tile, after g's) and the
    ``[b, 8, C]`` fp32 sums.  Either: the fp32 tables ``[9, C]``, again
    for every batch row where there is more than one channel tile."""
    passes, halos = (3, 3) if backward else (2, 1)
    tables = (b if c // lanes > 1 else 1) * (HALO + 1) * c * 4
    return _common.nbytes((b, s, c), dtype) * passes \
        + _common.nbytes((b, s // rows * HALO, c), dtype) * halos \
        + tables + (b * HALO * c * 4 if backward else 0)


def _tap_table(w):
    """w ``[C, K]`` as ``[8, C]`` fp32 rows of taps (a tile's lanes are
    its channels)."""
    return jnp.pad(w.astype(F32).T, ((0, HALO - w.shape[1]), (0, 0)))


def _tables(w, bias):
    """The taps' table and bias ``[1, C]``, fp32."""
    return _tap_table(w), bias.astype(F32).reshape(1, w.shape[0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def causal_conv_silu(x, w, bias, offset=0):
    """x ``[b, s, >= offset + C]``, w ``[C, K]``, bias ``[C]`` -> ``[b,
    s, C]`` in x's dtype: channels ``offset ..`` of x convolved
    (:func:`takes` says which shapes)."""
    return _fwd(x, w, bias, offset)[0]


def _fwd(x, w, bias, offset):
    b, s, _ = x.shape
    c, rows = w.shape[0], _rows(s)
    lanes = _lanes(c, offset, FWD_TILE)
    x_tile, x_before, _ = _specs(rows, s, lanes, offset // lanes)
    tile, _, _ = _specs(rows, s, lanes)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, taps=w.shape[1]),
        out_shape=jax.ShapeDtypeStruct((b, s, c), x.dtype),
        grid=(b, c // lanes, s // rows),
        in_specs=[x_tile, x_before, *_table_specs(lanes)],
        out_specs=tile,
        name="causal_conv_fwd",
        # an output: bias + K taps (2 K), then silu's negate, add,
        # quotient and product around its one exp
        cost_estimate=pl.CostEstimate(
            flops=b * s * c * (2 * w.shape[1] + 4),
            transcendentals=b * s * c,
            bytes_accessed=_bytes(x.dtype, b, s, c, rows, lanes, False)),
        interpret=_common.interpret(),
    )(x, x, *_tables(w, bias))
    return out, (x, w, bias)


def _bwd(offset, res, g):
    x, w, bias = res
    b, s, width = x.shape
    c, k = w.shape
    rows, lanes = _rows(s), _lanes(c, offset, BWD_TILE)
    x_views = _specs(rows, s, lanes, offset // lanes)
    tile, _, after = _specs(rows, s, lanes)
    g = g.astype(x.dtype)
    dx, dwb = pl.pallas_call(
        functools.partial(_bwd_kernel, taps=k),
        out_shape=(jax.ShapeDtypeStruct((b, s, c), x.dtype),
                   jax.ShapeDtypeStruct((b, HALO, c), F32)),
        grid=(b, c // lanes, s // rows),
        in_specs=[*x_views, tile, after, *_table_specs(lanes)],
        out_specs=(tile, pl.BlockSpec((None, HALO, lanes),
                                      lambda i, ch, r: idx32(i, 0, ch))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="causal_conv_bwd",
        # the pre-activation on a tile's rows and the 8 after (2 K), the
        # sigmoid's 3 and d silu's 5 there; dx (2 K) and the column
        # sums of dw and dbias (2 K + 1) on the tile's own
        cost_estimate=pl.CostEstimate(
            flops=b * c * (s + s // rows * HALO) * (2 * k + 8)
            + b * s * c * (4 * k + 1),
            transcendentals=b * c * (s + s // rows * HALO),
            bytes_accessed=_bytes(x.dtype, b, s, c, rows, lanes, True)),
        interpret=_common.interpret(),
    )(x, x, x, g, g, *_tables(w, bias))
    dwb = jnp.sum(dwb, axis=0)                          # [8, C]
    # the cotangent of the channels x holds beside these is none
    dx = jnp.pad(dx, ((0, 0), (0, 0), (offset, width - offset - c)))
    return dx, dwb[:k].T.astype(w.dtype), dwb[k].astype(bias.dtype)


causal_conv_silu.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# the gated short convolution: Cg * conv_K(B * X), three operands in one
# array
# ---------------------------------------------------------------------------
# the channel tile of both kernels, in lanes: the largest halving of it
# that divides the channels.  Timed on a v5e at 2 x 8192 x 3 x 2048, rows
# 512: the forward takes 0.541 / 0.460 / 0.434 ms a run with 128 / 256 /
# 512 lanes, the backward 1.602 / 1.346 / 1.266 (1,024 lanes: past VMEM);
# XLA's own form 1.18 and, with the forward it forms again, 3.94
GATED_TILE = 512


def _gated_lanes(c: int) -> int:
    lanes = GATED_TILE
    while c % lanes:
        lanes //= 2
    return lanes


def short_conv_gated_xla(bcx, w):
    """The same in plain ``jnp`` (fp32 inside, bcx's dtype out): the
    fallback, and the kernels' yardstick.  bcx ``[b, s, 3 C]`` = B | Cg |
    X, w ``[C, K]``."""
    (c, k), s = w.shape, bcx.shape[1]
    b_, cg, x = (bcx[..., i * c:(i + 1) * c].astype(F32) for i in range(3))
    up = jnp.pad(b_ * x, ((0, 0), (k - 1, 0), (0, 0)))
    v = sum(up[:, i:i + s, :] * w[:, i].astype(F32) for i in range(k))
    return (cg * v).astype(bcx.dtype)


def takes_gated(bcx, w) -> bool:
    """Whether the kernels take the in-projection's output as it lies:
    three groups of w's channels, each whole lane tiles; a row tile that
    divides the row; taps within the halo."""
    c = w.shape[0]
    return (c % LANES == 0 and bcx.shape[-1] == 3 * c
            and _rows(bcx.shape[1]) is not None and w.shape[1] <= HALO)


def _gated_fwd_kernel(b_ref, bbefore_ref, x_ref, xbefore_ref, cg_ref, w_ref,
                      o_ref, *, taps: int):
    first = pl.program_id(2) == 0
    before = jnp.where(first, 0.0, bbefore_ref[...].astype(F32)
                       * xbefore_ref[...].astype(F32))
    u = jnp.concatenate(
        [before, b_ref[...].astype(F32) * x_ref[...].astype(F32)], axis=0)
    o_ref[...] = (cg_ref[...].astype(F32) * _taps(u, w_ref, taps)).astype(
        o_ref.dtype)


def _gated_bwd_kernel(b_ref, bbefore_ref, x_ref, xbefore_ref, cg_ref,
                      cgafter_ref, g_ref, gafter_ref, w_ref, d_ref, dw_ref,
                      keep_ref, *, taps: int):
    """One tile's dB, dCg and dX, formed at the innermost grid axis' first
    step and written at its three: the axis walks the three groups of
    the ONE result, the inputs' blocks stay where they are."""
    blk, group = pl.program_id(2), pl.program_id(3)
    rows = b_ref.shape[0]

    @pl.when(group == 0)
    def _form():
        first, last = blk == 0, blk == pl.num_programs(2) - 1
        bt, xt = b_ref[...].astype(F32), x_ref[...].astype(F32)
        before = jnp.where(first, 0.0, bbefore_ref[...].astype(F32)
                           * xbefore_ref[...].astype(F32))
        u = jnp.concatenate([before, bt * xt], axis=0)     # [8 + rows]
        g = g_ref[...].astype(F32)
        # dv = g Cg on the tile's rows and the 8 after, whose taps reach
        # back into the tile
        dv = jnp.concatenate(
            [g * cg_ref[...].astype(F32),
             jnp.where(last, 0.0, gafter_ref[...].astype(F32)
                       * cgafter_ref[...].astype(F32))], axis=0)
        du = _back_taps(dv, w_ref, taps, rows)
        d_ref[...] = (du * xt).astype(d_ref.dtype)                  # dB
        keep_ref[0] = (g * _taps(u, w_ref, taps)).astype(d_ref.dtype)  # dCg
        keep_ref[1] = (du * bt).astype(d_ref.dtype)                 # dX

        @pl.when(first)
        def _start():
            dw_ref[...] = jnp.zeros_like(dw_ref)
        _tap_sums(dw_ref, dv[:rows], u, taps, rows)

    @pl.when(group > 0)
    def _hand_on():
        d_ref[...] = keep_ref[group - 1]


def _gated_bytes(dtype, b, s, c, rows, lanes, backward: bool) -> int:
    """HBM bytes by the BlockSpecs.  Forward: B, Cg, X in and out once,
    the halo before a tile of B and of X.  Backward: B, Cg, X, g in, dB |
    dCg | dX out, four halos (before B and X, after Cg and g) and the
    ``[b, 8, C]`` fp32 sums.  Either: the fp32 taps ``[8, C]``, again for
    every batch row where there is more than one channel tile."""
    passes, halos = (7, 4) if backward else (4, 2)
    return _common.nbytes((b, s, c), dtype) * passes \
        + _common.nbytes((b, s // rows * HALO, c), dtype) * halos \
        + (b if c // lanes > 1 else 1) * HALO * c * 4 \
        + (b * HALO * c * 4 if backward else 0)


@jax.custom_vjp
def short_conv_gated(bcx, w):
    """bcx ``[b, s, 3 C]`` = B | Cg | X, w ``[C, K]`` -> ``Cg * conv_K(B *
    X)`` ``[b, s, C]`` in bcx's dtype (:func:`takes_gated` says which
    shapes)."""
    return _gated_fwd(bcx, w)[0]


def _gated_fwd(bcx, w):
    b, s, _ = bcx.shape
    (c, k), rows = w.shape, _rows(s)
    lanes = _gated_lanes(c)
    group = c // lanes
    b_tile, b_before, _ = _specs(rows, s, lanes)
    cg_tile, _, _ = _specs(rows, s, lanes, group)
    x_tile, x_before, _ = _specs(rows, s, lanes, 2 * group)
    out = pl.pallas_call(
        functools.partial(_gated_fwd_kernel, taps=k),
        out_shape=jax.ShapeDtypeStruct((b, s, c), bcx.dtype),
        grid=(b, group, s // rows),
        in_specs=[b_tile, b_before, x_tile, x_before, cg_tile,
                  _table_specs(lanes)[0]],
        out_specs=b_tile,
        name="short_conv_fwd",
        # an output: B X (1), K taps (2 K - 1), the gate (1)
        cost_estimate=pl.CostEstimate(
            flops=b * s * c * (2 * k + 1), transcendentals=0,
            bytes_accessed=_gated_bytes(bcx.dtype, b, s, c, rows, lanes,
                                        False)),
        interpret=_common.interpret(),
    )(bcx, bcx, bcx, bcx, bcx, _tap_table(w))
    return out, (bcx, w)


def _gated_bwd(res, g):
    bcx, w = res
    b, s, _ = bcx.shape
    (c, k), rows = w.shape, _rows(s)
    lanes = _gated_lanes(c)
    group = c // lanes
    b_tile, b_before, _ = _specs(rows, s, lanes)
    cg_tile, _, cg_after = _specs(rows, s, lanes, group)
    x_tile, x_before, _ = _specs(rows, s, lanes, 2 * group)
    g_tile, _, g_after = _specs(rows, s, lanes)
    g = g.astype(bcx.dtype)
    d, dw = pl.pallas_call(
        functools.partial(_gated_bwd_kernel, taps=k),
        out_shape=(jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct((b, HALO, c), F32)),
        grid=(b, group, s // rows, 3),
        in_specs=[b_tile, b_before, x_tile, x_before, cg_tile, cg_after,
                  g_tile, g_after, _table_specs(lanes)[0]],
        out_specs=(
            pl.BlockSpec((None, rows, lanes),
                         lambda i, ch, r, j: idx32(i, r, j * group + ch)),
            pl.BlockSpec((None, HALO, lanes),
                         lambda i, ch, r, j: idx32(i, 0, ch))),
        scratch_shapes=[pltpu.VMEM((2, rows, lanes), bcx.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        name="short_conv_bwd",
        # a tile's own rows: B X and the taps again (2 K), g Cg (1), what
        # the taps hand back (2 K - 1), dB, dCg, dX (3), dw's column
        # sums (2 K); g Cg on the 8 rows after a tile
        cost_estimate=pl.CostEstimate(
            flops=b * s * c * (6 * k + 3) + b * c * (s // rows) * HALO,
            transcendentals=0,
            bytes_accessed=_gated_bytes(bcx.dtype, b, s, c, rows, lanes,
                                        True)),
        interpret=_common.interpret(),
    )(bcx, bcx, bcx, bcx, bcx, bcx, g, g, _tap_table(w))
    return d, jnp.sum(dw, axis=0)[:k].T.astype(w.dtype)


short_conv_gated.defvjp(_gated_fwd, _gated_bwd)
