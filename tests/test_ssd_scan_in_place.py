"""The scan's and the convolution's kernels reading their operands WHERE
THEY LIE (in the interpreter here) — xBC inside the in-projection's output
at a lane-tile offset, x | B | C inside the convolution's, dx | dB | dC
written as one array — bit for bit the kernels on slices.  The mixer that
puts them together: ``tests/test_ssd_scan_mixer.py``; against the
recurrence: ``tests/test_ssd_scan.py``.
"""

import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401  (enables x64 before arrays exist)
from paddle_tpu.ops import ssd_scan as op
from paddle_tpu.ops.pallas import causal_conv, ssd_scan as kernel
from test_ssd_scan import F32, _inputs


# ---------------------------------------------------------------------------
# operands read where they lie: bitwise the sliced form
# ---------------------------------------------------------------------------
def _same(got, want, names):
    """Bit for bit (a zero's sign apart), name by name."""
    for name, a, b in zip(names, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.all(a == b)), (
            name, float(jnp.max(jnp.abs(a.astype(F32) - b.astype(F32)))))


# offset, the holding array's width, channels: a 256-lane tile inside a
# projection's odd width, a 128-lane one, offset 0 in a wider array, and
# the whole array (what every other caller passes)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, F32])
@pytest.mark.parametrize("offset,width,c", [
    (256, 1000, 512), (128, 640, 384), (0, 300, 256), (0, 256, 256)])
def test_causal_conv_reads_its_channels_where_they_lie(offset, width, c,
                                                       dtype):
    """``causal_conv_silu(holding array, ..., offset)`` against the same
    kernels on the slice: out, and the gradients of the holding array
    (nothing outside the channels), w and bias — with NaN in every
    channel outside, which the kernels must never read."""
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    s = 1024
    inside = (jnp.arange(width) >= offset) & (jnp.arange(width) < offset + c)
    held = jnp.where(inside, jax.random.normal(ks[0], (2, s, width), F32),
                     jnp.nan).astype(dtype)
    w = jax.random.uniform(ks[1], (c, 4), F32, -0.5, 0.5)
    bias = jax.random.uniform(ks[2], (c,), F32, -0.5, 0.5)
    r = jax.random.normal(ks[3], (2, s, c), F32).astype(dtype)
    assert causal_conv.takes(held, w, offset)

    def run(f):
        out, vjp = jax.vjp(f, held, w, bias)
        return (out,) + vjp(r)
    got = run(lambda x, w, b: causal_conv.causal_conv_silu(x, w, b, offset))
    want = run(lambda x, w, b: causal_conv.causal_conv_silu(
        x[..., offset:offset + c], w, b))
    assert bool(jnp.all(jnp.isfinite(got[0].astype(F32))))
    assert bool(jnp.all(jnp.where(inside, True, got[1] == 0)))
    _same(got, want, ("out", "dx", "dw", "dbias"))


def _xbc_inputs(b, s, h, p, n, dtype, seed=21):
    x, dt, A, B, C = _inputs(b, s, h, p, n, seed)
    xbc = jnp.concatenate([x.reshape(b, s, h * p), B, C], -1).astype(dtype)
    return xbc, dt, A


def _scan_and_skip(scan, xbc, dt, A, D, r):
    """y + D x and the gradients of xbc, dt, A and D — x read twice, by
    the scan and by the skip, as the mixer reads it."""
    h = dt.shape[-1]

    def f(xbc, dt, A, D):
        y, x = scan(xbc, dt, A)
        x = x.reshape(*x.shape[:2], h, -1)
        return (y.reshape(x.shape).astype(F32)
                + D[:, None] * x.astype(F32)).astype(xbc.dtype)
    out, vjp = jax.vjp(f, xbc, dt, A, D)
    return (out,) + vjp(r.reshape(out.shape).astype(out.dtype))


def _sliced_scan(n, q):
    """x, B and C cut out of xbc and handed over apart, the cotangents
    padded and added by autodiff: the form the in-place one replaces."""
    def scan(xbc, dt, A):
        b, s, width = xbc.shape
        h, d = dt.shape[-1], width - 2 * n
        x = xbc[..., :d]
        y = op.ssd_scan(x.reshape(b, s, h, d // h), dt, A,
                        xbc[..., d:d + n], xbc[..., d + n:], chunk=q)
        return y.reshape(b, s, d), x
    return scan


# heads, head width, chunk, dtype: two heads and one to a 128-lane tile
@pytest.mark.parametrize("h,p,q,dtype", [
    (4, 64, 128, jnp.bfloat16), (4, 64, 256, F32), (2, 128, 128, jnp.bfloat16)])
def test_scan_reads_x_b_c_where_the_convolution_left_them(h, p, q, dtype):
    """``ssd_scan_xbc`` on ONE array ``[b, s, H*P + 2N]`` against the
    kernels on its three slices: y, and the gradients of the array — dx,
    dB and dC written side by side by ``ssd_scan_bwd``, the skip's share
    of dx added as it stores —, of dt, A and D."""
    b, s, n = 2, 512, 128
    xbc, dt, A = _xbc_inputs(b, s, h, p, n, dtype)
    D = jnp.linspace(0.5, 1.5, h, dtype=F32)
    r = jax.random.normal(jax.random.PRNGKey(5), (b, s, h * p), F32)
    assert kernel.takes_xbc(xbc.reshape(b, s // q, q, -1), h, n)
    got = _scan_and_skip(lambda *a: op.ssd_scan_xbc(*a, n, q),
                         xbc, dt, A, D, r)
    want = _scan_and_skip(_sliced_scan(n, q), xbc, dt, A, D, r)
    _same(got, want, ("y", "dxbc", "ddt", "dA", "dD"))


def test_scan_one_array_backward_with_nothing_owed_to_x():
    """A caller that reads only y: the cotangent of x's second reader is
    zeros, and dx | dB | dC is the kernels' own."""
    b, s, h, p, n, q = 1, 256, 2, 64, 128, 128
    xbc, dt, A = _xbc_inputs(b, s, h, p, n, F32, seed=8)
    r = jax.random.normal(jax.random.PRNGKey(6), (b, s, h * p), F32)
    grads = lambda scan: jax.grad(
        lambda *a: jnp.sum(scan(*a)[0] * r), argnums=(0, 1, 2))(xbc, dt, A)
    _same(grads(lambda *a: op.ssd_scan_xbc(*a, n, q)),
          grads(_sliced_scan(n, q)), ("dxbc", "ddt", "dA"))


# what takes_xbc refuses goes the sliced way: a state that is no lane
# tile, a row that is no whole number of chunks, heads that fill no tile
@pytest.mark.parametrize("s,h,p,n,q", [
    (256, 2, 64, 64, 128), (200, 2, 64, 128, 128), (256, 3, 16, 128, 128)])
def test_scan_takes_the_sliced_way_where_the_shapes_do_not_fit(s, h, p, n,
                                                              q):
    b = 1
    xbc, dt, A = _xbc_inputs(b, s, h, p, n, F32, seed=9)
    D = jnp.ones((h,), F32)
    r = jax.random.normal(jax.random.PRNGKey(7), (b, s, h * p), F32)
    assert s % q or not kernel.takes_xbc(
        xbc.reshape(b, s // q, q, -1), h, n)
    got = _scan_and_skip(lambda *a: op.ssd_scan_xbc(*a, n, q),
                         xbc, dt, A, D, r)
    want = _scan_and_skip(_sliced_scan(n, q), xbc, dt, A, D, r)
    _same(got, want, ("y", "dxbc", "ddt", "dA", "dD"))
