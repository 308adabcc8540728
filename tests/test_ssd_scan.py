"""The chunked selective scan (``ops/ssd_scan.py``), its Pallas kernels
(``ops/pallas/ssd_scan.py``, in the interpreter here) and the causal
depthwise convolution (``ops/pallas/causal_conv.py``) against the
recurrence stated token by token and a loop over positions: values and
every gradient.  ``tests/test_tpu_compile.py`` lowers the same kernels
through Mosaic, ``tests/test_pallas_tpu.py`` runs them on the chip.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401  (enables x64 before arrays exist)
from paddle_tpu.ops import ssd_scan as op
from paddle_tpu.ops.pallas import causal_conv, ssd_scan as kernel

F32 = jnp.float32


def _inputs(b, s, h, p, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), F32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), F32) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (h,), F32, 0.0, 2.7))
    B = jax.random.normal(ks[3], (b, s, n), F32) * 0.3
    C = jax.random.normal(ks[4], (b, s, n), F32) * 0.3
    return x, dt, A, B, C


def _value_and_grads(fn, args):
    weight = jnp.cos(jnp.arange(args[0].size, dtype=F32) * 0.01).reshape(
        args[0].shape)
    (_, y), g = jax.value_and_grad(
        lambda *a: (lambda y: (jnp.sum(y * weight), y))(fn(*a)),
        argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    return (y,) + g


def _close(got, want, tol):
    for name, a, b in zip(("y", "dx", "ddt", "dA", "dB", "dC"), got, want):
        err = float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))
        assert err < tol, (name, err)


# heads, head width, state, chunk: the kernels take the first four (two,
# one and four heads to a 128-lane tile); the last two are the jnp form
@pytest.mark.parametrize("h,p,n,q,by_kernel", [
    (4, 64, 32, 128, True), (4, 64, 32, 256, True),
    (2, 128, 16, 128, True), (8, 32, 16, 256, True),
    (3, 16, 8, 32, False), (3, 16, 8, 64, False)])
def test_chunked_scan_matches_the_recurrence(h, p, n, q, by_kernel):
    b, s = 2, 512
    args = _inputs(b, s, h, p, n)
    assert kernel.takes(args[0].reshape(b, s // q, q, h, p),
                        args[3].reshape(b, s // q, q, n)) == by_kernel
    want = _value_and_grads(op.ssd_recurrence, args)
    got = _value_and_grads(lambda *a: op.ssd_scan(*a, chunk=q), args)
    # dA sums every position's fp32 rounding of a whole row
    _close(got, want, 5e-4)


def test_kernels_match_the_jnp_form_on_one_chunking():
    """Same chunks, kernel against ``ssd_chunked_xla``: tighter than
    through the recurrence, since both round a chunk alike."""
    b, s, h, p, n, q = 1, 256, 2, 64, 16, 128
    x, dt, A, B, C = _inputs(b, s, h, p, n, seed=3)
    cut = lambda a: a.reshape(b, s // q, q, *a.shape[2:])

    def run(form):
        return _value_and_grads(
            lambda x, dt, A, B, C: form(
                x, dt, jnp.cumsum(dt * A, axis=2), B, C),
            (cut(x), cut(dt), A, cut(B), cut(C)))
    _close(run(kernel.ssd_chunked), run(op.ssd_chunked_xla), 2e-5)


def test_a_row_that_is_no_whole_number_of_chunks_is_padded_at_its_end():
    args = _inputs(1, 200, 3, 16, 8, seed=5)
    want = _value_and_grads(op.ssd_recurrence, args)
    got = _value_and_grads(lambda *a: op.ssd_scan(*a, chunk=64), args)
    _close(got, want, 5e-4)


def test_state_decays_and_carries_across_chunks():
    """One impulse at position 0 of a head with constant decay: y_t is
    the impulse times the decay to the power t, through every chunk."""
    s, q = 512, 128
    x = jnp.zeros((1, s, 2, 64), F32).at[0, 0, :, :].set(1.0)
    dt = jnp.ones((1, s, 2), F32)
    A = jnp.asarray([-0.01, -0.05], F32)
    B = jnp.ones((1, s, 8), F32)
    y = op.ssd_scan(x, dt, A, B, B, chunk=q)
    want = 8.0 * jnp.exp(A[None, :] * jnp.arange(s, dtype=F32)[:, None])
    assert float(jnp.max(jnp.abs(y[0, :, :, 0] - want))) < 1e-4


def _conv_loop(x, w, bias):
    x, w, bias = (np.asarray(a, np.float64) for a in (x, w, bias))
    b, s, c = x.shape
    k = w.shape[1]
    out = np.zeros_like(x)
    for t in range(s):
        acc = np.broadcast_to(bias, (b, c)).copy()
        for i in range(k):
            src = t - (k - 1) + i
            if src >= 0:
                acc += w[:, i] * x[:, src]
        out[:, t] = acc / (1.0 + np.exp(-acc))
    return out


# rows, channels, taps: the kernel's tiles of 512, 16 and 64 positions
# (one, three and one a row), and a row it does not take
@pytest.mark.parametrize("s,c,k,by_kernel", [
    (1024, 256, 4, True), (48, 128, 3, True), (64, 128, 4, True),
    (50, 96, 4, False)])
def test_causal_conv_matches_a_loop_over_positions(s, c, k, by_kernel):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(ks[0], (2, s, c), F32)
    w = jax.random.uniform(ks[1], (c, k), F32, -0.5, 0.5)
    bias = jax.random.uniform(ks[2], (c,), F32, -0.5, 0.5)
    weight = jax.random.normal(ks[3], x.shape, F32)
    assert causal_conv.takes(x, w) == by_kernel
    form = causal_conv.causal_conv_silu if by_kernel \
        else causal_conv.causal_conv_silu_xla
    assert float(jnp.max(jnp.abs(form(x, w, bias)
                                 - _conv_loop(x, w, bias)))) < 2e-6
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * weight),
                               argnums=(0, 1, 2))(x, w, bias)
    for got, want in zip(grads(form),
                         grads(causal_conv.causal_conv_silu_xla)):
        assert float(jnp.max(jnp.abs(got - want))
                     / jnp.max(jnp.abs(want))) < 2e-6


def test_causal_conv_sees_nothing_ahead():
    x = jnp.zeros((1, 64, 128), F32).at[0, 40, :].set(1.0)
    w = jnp.ones((128, 4), F32)
    out = causal_conv.causal_conv_silu(x, w, jnp.zeros((128,), F32))
    assert float(jnp.max(jnp.abs(out[0, :40]))) == 0.0
    assert float(jnp.min(out[0, 40:44])) > 0.5
    assert float(jnp.max(jnp.abs(out[0, 44:]))) == 0.0


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


def _mixer(heads, state, seed=13):
    """One Mamba-2 mixer at toy widths, heads of 64, two chunks a row."""
    from paddle_tpu.models import hybrid_trunk
    from paddle_tpu.models.llama_pretrain import LlamaPretrainConfig
    cfg = LlamaPretrainConfig(
        vocab_size=64, hidden_size=64, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        layer_types=("mamba",), mamba_n_heads=heads, mamba_d_head=64,
        mamba_d_state=state, mamba_chunk_size=128, dtype=F32,
        param_dtype=F32)
    names = [nm for nm in hybrid_trunk.kind_shapes(cfg, "mamba")
             if nm not in ("ln1", "ln2", "w_gate", "w_up", "w_down")]
    keys = jax.random.split(jax.random.PRNGKey(seed), len(names) + 1)
    bp = {nm: hybrid_trunk.init_leaf(cfg, k, "mamba", nm, 1)[0]
          for nm, k in zip(names, keys)}
    v = jax.random.normal(keys[-1], (2, 256, cfg.hidden_size), F32)
    return cfg, bp, v, functools.partial(hybrid_trunk._mamba_mixer, cfg=cfg)


# heads of 64, state: xBC starts on a lane tile and the state is one
# (both in place); starts half a tile in (the convolution on a slice);
# a state of 64 (the scan on slices, the convolution in place)
@pytest.mark.parametrize("heads,state,conv_in_place,scan_in_place", [
    (2, 128, True, True), (1, 32, False, False), (2, 64, True, False)])
def test_mixer_reads_in_place_where_it_can_and_the_same_values_where_not(
        monkeypatch, heads, state, conv_in_place, scan_in_place):
    """The mixer as the train step calls it, against itself with both
    ``takes`` of the in-place forms answering no: every piece sliced out
    for the kernels, as before they took offsets.  Output and every
    gradient bit for bit."""
    from paddle_tpu.models import hybrid_trunk
    cfg, bp, v, mixer = _mixer(heads, state)
    d_inner, conv, _ = hybrid_trunk.mamba_dims(cfg)
    zxbcdt = v @ bp["w_in"]
    assert causal_conv.takes(zxbcdt, bp["conv_w"], d_inner) == conv_in_place
    assert causal_conv.takes(zxbcdt[..., d_inner:d_inner + conv],
                             bp["conv_w"])
    assert kernel.takes_xbc(jnp.zeros((2, 2, 128, conv)), heads,
                            state) == scan_in_place

    def run():
        out, vjp = jax.vjp(mixer, bp, v)
        dbp, dv = vjp(jnp.cos(out * 3.0))
        return [out, dv] + [dbp[nm] for nm in sorted(dbp)]
    got = run()
    takes = causal_conv.takes
    monkeypatch.setattr(causal_conv, "takes",
                        lambda x, w, offset=0: not offset and takes(x, w))
    monkeypatch.setattr(kernel, "takes_xbc", lambda *a: False)
    _same(got, run(), ["out", "dv"] + sorted(bp))
