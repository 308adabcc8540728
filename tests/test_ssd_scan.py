"""The chunked selective scan (``ops/ssd_scan.py``), its Pallas kernels
(``ops/pallas/ssd_scan.py``, in the interpreter here) and the causal
depthwise convolution (``ops/pallas/causal_conv.py``) against the
recurrence stated token by token and a loop over positions: values and
every gradient.  The kernels reading their operands where they lie:
``tests/test_ssd_scan_in_place.py``.  ``tests/test_tpu_compile.py`` lowers
the same kernels through Mosaic, ``tests/test_pallas_tpu.py`` runs them on
the chip.
"""

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
    # one program a form: run op by op, the interpreter's kernels
    # compile and dispatch an equation at a time
    (_, y), g = jax.jit(jax.value_and_grad(
        lambda *a: (lambda y: (jnp.sum(y * weight), y))(fn(*a)),
        argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
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
    grads = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * weight),
                                       argnums=(0, 1, 2)))(x, w, bias)
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
