"""Dense flash attention, forward and both gradients against the XLA
attention (interpret mode on the CPU): MHA and GQA, the block a length
gets, and the functional entry point."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from _pallas_flash import _interpret_mode, _ref_attn  # noqa: F401


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(1, 64, 2, 32), (2, 128, 4, 64)])
def test_flash_attention_parity(causal, shape):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    rng = np.random.RandomState(0)
    b, s, h, d = shape
    q = jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
    out = flash_attention(q, k, v, causal)
    np.testing.assert_allclose(out, _ref_attn(q, k, v, causal),
                               atol=2e-5, rtol=2e-5)
    g = jax.jit(jax.grad(
        lambda *a: (flash_attention(*a, causal) ** 2).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(lambda *a: (_ref_attn(*a, causal) ** 2).sum(),
                          argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(a, b_, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("h,nkv,d", [(4, 4, 128), (4, 2, 128), (4, 1, 128),
                                     (4, 2, 64), (2, 2, 32)])
def test_flash_attention_gqa_parity(causal, h, nkv, d):
    """K/V at their own head count against the einsum reference with
    K/V repeated by hand.  Two 512-row blocks: the off-diagonal loop,
    the diagonal block and the sum over a group's query heads all run;
    head dim 128 is addressed flat in [b, s, heads*d], 64 and 32 through
    the transposing entry."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    rng = np.random.RandomState(h * 100 + nkv * 10 + d)
    b, s = 2, 1024
    q = jnp.asarray(rng.normal(0, 1, (b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (b, s, nkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (b, s, nkv, d)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (b, s, h, d)), jnp.float32)

    def ref(q, k, v):
        rep = h // nkv
        return _ref_attn(q, jnp.repeat(k, rep, axis=2),
                         jnp.repeat(v, rep, axis=2), causal)

    out = flash_attention(q, k, v, causal)
    assert out.shape == q.shape
    np.testing.assert_allclose(out, ref(q, k, v), atol=2e-5, rtol=2e-5)
    g = jax.jit(jax.grad(
        lambda *a: (flash_attention(*a, causal) * w).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(lambda *a: (ref(*a) * w).sum(),
                          argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(g, gr):
        assert a.shape == b_.shape
        np.testing.assert_allclose(a, b_, atol=2e-4, rtol=2e-4)


def test_flash_attention_rejects_ragged_groups():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    q = jnp.zeros((1, 64, 4, 32), jnp.float32)
    kv = jnp.zeros((1, 64, 3, 32), jnp.float32)
    with pytest.raises(ValueError, match="multiple of kv heads"):
        flash_attention(q, kv, kv, True)


@pytest.mark.parametrize("s,pallas", [(192, True), (64, True), (384, True),
                                      (576, True), (129, False)])
def test_flash_attention_block_choice(s, pallas):
    """Every length a block of 8 or more divides takes the kernels —
    192 and 576 in blocks of 64, whose statistics are addressed a block
    at a time on an untiled axis — and any other the XLA attention: same
    values and gradients either way, GQA included."""
    from paddle_tpu.ops.pallas.flash_attention import (
        _pick_blocks, flash_attention)
    assert (_pick_blocks(s) is not None) == pallas
    rng = np.random.RandomState(s)
    q = jnp.asarray(rng.normal(0, 1, (1, s, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (1, s, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (1, s, 2, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (1, s, 4, 32)), jnp.float32)

    def ref(q, k, v):
        return _ref_attn(q, jnp.repeat(k, 2, axis=2),
                         jnp.repeat(v, 2, axis=2), True)

    def loss(fn):
        return lambda *a: (fn(*a) * w).sum()

    flash = functools.partial(flash_attention, causal=True)
    assert ("pallas_call" in str(jax.make_jaxpr(flash)(q, k, v))) == pallas
    np.testing.assert_allclose(flash(q, k, v), ref(q, k, v),
                               atol=2e-5, rtol=2e-5)
    grads = lambda fn: jax.jit(jax.grad(loss(fn), argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(grads(flash), grads(ref)):
        np.testing.assert_allclose(a, b_, atol=2e-4, rtol=2e-4)


def test_flash_attention_via_sdpa():
    """The functional sdpa routes to the Pallas kernel when enabled."""
    import paddle_tpu.nn.functional as F
    rng = np.random.RandomState(1)
    shape = (2, 64, 2, 32)
    qn = rng.normal(0, 1, shape).astype("float32")
    q = paddle.to_tensor(qn, stop_gradient=False)
    k = paddle.to_tensor(rng.normal(0, 1, shape).astype("float32"))
    v = paddle.to_tensor(rng.normal(0, 1, shape).astype("float32"))
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    ref = _ref_attn(jnp.asarray(qn), k._data, v._data, True)
    np.testing.assert_allclose(out.numpy(), np.asarray(ref), atol=2e-5)
    out.sum().backward()
    assert q.grad is not None
