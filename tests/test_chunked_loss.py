"""Chunked softmax cross-entropy (ops/chunked_loss.py) parity tests.

The chunked head must match the plain fp32 log_softmax head bit-closely in
both value and gradients, including through the flagship forward_loss
(models/llama_pretrain.py loss_chunks config).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.chunked_loss import chunked_softmax_cross_entropy


def _ref_loss(x, w, t):
    logits = (x @ w).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, t[..., None], -1))


@pytest.mark.parametrize("num_chunks", [1, 2, 4])
def test_value_and_grads_match_reference(num_chunks):
    rs = np.random.RandomState(0)
    B, S, H, V = 2, 8, 16, 64
    x = jnp.asarray(rs.randn(B, S, H), jnp.float32)
    w = jnp.asarray(rs.randn(H, V) * 0.2, jnp.float32)
    t = jnp.asarray(rs.randint(0, V, (B, S)))

    loss = chunked_softmax_cross_entropy(x, w, t, num_chunks, jnp.float32)
    np.testing.assert_allclose(loss, _ref_loss(x, w, t), rtol=1e-6, atol=1e-6)

    g1 = jax.grad(lambda x, w: chunked_softmax_cross_entropy(
        x, w, t, num_chunks, jnp.float32), argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda x, w: _ref_loss(x, w, t), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(g1[0], g2[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(g1[1], g2[1], rtol=1e-5, atol=1e-6)


def test_indivisible_chunks_raises():
    x = jnp.zeros((2, 7, 4))
    w = jnp.zeros((4, 8))
    t = jnp.zeros((2, 7), jnp.int32)
    with pytest.raises(ValueError):
        chunked_softmax_cross_entropy(x, w, t, 4, jnp.float32)


@pytest.mark.slow
def test_flagship_loss_chunks_parity():
    from paddle_tpu.models.llama_pretrain import (
        LlamaPretrainConfig, build_mesh, init_params, make_forward)
    cfgs = [LlamaPretrainConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, max_seq_len=32,
        use_pallas_attention=False, sequence_parallel=False, remat=False,
        dtype=jnp.float32, loss_chunks=c) for c in (0, 3)]
    mesh = build_mesh(devices=jax.devices()[:1])
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 128, (3, 32)))
    with mesh:
        params = init_params(cfgs[0], jax.random.PRNGKey(0), mesh, pp=1)
        losses = []
        grads = []
        for cfg in cfgs:
            fwd = make_forward(cfg, mesh)
            l, g = jax.value_and_grad(fwd)(params, tokens)
            losses.append(float(l))
            grads.append(g)
    assert abs(losses[0] - losses[1]) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(grads[0]),
                    jax.tree_util.tree_leaves(grads[1])):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5)


def _toy(seed=0, B=2, S=8, H=16, V=64):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(B, S, H), jnp.float32),
            jnp.asarray(rs.randn(H, V) * 0.2, jnp.float32),
            jnp.asarray(rs.randint(0, V, (B, S))))


def _count(jaxpr, name):
    """Equations of primitive ``name`` in a jaxpr and every jaxpr its
    equations carry (scan bodies, pjit and custom_vjp calls)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count(sub, name)
    return n


@pytest.mark.parametrize("differentiated,dots", [(True, 3), (False, 1)])
def test_one_scan_and_no_matmul_more_than_the_mathematics(differentiated,
                                                          dots):
    # the gradients are formed in the forward scan: under grad there is no
    # second scan and no second x @ W; an evaluation pays for neither
    x, w, t = _toy()

    def head(x, w):
        return chunked_softmax_cross_entropy(x, w, t, 4, jnp.bfloat16)

    fn = jax.grad(head, argnums=(0, 1)) if differentiated else head
    jaxpr = jax.make_jaxpr(fn)(x, w).jaxpr
    assert _count(jaxpr, "scan") == 1
    assert _count(jaxpr, "dot_general") == dots


def test_cotangent_other_than_one():
    # the backward rule only scales what the forward formed
    x, w, t = _toy(1)
    g1 = jax.grad(lambda x, w: 3.0 * chunked_softmax_cross_entropy(
        x, w, t, 4, jnp.float32), argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda x, w: 3.0 * _ref_loss(x, w, t),
                  argnums=(0, 1))(x, w)
    np.testing.assert_allclose(g1[0], g2[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(g1[1], g2[1], rtol=1e-5, atol=1e-6)


def _bf16_ref(x, w, t):
    """The plain head at the chunked head's precisions: bf16 operands with
    fp32 accumulation, fp32 softmax, d_logits rounded to bf16."""
    bf, f32 = jnp.bfloat16, jnp.float32
    T, V = t.size, w.shape[1]
    xb, wb = x.reshape(T, -1).astype(bf), w.astype(bf)
    logits = jnp.dot(xb, wb, preferred_element_type=f32)
    onehot = jax.nn.one_hot(t.reshape(T), V, dtype=f32)
    loss = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits, -1) * onehot, -1))
    d = ((jax.nn.softmax(logits, -1) - onehot) / T).astype(bf)
    dx = jnp.dot(d, wb.T, preferred_element_type=f32).reshape(x.shape)
    dw = jnp.dot(xb.T, d, preferred_element_type=f32)
    return loss, dx, dw


@pytest.mark.parametrize("num_chunks", [2, 8])
def test_bf16_compute_on_fp32_inputs(num_chunks):
    x, w, t = _toy(2)
    loss, (dx, dw) = jax.value_and_grad(
        lambda x, w: chunked_softmax_cross_entropy(
            x, w, t, num_chunks, jnp.bfloat16), argnums=(0, 1))(x, w)
    assert dx.dtype == x.dtype and dw.dtype == w.dtype
    ref_loss, ref_dx, ref_dw = _bf16_ref(x, w, t)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    np.testing.assert_allclose(dx, ref_dx, rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(dw, ref_dw, rtol=2e-4, atol=1e-5)
    # and the evaluation path gives the differentiated path's value
    np.testing.assert_allclose(chunked_softmax_cross_entropy(
        x, w, t, num_chunks, jnp.bfloat16), loss, rtol=1e-6)


def _flagship(loss_chunks, **kw):
    from paddle_tpu.models.llama_pretrain import LlamaPretrainConfig
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_seq_len=32,
                use_pallas_attention=False, sequence_parallel=False,
                dtype=jnp.float32, loss_chunks=loss_chunks)
    base.update(kw)
    return LlamaPretrainConfig(**base)


@pytest.mark.parametrize("dp,mp", [(1, 1), (2, 2)])
def test_forward_loss_gradients_every_leaf(dp, mp):
    # (2, 2): the residual dW carries lm_head's sharding, dx the
    # activations' (sequence parallel on)
    from paddle_tpu.models.llama_pretrain import (
        build_mesh, init_params, make_forward)
    mesh = build_mesh(dp=dp, mp=mp, devices=jax.devices()[:dp * mp])
    tokens = jnp.asarray(np.random.RandomState(3).randint(0, 128, (4, 33)))
    kw = dict(sequence_parallel=mp > 1)
    with mesh:
        params = init_params(_flagship(0), jax.random.PRNGKey(0), mesh)
        (l0, g0), (l4, g4) = (
            jax.jit(jax.value_and_grad(make_forward(_flagship(c, **kw),
                                                    mesh)))(
                params, tokens) for c in (0, 4))
    assert abs(float(l0) - float(l4)) < 1e-5
    assert jax.tree_util.tree_structure(g0) == \
        jax.tree_util.tree_structure(g4)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g0),
                            jax.tree_util.tree_leaves(g4)):
        assert a.dtype == b.dtype and a.sharding == b.sharding, path
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5,
                                   err_msg=str(path))


def test_train_step_with_accumulation_matches_the_plain_head():
    # the head differentiated inside the accumulation scan of the one
    # jitted, donated step program
    from paddle_tpu.models.llama_pretrain import (
        build_mesh, init_adafactor_state, init_params, make_train_step)
    mesh = build_mesh(devices=jax.devices()[:1])
    tokens = jnp.asarray(np.random.RandomState(4).randint(0, 128, (4, 33)))
    out = []
    with mesh:
        for c in (0, 2):
            cfg = _flagship(c)
            params = init_params(cfg, jax.random.PRNGKey(0), mesh)
            step = make_train_step(cfg, mesh, lr=1e-2,
                                   optimizer="adafactor", accum_steps=2)
            new, _, loss = step(params, init_adafactor_state(params),
                                tokens)
            out.append((float(loss), new))
    assert abs(out[0][0] - out[1][0]) < 1e-5
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(out[0][1]),
            jax.tree_util.tree_leaves(out[1][1])):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5,
                                   err_msg=str(path))
