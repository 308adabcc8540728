"""What the Pallas flash-attention parity files share
(``tests/test_pallas_flash*.py``; interpret mode on the CPU, the same
kernels compile natively on the TPU): the interpreter's switch, plain
attentions to compare with, seeded inputs, and the gradients of a form
with the names of the kernels its program calls.
"""

import importlib
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.flags import set_flags


@pytest.fixture(autouse=True)
def _interpret_mode():
    set_flags({"FLAGS_pallas_interpret": True})
    yield
    set_flags({"FLAGS_pallas_interpret": False})


def _ref_attn(q, k, v, causal):
    d = q.shape[-1]
    logits = jnp.einsum("bqnd,bknd->bnqk", q, k) / np.sqrt(d)
    if causal:
        s = logits.shape[-1]
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, -1)
    return jnp.einsum("bnqk,bknd->bqnd", p, v)


def _flash_module():
    """The module, not the function the package re-exports by its name."""
    return importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def _flash_inputs(seed, b, s, h, nkv, d):
    """q, k, v and the cotangent's weights, fp32."""
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.normal(0, 1, (b, s, n, d)), jnp.float32)
            for n in (h, nkv, nkv, h)]


def _grads_and_kernels(loss, *args):
    """The gradient of ``loss`` with respect to every argument, and the
    flash kernels the whole forward + backward program calls, in
    order."""
    traced = jax.jit(jax.grad(
        loss, argnums=tuple(range(len(args))))).trace(*args)
    # a ``pallas_call``'s name, not ``name[name=flash_out]``: the names
    # ``_flash_fwd`` gives its outputs for a checkpoint policy
    kernels = re.findall(r"(?<!name\[)\bname=(flash_\w+)",
                         str(traced.jaxpr))
    # ONE trace and ONE program a form: run op by op, the interpreter's
    # kernels compile and dispatch an equation at a time
    return traced.lower().compile()(*args), kernels


def _flash_grads(fn, q, k, v, w, causal):
    """(dq, dk, dv) of sum(fn(q, k, v, causal) * w), and the kernels."""
    return _grads_and_kernels(lambda *a: (fn(*a, causal) * w).sum(), q, k, v)


def _two_kernels(monkeypatch, fa):
    """Both one-pass budgets at 0 bytes (the module constants, no flag):
    ``flash_bwd_dq`` then ``flash_bwd_dkv``, whatever the shapes."""
    monkeypatch.setattr(fa, "ONE_PASS_DQ_BYTES", 0)
    monkeypatch.setattr(fa, "ONE_PASS_DKV_BYTES", 0)


def _masked_attention(q, k, v, window):
    """Plain attention under the window's own rule, key j visible to
    query i iff i - window < j <= i: a [s, s] mask, no kernel."""
    s, d = q.shape[1], q.shape[-1]
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / d ** .5
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    sc = jnp.where((j <= i) & (j > i - window), sc, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                      precision="highest")


def _split_inputs(b, s, h, d, d2, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    shapes = [(b, s, h, d), (b, s, h, d2), (b, s, h, d), (b, s, d2),
              (b, s, h, d), (b, s, h, d)]
    return [jax.random.normal(k, sh, dtype) for k, sh in zip(ks, shapes)]


def _concatenated_attention(q, q2, k, k2, v, scale):
    """Plain causal attention over the 192-wide operands: q | q2 against
    k | k2 copied to every head."""
    b, s, h, _ = q.shape
    qq = jnp.concatenate([q, q2], -1)
    kk = jnp.concatenate(
        [k, jnp.broadcast_to(k2[:, :, None], (b, s, h, k2.shape[-1]))], -1)
    sc = jnp.einsum("bqhd,bkhd->bhqk", qq, kk, precision="highest") * scale
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                      precision="highest")


def _split_grads(fn, q, q2, k, k2, v, co, scale):
    """The five gradients of sum(fn(q, q2, k, k2, v, scale) * co) in
    fp32, and the kernels."""
    grads, kernels = _grads_and_kernels(
        lambda *a: (fn(*a, scale).astype(jnp.float32) * co).sum(),
        q, q2, k, k2, v)
    return [g.astype(jnp.float32) for g in grads], kernels
