"""Pallas kernel parity tests (interpret mode on CPU; the same kernels
compile natively on TPU)."""

import functools
import importlib
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
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
    g = jax.grad(lambda *a: (flash_attention(*a, causal) ** 2).sum(),
                 argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (_ref_attn(*a, causal) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
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
    g = jax.grad(lambda *a: (flash_attention(*a, causal) * w).sum(),
                 argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (ref(*a) * w).sum(),
                  argnums=(0, 1, 2))(q, k, v)
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
    for a, b_ in zip(jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v),
                     jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(a, b_, atol=2e-4, rtol=2e-4)


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
    grad = jax.grad(loss, argnums=tuple(range(len(args))))
    # a ``pallas_call``'s name, not ``name[name=flash_out]``: the names
    # ``_flash_fwd`` gives its outputs for a checkpoint policy
    kernels = re.findall(r"(?<!name\[)\bname=(flash_\w+)",
                         str(jax.make_jaxpr(grad)(*args)))
    return grad(*args), kernels


def _flash_grads(fn, q, k, v, w, causal):
    """(dq, dk, dv) of sum(fn(q, k, v, causal) * w), and the kernels."""
    return _grads_and_kernels(lambda *a: (fn(*a, causal) * w).sum(), q, k, v)


def _two_kernels(monkeypatch, fa):
    """Both one-pass budgets at 0 bytes (the module constants, no flag):
    ``flash_bwd_dq`` then ``flash_bwd_dkv``, whatever the shapes."""
    monkeypatch.setattr(fa, "ONE_PASS_DQ_BYTES", 0)
    monkeypatch.setattr(fa, "ONE_PASS_DKV_BYTES", 0)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("h,nkv,d,s", [
    (2, 2, 128, 512),       # group 1, flat, ONE 512-block
    (4, 2, 128, 1024),      # group 2, flat, two 512-blocks
    (4, 1, 128, 192),       # group 4, flat, three 64-blocks
    (4, 2, 64, 576),        # group 2, transposed entry, nine 64-blocks
    (2, 2, 64, 1024),       # group 1, transposed entry, two 512-blocks
    (4, 1, 64, 512),        # group 4, transposed entry, one block
])
def test_flash_backward_one_pass_parity(monkeypatch, causal, h, nkv, d, s):
    """The backward in one pass — ``flash_bwd_dkv`` sums dQ too and forms
    delta from ``o``; ``flash_bwd_dq`` does not run — against autodiff of
    the XLA attention AND against the two kernels on the same inputs
    (the budgets set to 0 bytes: the module constants, no flag)."""
    fa = _flash_module()
    args = _flash_inputs(h * 1000 + nkv * 100 + d + s, 1, s, h, nkv, d)
    assert (h // nkv) * s * d * 4 <= fa.ONE_PASS_DQ_BYTES
    one, kernels = _flash_grads(fa.flash_attention, *args, causal)
    assert kernels == ["flash_fwd", "flash_bwd_dkv"]
    _two_kernels(monkeypatch, fa)
    two, kernels = _flash_grads(fa.flash_attention, *args, causal)
    assert kernels == ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]
    want, _ = _flash_grads(fa._xla_sdpa, *args, causal)
    for name, a, b_, c in zip(("dq", "dk", "dv"), one, two, want):
        assert a.shape == c.shape, name
        # a re-ordered fp32 sum at most
        np.testing.assert_allclose(a, b_, atol=2e-5, rtol=2e-5, err_msg=name)
        np.testing.assert_allclose(a, c, atol=2e-4, rtol=2e-4, err_msg=name)


@pytest.mark.parametrize("d", [128, 64])       # in place | transposed
@pytest.mark.parametrize("group", [1, 4, 7])
@pytest.mark.parametrize("form", ["causal", "window", "full"])
def test_flash_backward_by_query_parity(monkeypatch, form, group, d):
    """The QUERY-major one pass — ``flash_bwd_dq``'s site and grid also
    sums dK and dV of the KV head in two fp32 ``[S, d]`` scratches;
    ``flash_bwd_dkv`` does not run — on a row of five 64-blocks, two KV
    heads (the sums start again at the second), a window of two blocks
    (its edge in one): dq, dk, dv against the two kernels on the same
    inputs and against autodiff of the XLA attention."""
    fa = _flash_module()
    s, nkv = 320, 2
    causal, window = form != "full", 128 if form == "window" else None
    args = _flash_inputs(group * 100 + d, 1, s, group * nkv, nkv, d)
    assert fa._pick_blocks(s, window) == (64, 64)
    flash = lambda q, k, v, c: fa.flash_attention(q, k, v, c, window=window)
    plain = lambda q, k, v, c: fa._xla_sdpa(q, k, v, c, window)
    names = tuple(("flash_win_" if window else "flash_") + x
                  for x in ("fwd", "bwd_dq", "bwd_dkv"))
    # past rule (a) at any size; 2 * 320 * 128 * 4 B is within rule (b)
    monkeypatch.setattr(fa, "ONE_PASS_DQ_BYTES", 0)
    one, kernels = _flash_grads(flash, *args, causal)
    assert tuple(kernels) == names[:2]
    monkeypatch.setattr(fa, "ONE_PASS_DKV_BYTES", 0)
    two, kernels = _flash_grads(flash, *args, causal)
    assert tuple(kernels) == names
    want, _ = _flash_grads(plain, *args, causal)
    for name, a, b_, c in zip(("dq", "dk", "dv"), one, two, want):
        assert a.shape == c.shape, name
        # a re-ordered fp32 sum at most
        np.testing.assert_allclose(a, b_, atol=2e-5, rtol=2e-5, err_msg=name)
        np.testing.assert_allclose(a, c, atol=2e-4, rtol=2e-4, err_msg=name)


@pytest.mark.parametrize("case,shape,kernels", [
    # (a) the dense cell: 2 MiB of fp32 dQ a group — key-major, as ever
    ("a", (8, 2048, 16, 8, 128, None), ["flash_fwd", "flash_bwd_dkv"]),
    # (b) the window cell's two forms: 58.7 MB of dQ, 16 MiB of dK and dV
    ("b", (1, 16384, 28, 4, 128, None), ["flash_fwd", "flash_bwd_dq"]),
    ("b, window", (1, 16384, 28, 4, 128, 4096),
     ["flash_win_fwd", "flash_win_bwd_dq"]),
    # (b) the hybrid cell: 8 MiB of dQ; a 64-wide row fills a lane tile:
    # 8 MiB of dK and dV
    ("b, d 64", (2, 8192, 32, 8, 64, None), ["flash_fwd", "flash_bwd_dq"]),
    # (c) a row of 32,768: 32 MiB of dK and dV — the two kernels
    ("c", (1, 32768, 28, 4, 128, None),
     ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
    ("c, window", (1, 32768, 28, 4, 128, 4096),
     ["flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv"]),
])
def test_flash_backward_form_follows_the_shapes(case, shape, kernels):
    """The three-way rule of ``_flash_bwd_vjp`` at the cells' own
    shapes, by the kernels' names in the traced program (nothing runs):
    the module's constants as they are, no flag."""
    fa = _flash_module()
    b, s, h, nkv, d, window = shape
    q, kv = (jax.ShapeDtypeStruct((b, s, n, d), jnp.bfloat16)
             for n in (h, nkv))
    group = h // nkv
    lanes = -(-d // 128) * 128
    assert case[0] == ("a" if group * s * d * 4 <= fa.ONE_PASS_DQ_BYTES
                       else "b" if 2 * s * lanes * 4 <= fa.ONE_PASS_DKV_BYTES
                       else "c")
    text = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: fa.flash_attention(
            q, k, v, True, window=window).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(q, kv, kv))
    assert re.findall(r"(?<!name\[)\bname=(flash_\w+)", text) == kernels


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


@pytest.mark.parametrize("windows,h,nkv,d,window,backward", [
    # the cell's group of 7 at head dim 128, blocks of 512, a window of
    # two blocks: 7 * S * 128 * 4 B of dQ is past the key-major budget —
    # the QUERY-major one pass, and (both budgets at 0) the two kernels
    (2, 7, 1, 128, 1024, ["dq"]), (3, 7, 1, 128, 1024, ["dq"]),
    (3, 7, 1, 128, 1024, ["dq", "dkv"]),
    # four windows, a group of 2: the key-major one pass under a window
    (4, 2, 1, 128, 1024, ["dkv"]),
    # the transposed entry, a window of ONE 64-block, GQA 4 / 2
    (4, 4, 2, 64, 64, ["dkv"]), (3, 4, 2, 64, 64, ["dkv"]),
])
def test_flash_window_parity(monkeypatch, windows, h, nkv, d, window,
                             backward):
    """The windowed form — the dense kernels' bodies on the block pairs
    a window leaves, under the names ``flash_win_*`` — against a plain
    masked attention: the output and the three gradients, on rows of 2,
    3 and 4 windows, the first and the last query block alike."""
    fa = _flash_module()
    if len(backward) == 2:
        _two_kernels(monkeypatch, fa)
    s = windows * window
    q, k, v, w = _flash_inputs(windows * 100 + h * 10 + d, 1, s, h, nkv, d)
    block = fa._pick_blocks(s, window)[0]
    assert block == min(512, window) and window % block == 0
    flash = lambda q, k, v, causal: fa.flash_attention(q, k, v, causal,
                                                       window=window)
    plain = lambda q, k, v, causal: _masked_attention(q, k, v, window)
    got, names = _flash_grads(flash, q, k, v, w, True)
    assert names == ["flash_win_fwd"] + ["flash_win_bwd_" + x
                                         for x in backward]
    want, _ = _flash_grads(plain, q, k, v, w, True)
    out, ref = flash(q, k, v, True), plain(q, k, v, True)
    for rows in (slice(0, block), slice(s - block, s), slice(None)):
        np.testing.assert_allclose(out[:, rows], ref[:, rows], atol=2e-5,
                                   rtol=2e-5)
        for name, a, b_ in zip(("dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(a[:, rows], b_[:, rows], atol=2e-4,
                                       rtol=2e-4, err_msg=name)
    # and it is NOT the causal form's result: the window hides keys
    dense = fa.flash_attention(q, k, v, True)
    assert float(jnp.max(jnp.abs(dense[:, -block:] - ref[:, -block:]))) \
        > 1e-2


def test_flash_window_picks_its_form_by_the_shapes():
    """A window the row does not outgrow hides nothing and is the dense
    form, kernel names and all; one that fits no block falls to the
    composite's mask; a window is causal."""
    fa = _flash_module()
    q, k, v, w = _flash_inputs(3, 1, 256, 2, 1, 128)
    for window in (256, 4096):
        got, names = _flash_grads(
            lambda *a: fa.flash_attention(*a, window=window), q, k, v, w,
            True)
        assert names == ["flash_fwd", "flash_bwd_dkv"]
        want, _ = _flash_grads(fa.flash_attention, q, k, v, w, True)
        for a, b_ in zip(got, want):
            assert bool(jnp.all(a == b_))
    assert fa._pick_blocks(256, 63) is None
    odd = fa.flash_attention(q, k, v, True, window=63)
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda *a: fa.flash_attention(*a, True, window=63))(q, k, v))
    np.testing.assert_allclose(odd, _masked_attention(q, k, v, 63),
                               atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError):
        fa.flash_attention(q, k, v, False, window=64)
    # the pairs a head's kernels execute: the cell's row, and a toy's
    assert fa._pairs(16384, 512, True, 4096) == 252
    assert fa._pairs(16384, 512, True) == 528
    assert fa._pairs(2048, 512, True, 1024) == 1 + 2 + 3 + 3
    assert fa._pairs(2048, 512, True, 2048) == fa._pairs(2048, 512, True)


@pytest.mark.parametrize("form", ["dense", "split"])
@pytest.mark.parametrize("fits", ["dq", "dkv", "neither"])
def test_flash_backward_pass_count_follows_the_vmem_rule(monkeypatch, fits,
                                                         form):
    """Key-major in one pass where a group's fp32 dQ, group*S*d*4 B, is
    within ``ONE_PASS_DQ_BYTES``; a byte past it the dense form goes
    query-major in one pass where a KV head's fp32 dK and dV,
    2*S*lanes(d)*4 B, are within ``ONE_PASS_DKV_BYTES``, and a byte past
    that the two kernels run — with the same gradients.  The split form
    goes by the FIRST rule alone (its dQ2 is no part of the budget) and
    keeps the two kernels past it."""
    fa = _flash_module()
    if form == "dense":
        h, nkv, d, s = 4, 2, 128, 256
        args = _flash_inputs(7, 2, s, h, nkv, d)
        run = lambda fn: _flash_grads(fn, *args, True)
        flash, plain = fa.flash_attention, fa._xla_sdpa
    else:
        h, nkv, d, s = 2, 2, 128, 256
        *args, co = _split_inputs(2, s, h, d, 64)
        run = lambda fn: _split_grads(fn, *args, co, 0.137)
        flash, plain = fa.flash_attention_split, _concatenated_attention
    need, need_dkv = (h // nkv) * s * d * 4, 2 * s * d * 4
    monkeypatch.setattr(fa, "ONE_PASS_DQ_BYTES",
                        need if fits == "dq" else need - 1)
    monkeypatch.setattr(fa, "ONE_PASS_DKV_BYTES",
                        need_dkv if fits == "dkv" else need_dkv - 1)
    got, kernels = run(flash)
    assert kernels == ["flash_fwd"] + {
        "dq": ["flash_bwd_dkv"],
        "dkv": ["flash_bwd_dq"] + ["flash_bwd_dkv"] * (form == "split"),
        "neither": ["flash_bwd_dq", "flash_bwd_dkv"]}[fits]
    want, _ = run(plain)
    for a, b_ in zip(got, want):
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


def test_rms_norm_parity():
    from paddle_tpu.ops.pallas.rms_norm import rms_norm
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.normal(0, 1, (4, 16, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(1, 0.1, (256,)), jnp.float32)

    def ref(x, w, eps=1e-6):
        var = jnp.mean(x * x, -1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * w

    np.testing.assert_allclose(rms_norm(x, w), ref(x, w), atol=1e-5,
                               rtol=1e-5)
    g = jax.grad(lambda x, w: (rms_norm(x, w) ** 2).sum(),
                 argnums=(0, 1))(x, w)
    gr = jax.grad(lambda x, w: (ref(x, w) ** 2).sum(),
                  argnums=(0, 1))(x, w)
    np.testing.assert_allclose(g[0], gr[0], atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(g[1], gr[1], atol=1e-3, rtol=1e-4)


def test_fused_adamw_parity():
    from paddle_tpu.ops.pallas.fused_adamw import fused_adamw
    rng = np.random.RandomState(0)
    p = jnp.asarray(rng.normal(0, 1, (64, 128)), jnp.float32)
    g = jnp.asarray(rng.normal(0, 1, (64, 128)), jnp.float32)
    m = jnp.zeros_like(p)
    v = jnp.zeros_like(p)
    lr, b1, b2, eps, wd = 1e-3, 0.9, 0.95, 1e-8, 0.1
    new_p, mo = fused_adamw(p, g, m, v, 1.0, lr, b1, b2, eps, wd)
    # reference update
    m_ref = b1 * m + (1 - b1) * g
    v_ref = b2 * v + (1 - b2) * g * g
    mhat = m_ref / (1 - b1)
    vhat = v_ref / (1 - b2)
    p_ref = p * (1 - lr * wd) - lr * mhat / (jnp.sqrt(vhat) + eps)
    np.testing.assert_allclose(new_p, p_ref, atol=1e-6)
    np.testing.assert_allclose(mo["m"], m_ref, atol=1e-6)
    np.testing.assert_allclose(mo["v"], v_ref, atol=1e-6)


def test_fused_adamw_indivisible_size():
    """Sizes not divisible by 128 must pad to (8,128) tiles rather than
    fall back to a [N,1] layout (128x padded-HBM blowup under TPU tiling)."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.fused_adamw import fused_adamw
    n = 1000
    p = jnp.arange(n, dtype=jnp.float32) * 0.01
    g = jnp.ones(n, jnp.float32) * 0.1
    m = jnp.zeros(n, jnp.float32)
    v = jnp.zeros(n, jnp.float32)
    p2, st = fused_adamw(p, g, m, v, 1, 1e-2)
    b1, b2, eps, wd, lr, t = 0.9, 0.95, 1e-8, 0.1, 1e-2, 1
    m2 = (1 - b1) * g
    v2 = (1 - b2) * g * g
    ref = (p * (1 - lr * wd)
           - lr * (m2 / (1 - b1 ** t)) / (jnp.sqrt(v2 / (1 - b2 ** t)) + eps))
    np.testing.assert_allclose(np.asarray(p2), np.asarray(ref), rtol=1e-4,
                               atol=1e-7)
    assert p2.shape == (n,) and st["m"].shape == (n,) and st["v"].shape == (n,)


def test_fused_rope_parity(_interpret_mode):
    from paddle_tpu.ops.pallas import fused_rope, rope_tables
    rng = np.random.RandomState(4)
    b, s, n, d = 2, 16, 4, 128
    x = jnp.asarray(rng.randn(b, s, n, d).astype(np.float32))
    cos, sin = rope_tables(s, d)

    def ref_rope(x):
        x1, x2 = jnp.split(x, 2, axis=-1)
        c = cos[None, :, None, :]
        s_ = sin[None, :, None, :]
        return jnp.concatenate([x1 * c - x2 * s_, x2 * c + x1 * s_], -1)

    np.testing.assert_allclose(np.asarray(fused_rope(x, cos, sin)),
                               np.asarray(ref_rope(x)), atol=1e-5)
    gr = jax.grad(lambda x: jnp.sum(ref_rope(x) * 0.2))(x)
    gk = jax.grad(lambda x: jnp.sum(fused_rope(x, cos, sin) * 0.2))(x)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), atol=1e-5)


@pytest.mark.parametrize("split", [False, True])
def test_incubate_swiglu_matches_numpy(split):
    """incubate.nn.functional.swiglu against silu(x)·y written out in
    numpy, as swiglu(x, y) and as the split-last-axis swiglu(x)."""
    import paddle_tpu as paddle
    import paddle_tpu.incubate.nn.functional as IF
    rng = np.random.RandomState(5)
    x = rng.randn(4, 64).astype(np.float32)
    y = rng.randn(4, 64).astype(np.float32)
    want = x / (1.0 + np.exp(-x)) * y
    if split:
        got = IF.swiglu(paddle.to_tensor(np.concatenate([x, y], -1)))
    else:
        got = IF.swiglu(paddle.to_tensor(x), paddle.to_tensor(y))
    np.testing.assert_allclose(got.numpy(), want, atol=1e-6, rtol=1e-6)


def test_incubate_fused_rope_kernel_route(_interpret_mode):
    """fused_rotary_position_embedding routes to the kernel under
    FLAGS_pallas_rope (neox style, default tables) with identical
    numerics."""
    import paddle_tpu as paddle
    import paddle_tpu.incubate.nn.functional as IF
    from paddle_tpu.flags import set_flags
    rng = np.random.RandomState(6)
    q = paddle.to_tensor(rng.randn(2, 16, 4, 128).astype(np.float32))
    set_flags({"FLAGS_pallas_rope": False})
    try:
        base = IF.fused_rotary_position_embedding(q)[0].numpy()
    finally:
        set_flags({"FLAGS_pallas_rope": True})
    kern = IF.fused_rotary_position_embedding(q)[0].numpy()
    np.testing.assert_allclose(kern, base, atol=1e-5)


def test_int8_matmul_parity(_interpret_mode):
    from paddle_tpu.ops.pallas import int8_matmul, quantize_int8
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(5, 256).astype(np.float32))
    w = jnp.asarray(rng.randn(256, 384).astype(np.float32) * 0.1)
    qd = quantize_int8(w)
    out = np.asarray(int8_matmul(x, qd["q"], qd["s"],
                                 out_dtype=jnp.float32))
    ref = np.asarray(x) @ np.asarray(w)
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    assert rel < 0.02, rel


def test_quantized_decode_agrees(_interpret_mode):
    import jax
    from paddle_tpu.models.llama_pretrain import (LlamaPretrainConfig,
                                                  build_mesh,
                                                  init_params)
    from paddle_tpu.models.decode import (make_generate,
                                          quantize_params_int8)
    cfg = LlamaPretrainConfig(
        vocab_size=128, hidden_size=128, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_seq_len=64,
        use_pallas_attention=False, sequence_parallel=False,
        remat=False, dtype=jnp.float32)
    mesh = build_mesh(devices=jax.devices()[:1])
    with mesh:
        params = init_params(cfg, jax.random.PRNGKey(0), mesh)
        qparams = quantize_params_int8(params)
        gen = make_generate(cfg, prompt_len=8, max_new_tokens=6)
        prompt = jnp.asarray(
            np.random.RandomState(0).randint(0, 128, (2, 8)))
        t_full = np.asarray(gen(params, prompt, jax.random.PRNGKey(1)))
        t_q = np.asarray(gen(qparams, prompt, jax.random.PRNGKey(1)))
        # int8 flips occasional argmax ties on a random tiny model;
        # the sequences must still largely agree
        assert (t_full == t_q).mean() >= 0.5


# -- scores from two operand pairs (latent attention) ------------------------
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


@pytest.mark.parametrize("s,h,dtype", [
    (1024, 3, jnp.float32),     # two 512-row blocks: the off-diagonal loop
    (256, 2, jnp.float32),      # one 256-row block: the masked diagonal alone
    (1536, 1, jnp.float32),     # three: dQ | dQ2 summed over three k blocks
    (1024, 2, jnp.bfloat16)])
def test_flash_attention_split_forward_and_five_gradients(monkeypatch, s, h,
                                                          dtype):
    """The forward and the five gradients (dk2 is the SUM over the
    heads) against autodiff of the plain form, the backward in ONE pass
    — ``flash_bwd_dkv`` sums dQ and dQ2 too; ``flash_bwd_dq`` does not
    run — AND against the two kernels on the same inputs (the budget set
    to 0 bytes: the module constant, no flag)."""
    fa = _flash_module()
    *args, co = _split_inputs(2, s, h, 128, 64, dtype)
    co = co.astype(jnp.float32)
    scale = 0.137
    f32 = [a.astype(jnp.float32) for a in args]
    # bf16: the kernels' products take bf16 P and dS, the results leave
    # in bf16 (2^-8 of a value, and a few roundings on the way)
    out_tol, tol = (2e-5, 2e-4) if dtype == jnp.float32 else (6e-2, 6e-2)
    np.testing.assert_allclose(
        fa.flash_attention_split(*args, scale).astype(jnp.float32),
        _concatenated_attention(*f32, scale), atol=out_tol, rtol=out_tol)
    one, kernels = _split_grads(fa.flash_attention_split, *args, co, scale)
    assert kernels == ["flash_fwd", "flash_bwd_dkv"]
    monkeypatch.setattr(fa, "ONE_PASS_DQ_BYTES", 0)
    two, kernels = _split_grads(fa.flash_attention_split, *args, co, scale)
    assert kernels == ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]
    want, _ = _split_grads(_concatenated_attention, *f32, co, scale)
    for name, a, b_, c, x in zip(("dq", "dq2", "dk", "dk2", "dv"), one, two,
                                 want, args):
        assert a.shape == x.shape, name
        if dtype == jnp.float32:
            # a re-ordered fp32 sum at most
            np.testing.assert_allclose(a, b_, atol=2e-5, rtol=2e-5,
                                       err_msg=name)
        else:
            # the same terms from the same bf16 operands, rounded once
            assert float(jnp.abs(a - b_).max()) <= \
                2 ** -7 * float(jnp.abs(b_).max()), name
        np.testing.assert_allclose(a, c, atol=tol, rtol=tol, err_msg=name)


@pytest.mark.parametrize("kernels", [2, 3])
def test_flash_attention_split_makes_no_wide_operand(monkeypatch, kernels):
    """No ``[.., 192]`` operand and no h-fold copy of the shared key
    reaches the kernels, one pass (2) or two kernels (3: the budget set
    to 0 bytes): they take the five arrays as they are."""
    fa = _flash_module()
    if kernels == 3:
        monkeypatch.setattr(fa, "ONE_PASS_DQ_BYTES", 0)
    q, q2, k, k2, v, _ = _split_inputs(1, 512, 4, 128, 64)
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: fa.flash_attention_split(*a, 0.1).sum(),
        argnums=(0, 1, 2, 3, 4)))(q, q2, k, k2, v))
    assert text.count("pallas_call") == kernels
    assert "192" not in text and "concatenate" not in text


def test_flash_attention_split_refuses_what_it_cannot_address():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_split
    q, q2, k, k2, v, _ = _split_inputs(1, 256, 2, 128, 64)
    with pytest.raises(ValueError):
        flash_attention_split(q[..., :64], q2, k[..., :64], k2,
                              v[..., :64], 0.1)
    with pytest.raises(ValueError):
        flash_attention_split(q, q2, k, k2[:, :, None].repeat(2, 2), v, 0.1)


# -- grouped products over the experts held -----------------------------------
@pytest.mark.parametrize("sizes", [(300, 0, 257, 5), (0, 0, 0, 0),
                                   (1024, 0, 0, 0)])
def test_grouped_mm_matches_a_loop_over_the_experts(sizes):
    """Groups of uneven, zero and tile-crossing sizes, laid out as
    ``ops/moe.plan`` lays them: each at a multiple of TILE_M, one tile
    at least."""
    from paddle_tpu.ops.pallas.grouped_mm import (TILE_M, grouped_mm,
                                                  grouped_mm_dw)
    E, K, N = len(sizes), 128, 256
    tiles = [max(-(-n // TILE_M), 1) for n in sizes]
    M = (sum(tiles) + 3) * TILE_M               # three tiles never used
    te = np.full((M // TILE_M,), E - 1, np.int32)
    te[:sum(tiles)] = np.repeat(np.arange(E), tiles)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (M, K), jnp.float32)
    dy = jax.random.normal(ks[1], (M, N), jnp.float32)
    w = jax.random.normal(ks[2], (E, K, N), jnp.float32)
    starts = np.concatenate([[0], np.cumsum(tiles)[:-1]]) * TILE_M
    valid = np.zeros((M, 1), bool)
    for e0, n in zip(starts, sizes):
        valid[e0:e0 + n] = True
    dy = jnp.where(valid, dy, 0)                # rows without a pair: zero
    te_j, n_j = jnp.asarray(te), jnp.asarray([sum(tiles)], jnp.int32)
    out = grouped_mm(x, w, te_j, n_j)
    dx = grouped_mm(dy, w, te_j, n_j, trans_w=True)
    dw = grouped_mm_dw(x, dy, te_j, n_j, E)
    for e, (e0, t) in enumerate(zip(starts, tiles)):
        rows = slice(e0, e0 + t * TILE_M)
        np.testing.assert_allclose(out[rows], x[rows] @ w[e],
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(dx[rows], dy[rows] @ w[e].T,
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(dw[e], x[rows].T @ dy[rows],
                                   atol=2e-3, rtol=2e-4)


# -- the token side of the routed experts --------------------------------------
def _runs(counts, P):
    """Slots of ``len(counts)`` tokens holding ``counts`` pairs each:
    (slot_token [P], first_slot [T + 1])."""
    first = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    token = np.full((P,), -1, np.int32)
    token[:first[-1]] = np.repeat(np.arange(len(counts)), counts)
    return jnp.asarray(token), jnp.asarray(first)


@pytest.mark.parametrize("load", ["none", "one_each", "four_each", "mixed",
                                  "all_on_the_first_tile",
                                  "a_run_across_three_chunks"])
@pytest.mark.parametrize("T", [512, 300])
def test_moe_sum_pairs_sums_each_token_s_run_of_rows(load, T):
    """``moe_sum_pairs``: rows in token order, a token's run anywhere
    from empty to four rows, runs that start mid-chunk and cross chunks,
    tokens that are no whole number of tiles, slots past the last pair
    holding rows that must not be read into any sum."""
    from paddle_tpu.ops.pallas.moe_sum_pairs import CHUNK, moe_sum_pairs
    rng = np.random.default_rng(3)
    counts = {"none": np.zeros(T, int), "one_each": np.ones(T, int),
              "four_each": np.full(T, 4),
              "mixed": rng.integers(0, 5, T),
              "all_on_the_first_tile": np.where(np.arange(T) < 200, 4, 0),
              "a_run_across_three_chunks": np.where(
                  (np.arange(T) >= 100) & (np.arange(T) < 260), 4, 0),
              }[load]
    P = 4 * 512 + CHUNK
    token, first = _runs(counts, P)
    rows = jax.random.normal(jax.random.PRNGKey(8), (P, 256), jnp.float32)
    got = np.asarray(moe_sum_pairs(rows, token, first))
    assert got.shape == (T, 256)
    r = np.asarray(rows)
    # fp32 sums in the product's order: one ulp of what is summed
    for t in range(T):
        mine = r[int(first[t]):int(first[t + 1])]
        want = mine.sum(0) if len(mine) else np.zeros(256, np.float32)
        room = np.spacing(np.abs(mine).sum(0).astype(np.float32)) \
            if len(mine) > 2 else 0
        assert (np.abs(got[t] - want) <= room).all(), t


def test_moe_sum_pairs_rounds_once_in_the_rows_dtype():
    """bf16 rows: the sum is fp32 and the store is the one rounding."""
    from paddle_tpu.ops.pallas.moe_sum_pairs import moe_sum_pairs
    counts = np.arange(512) % 5
    token, first = _runs(counts, 2048)
    rows = jax.random.normal(jax.random.PRNGKey(9), (2048, 128),
                             jnp.bfloat16)
    got = moe_sum_pairs(rows, token, first)
    assert got.dtype == jnp.bfloat16
    r = np.asarray(rows.astype(jnp.float32))
    want = np.stack([r[int(first[t]):int(first[t + 1])].sum(0)
                     for t in range(512)])
    assert (np.asarray(got.astype(jnp.float32))
            == np.asarray(jnp.asarray(want).astype(jnp.bfloat16)
                          .astype(jnp.float32))).mean() > 0.999
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=8e-3,
                               atol=1e-6)
