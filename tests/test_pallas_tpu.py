"""Compiled-path (Mosaic, interpret=False) Pallas kernel tests.

Round-1 lesson: interpret-only tests let three broken-on-TPU kernels ship
green.  This lane runs the kernels through the real Mosaic compiler on
the TPU chip — parity vs the XLA composite per dtype, decode shapes, and
the odd-length fallback (reference test model:
/root/reference/test/legacy_test/op_test.py:2762 per-place/dtype checks).

Run with:  PADDLE_TPU_TESTS_ON_TPU=1 python -m pytest tests/test_pallas_tpu.py
(the default CPU-pinned suite skips this file).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _grouped_rows import laid_out


@pytest.fixture(autouse=True)
def _needs_tpu():
    """The platform is asked when a test of THIS file starts — never
    while the module is imported (every xdist worker imports it)."""
    if jax.default_backend() != "tpu":
        pytest.skip("compiled Pallas lane needs the real TPU chip")


def _mk_qkv(b, s, h, d, dtype, kv_s=None):
    kk = jax.random.PRNGKey
    q = jax.random.normal(kk(0), (b, s, h, d), dtype)
    k = jax.random.normal(kk(1), (b, kv_s or s, h, d), dtype)
    v = jax.random.normal(kk(2), (b, kv_s or s, h, d), dtype)
    return q, k, v


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-2),
                                       (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_fwd_parity(dtype, tol, causal):
    from paddle_tpu.ops.pallas.flash_attention import (
        flash_attention, _xla_sdpa)
    q, k, v = _mk_qkv(1, 256, 4, 64, dtype)
    out = flash_attention(q, k, v, causal)
    ref = _xla_sdpa(q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), causal)
    err = float(jnp.abs(out.astype(jnp.float32) - ref).max())
    assert err < tol, err


def test_flash_bwd_parity():
    from paddle_tpu.ops.pallas.flash_attention import (
        flash_attention, _xla_sdpa)
    q, k, v = _mk_qkv(1, 256, 4, 64, jnp.float32)
    g = jax.grad(lambda *a: flash_attention(*a, True).sum(),
                 argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: _xla_sdpa(*a, True).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        assert float(jnp.abs(a - b).max()) < 2e-2


@pytest.mark.parametrize("s,h,nkv,d", [
    (1024, 4, 4, 128), (1024, 4, 2, 128), (1024, 4, 1, 128),
    (1024, 4, 2, 64), (192, 4, 2, 128), (576, 4, 2, 64),
    (2048, 16, 8, 128)])
def test_flash_gqa_fwd_bwd_parity(s, h, nkv, d):
    """K/V at their own head count through Mosaic: head dim 128
    addressed flat in [b, s, heads*d], 64 transposed.  1024 is two
    512-row blocks; 192 and 576 run in blocks of 64 (three, nine); 2048
    x 16 / 8 heads is the pretraining cell's own shape.  Every one of
    them takes the backward in ONE pass (``flash_bwd_dkv`` sums dQ)."""
    from paddle_tpu.ops.pallas.flash_attention import (
        flash_attention, _xla_sdpa)
    kk = jax.random.PRNGKey
    q = jax.random.normal(kk(0), (2, s, h, d), jnp.bfloat16)
    k = jax.random.normal(kk(1), (2, s, nkv, d), jnp.bfloat16)
    v = jax.random.normal(kk(2), (2, s, nkv, d), jnp.bfloat16)
    w = jax.random.normal(kk(3), (2, s, h, d), jnp.float32)

    def run(fn):
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        args = (q, k, v) if fn is flash_attention else f32
        out, vjp = jax.vjp(lambda *a: fn(*a, True).astype(jnp.float32),
                           *args)
        return [out] + [g.astype(jnp.float32) for g in vjp(w)]

    for got, want in zip(run(flash_attention), run(_xla_sdpa)):
        assert got.shape == want.shape
        err = float(jnp.abs(got - want).max())
        assert err < 6e-2 * max(1.0, float(jnp.abs(want).max())), err


@pytest.mark.parametrize("s,h,nkv,d", [
    (2048, 16, 8, 128), (576, 4, 2, 64),
    # past ``ONE_PASS_DQ_BYTES``: the QUERY-major pass, a group of 8 at
    # head dim 128 (in place) and the hybrid cell's 32 / 8 x 64
    (4096, 16, 2, 128), (8192, 32, 8, 64)])
def test_flash_backward_one_pass_matches_two_kernels(monkeypatch, s, h, nkv,
                                                     d):
    """The one-pass backward — key-major within ``ONE_PASS_DQ_BYTES``,
    query-major within ``ONE_PASS_DKV_BYTES`` — against ``flash_bwd_dq``
    + ``flash_bwd_dkv`` (both rules set to 0 bytes) on the same bf16
    inputs: dk and dv are the same sums, dq the same terms from a product
    turned round."""
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    kk = jax.random.PRNGKey
    q = jax.random.normal(kk(0), (2, s, h, d), jnp.bfloat16)
    k = jax.random.normal(kk(1), (2, s, nkv, d), jnp.bfloat16)
    v = jax.random.normal(kk(2), (2, s, nkv, d), jnp.bfloat16)
    w = jax.random.normal(kk(3), (2, s, h, d), jnp.bfloat16)

    def grads():
        _, vjp = jax.vjp(lambda *a: fa.flash_attention(*a, True), q, k, v)
        return [g.astype(jnp.float32) for g in vjp(w)]

    one = grads()
    monkeypatch.setattr(fa, "ONE_PASS_DQ_BYTES", 0)
    monkeypatch.setattr(fa, "ONE_PASS_DKV_BYTES", 0)
    for got, want in zip(one, grads()):
        err = float(jnp.abs(got - want).max())
        assert err <= 2 ** -7 * float(jnp.abs(want).max()), err


def test_flash_decode_and_odd_lengths():
    """q_len != kv_len (decode) and indivisible S take the XLA fallback
    and must stay finite/correct (round-1: NaN at S=129, crash at decode)."""
    from paddle_tpu.ops.pallas.flash_attention import (
        flash_attention, _xla_sdpa)
    q, k, v = _mk_qkv(1, 8, 2, 64, jnp.float32, kv_s=8)
    # decode: 1 query against 8-token cache == last row of full attention
    dec = flash_attention(q[:, -1:], k, v, True)
    full = _xla_sdpa(q, k, v, True)
    # fp32 matmuls run through the MXU at reduced internal precision on
    # TPU, so parity is ~1e-3, not 1e-6
    assert float(jnp.abs(dec[:, 0] - full[:, -1]).max()) < 2e-2
    # odd length: no block divides 129
    q2, k2, v2 = _mk_qkv(1, 129, 2, 64, jnp.float32)
    out = flash_attention(q2, k2, v2, True)
    assert bool(jnp.isfinite(out).all())


def test_flash_q_longer_than_kv_raises():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    q, k, v = _mk_qkv(1, 16, 2, 64, jnp.float32, kv_s=8)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, True)


def test_fused_adamw_compiled():
    from paddle_tpu.ops.pallas.fused_adamw import fused_adamw
    kk = jax.random.PRNGKey
    p = jax.random.normal(kk(0), (256, 128), jnp.float32)
    g = jax.random.normal(kk(1), (256, 128), jnp.float32)
    m = jnp.full_like(p, 0.5)
    v = jnp.full_like(p, 0.25)
    t, lr, b1, b2, eps, wd = 3, 1e-3, 0.9, 0.95, 1e-8, 0.1
    new_p, slots = fused_adamw(p, g, m, v, t, lr, b1, b2, eps, wd)
    mn = b1 * np.asarray(m) + (1 - b1) * np.asarray(g)
    vn = b2 * np.asarray(v) + (1 - b2) * np.asarray(g) ** 2
    mh = mn / (1 - b1 ** t)
    vh = vn / (1 - b2 ** t)
    ref = np.asarray(p) * (1 - lr * wd) - lr * mh / (np.sqrt(vh) + eps)
    assert np.abs(np.asarray(new_p) - ref).max() < 1e-6
    assert np.abs(np.asarray(slots["m"]) - mn).max() < 1e-6
    assert np.abs(np.asarray(slots["v"]) - vn).max() < 1e-6


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 4e-2)])
def test_rms_norm_compiled(dtype, tol):
    from paddle_tpu.ops.pallas.rms_norm import rms_norm
    kk = jax.random.PRNGKey
    x = jax.random.normal(kk(0), (64, 512), dtype)
    w = jax.random.normal(kk(1), (512,), jnp.float32)
    out = rms_norm(x, w)
    xf = np.asarray(x, np.float32)
    ref = xf / np.sqrt((xf ** 2).mean(-1, keepdims=True) + 1e-6) \
        * np.asarray(w)
    assert np.abs(np.asarray(out, np.float32) - ref).max() < tol
    if dtype == jnp.float32:
        gx, gw = jax.grad(lambda x, w: rms_norm(x, w).sum(),
                          argnums=(0, 1))(x, w)
        # numeric check on a few coordinates
        def f(x):
            return float(rms_norm(x, w).sum())
        eps = 1e-3
        for idx in [(0, 0), (3, 17), (63, 511)]:
            xp = x.at[idx].add(eps)
            xm = x.at[idx].add(-eps)
            num = (f(xp) - f(xm)) / (2 * eps)
            assert abs(num - float(gx[idx])) < 1e-2


def test_fused_rope_compiled():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import fused_rope, rope_tables
    kk = jax.random.PRNGKey
    b, s, n, d = 2, 256, 4, 128
    x = jax.random.normal(kk(0), (b, s, n, d), jnp.bfloat16)
    cos, sin = rope_tables(s, d)
    out = jax.jit(fused_rope)(x, cos, sin)
    xf = np.asarray(x, np.float32)
    x1, x2 = xf[..., :64], xf[..., 64:]
    c = np.asarray(cos)[None, :, None, :]
    s_ = np.asarray(sin)[None, :, None, :]
    ref = np.concatenate([x1 * c - x2 * s_, x2 * c + x1 * s_], -1)
    assert np.abs(np.asarray(out, np.float32) - ref).max() < 3e-2


def test_int8_matmul_compiled():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import int8_matmul, quantize_int8
    kk = jax.random.PRNGKey
    x = jax.random.normal(kk(0), (8, 512), jnp.bfloat16)
    w = jax.random.normal(kk(1), (512, 1024), jnp.float32) * 0.1
    qd = quantize_int8(w)
    out = jax.jit(lambda x: int8_matmul(x, qd["q"], qd["s"]))(x)
    ref = np.asarray(x, np.float32) @ np.asarray(w)
    rel = np.abs(np.asarray(out, np.float32) - ref).max() / \
        np.abs(ref).max()
    assert rel < 0.05, rel


@pytest.mark.parametrize("S,lens,hkv", [
    (512, [100, 44, 228, 140], 4),      # MHA, multi-block
    (512, [100, 44, 228, 140], 2),      # GQA-native kv index maps
    (64, [20, 30, 14], 4),              # one block under 128 lanes (the
    #                                     engine's smallest packed bucket)
])
def test_flash_varlen_segmented_compiled(S, lens, hkv):
    """Segment-aware varlen flash through real Mosaic: parity + grads
    vs the dense-mask XLA oracle on a ragged batch."""
    from paddle_tpu.ops.pallas.flash_varlen import (
        flash_attention_segmented, segment_ids_from_cu_seqlens,
        xla_segmented_sdpa)
    B, H, D = 1, 4, 64
    cu = np.cumsum([0] + lens)
    seg = jnp.asarray(np.asarray(
        segment_ids_from_cu_seqlens(jnp.asarray(cu), S))[None])
    kk = jax.random.PRNGKey
    q = jax.random.normal(kk(3), (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(kk(4), (B, S, hkv, D), jnp.bfloat16)
    v = jax.random.normal(kk(5), (B, S, hkv, D), jnp.bfloat16)
    out = jax.jit(lambda *a: flash_attention_segmented(
        *a, seg, causal=True))(q, k, v)
    ref = xla_segmented_sdpa(q, k, v, seg, True)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                ref.astype(jnp.float32))))
    assert err < 3e-2, err
    g = jax.jit(jax.grad(lambda *a: (flash_attention_segmented(
        *a, seg, causal=True).astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(lambda *a: (xla_segmented_sdpa(
        *a, seg, True).astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        rel = float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                    b.astype(jnp.float32)))) / (
            float(jnp.max(jnp.abs(b.astype(jnp.float32)))) + 1e-9)
        assert rel < 0.05, rel


def test_paged_decode_attention_compiled():
    """Block-table paged decode kernel (round 4) through real Mosaic:
    parity vs the XLA gather oracle at serving-like dims."""
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_decode_attention, paged_decode_attention_xla)
    rng = np.random.RandomState(0)
    B, n, nkv, d, P = 8, 16, 16, 128, 64
    pages_max = 8
    num_pages = B * pages_max + 1
    kpool = jnp.asarray(rng.randn(num_pages, nkv, P, d), jnp.bfloat16)
    vpool = jnp.asarray(rng.randn(num_pages, nkv, P, d), jnp.bfloat16)
    q = jnp.asarray(rng.randn(B, n, d), jnp.bfloat16)
    lens = np.array([500, 64, 512, 1, 130, 77, 256, 333], np.int32)
    tables = np.zeros((B, pages_max), np.int32)
    nf = 1
    for b in range(B):
        for j in range((lens[b] + P - 1) // P):
            tables[b, j] = nf
            nf += 1
    out = jax.jit(lambda *a: paged_decode_attention(
        *a, force_kernel=True))(q, kpool, vpool,
                                jnp.asarray(tables), jnp.asarray(lens))
    ref = paged_decode_attention_xla(q, kpool, vpool,
                                     jnp.asarray(tables),
                                     jnp.asarray(lens))
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                ref.astype(jnp.float32))))
    assert err < 3e-2, err


def test_paged_decode_attention_q8_compiled():
    """int8-KV paged decode kernel (scale-folded) through real Mosaic:
    parity vs its XLA dequantising oracle at serving dims."""
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_q8, paged_decode_attention_q8_xla)
    rng = np.random.RandomState(1)
    B, n, nkv, d, P = 8, 16, 16, 128, 64
    pages_max = 8
    num_pages = B * pages_max + 1

    def quant(x):
        s = np.abs(x).max(-1) / 127.0
        s[s == 0] = 1.0
        return (jnp.asarray(np.clip(np.round(x / s[..., None]), -127,
                                    127), jnp.int8),
                jnp.asarray(s, jnp.float32))

    kpool, kscale = quant(rng.randn(num_pages, nkv, P, d))
    vpool, vscale = quant(rng.randn(num_pages, nkv, P, d))
    q = jnp.asarray(rng.randn(B, n, d), jnp.bfloat16)
    lens = np.array([500, 64, 512, 1, 130, 77, 256, 333], np.int32)
    tables = np.zeros((B, pages_max), np.int32)
    nf = 1
    for b in range(B):
        for j in range((lens[b] + P - 1) // P):
            tables[b, j] = nf
            nf += 1
    args = (q, kpool, vpool, kscale, vscale, jnp.asarray(tables),
            jnp.asarray(lens))
    out = jax.jit(lambda *a: paged_decode_attention_q8(
        *a, force_kernel=True))(*args)
    ref = paged_decode_attention_q8_xla(*args)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                ref.astype(jnp.float32))))
    assert err < 3e-2, err


@pytest.mark.parametrize("b", [1, 2])
def test_flash_gqa_8k_head_dim_64(b):
    """The hybrid cell's attention layer (``b`` 2 is the cell's step):
    S 8192, 32 query / 8 KV heads of 64 — the transposed entry, and
    ``group * S * d * 4`` = 8 MiB of fp32 dQ, past ``ONE_PASS_DQ_BYTES``,
    with 8 MiB of fp32 dK and dV a KV head (a 64-wide row fills a lane
    tile) within ``ONE_PASS_DKV_BYTES``: the query-major one-pass
    backward.  The composite holds [heads, S, S], so it is asked for one
    KV head's group of the last row at a time (a group's gradients
    depend on no other)."""
    from paddle_tpu.ops.pallas.flash_attention import (
        flash_attention, _xla_sdpa)
    s, h, nkv, d = 8192, 32, 8, 64
    kk = jax.random.PRNGKey
    q = jax.random.normal(kk(0), (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(kk(1), (b, s, nkv, d), jnp.bfloat16)
    v = jax.random.normal(kk(2), (b, s, nkv, d), jnp.bfloat16)
    w = jax.random.normal(kk(3), (b, s, h, d), jnp.float32)
    out, vjp = jax.vjp(lambda *a: flash_attention(*a, True).astype(
        jnp.float32), q, k, v)
    dq, dk, dv = (g.astype(jnp.float32)[-1:] for g in vjp(w))
    out, q, k, v, w = (x[-1:] for x in (out, q, k, v, w))
    g = h // nkv
    for j in (0, nkv - 1):
        heads = slice(j * g, (j + 1) * g)
        f32 = [x.astype(jnp.float32)
               for x in (q[:, :, heads], k[:, :, j:j + 1], v[:, :, j:j + 1])]
        want, ref_vjp = jax.vjp(lambda *a: _xla_sdpa(*a, True), *f32)
        wants = [want] + list(ref_vjp(w[:, :, heads]))
        gots = [out[:, :, heads], dq[:, :, heads], dk[:, :, j:j + 1],
                dv[:, :, j:j + 1]]
        for got, want in zip(gots, wants):
            err = float(jnp.abs(got - want).max())
            assert err < 6e-2 * max(1.0, float(jnp.abs(want).max())), err


@pytest.mark.parametrize("window", [None, 4096])
def test_flash_16k_at_the_window_cell_s_shapes(window):
    """The window cell's attention: ONE row of 16,384, 28 query / 4 KV
    heads of 128 — the dense form (the global layers; 16 MiB of K and V
    resident, the calls ask for their VMEM) and the windowed form (a
    window of 4,096: eight blocks and the two masked ends), the
    query-major one-pass backward in both (58.7 MB of fp32 dQ a group is
    past ``ONE_PASS_DQ_BYTES``; 16 MiB of fp32 dK and dV a KV head is
    ``ONE_PASS_DKV_BYTES``).  The composite holds [heads, S, S], so it is
    asked for one query head of one KV head at a time."""
    from paddle_tpu.ops.pallas.flash_attention import (
        flash_attention, _xla_sdpa)
    s, h, nkv, d = 16384, 28, 4, 128
    kk = jax.random.PRNGKey
    q = jax.random.normal(kk(0), (1, s, h, d), jnp.bfloat16)
    k = jax.random.normal(kk(1), (1, s, nkv, d), jnp.bfloat16)
    v = jax.random.normal(kk(2), (1, s, nkv, d), jnp.bfloat16)
    w = jax.random.normal(kk(3), (1, s, h, d), jnp.float32)
    out, vjp = jax.vjp(lambda *a: flash_attention(
        *a, True, window=window).astype(jnp.float32), q, k, v)
    dq, dk, dv = (g.astype(jnp.float32) for g in vjp(w))
    g = h // nkv
    for j, head in ((0, 0), (nkv - 1, h - 1)):
        f32 = [x.astype(jnp.float32) for x in (
            q[:, :, head:head + 1], k[:, :, j:j + 1], v[:, :, j:j + 1])]
        want, ref_vjp = jax.vjp(
            lambda *a: _xla_sdpa(*a, True, window), *f32)
        dq_want = ref_vjp(w[:, :, head:head + 1])[0]
        for got, ref in ((out[:, :, head:head + 1], want),
                         (dq[:, :, head:head + 1], dq_want)):
            err = float(jnp.abs(got - ref).max())
            assert err < 6e-2 * max(1.0, float(jnp.abs(ref).max())), err
    # dK and dV sum a group's seven heads: one KV head's whole group
    for j in (0, nkv - 1):
        heads = slice(j * g, (j + 1) * g)
        dkv = [0.0, 0.0]
        for head in range(j * g, (j + 1) * g):
            f32 = [x.astype(jnp.float32) for x in (
                q[:, :, head:head + 1], k[:, :, j:j + 1], v[:, :, j:j + 1])]
            _, ref_vjp = jax.vjp(
                lambda *a: _xla_sdpa(*a, True, window), *f32)
            _, dk1, dv1 = ref_vjp(w[:, :, head:head + 1])
            dkv = [dkv[0] + dk1, dkv[1] + dv1]
        for got, ref in ((dk[:, :, j:j + 1], dkv[0]),
                         (dv[:, :, j:j + 1], dkv[1])):
            err = float(jnp.abs(got - ref).max())
            assert err < 6e-2 * max(1.0, float(jnp.abs(ref).max())), err


def test_routed_ffn_relu_at_the_window_cell_s_shapes():
    """``routed_ffn`` under the ReLU gate at the window cell's widths
    (2560, experts of 768, top-6 of 64, 16 held) on 4,096 tokens, against
    the plain masked sum, value and the four gradients."""
    from paddle_tpu.ops import moe
    T, C, F, K, PUB, HELD = 4096, 2560, 768, 6, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    x = jax.random.normal(ks[0], (T, C), jnp.bfloat16)
    wgu = jax.random.normal(ks[1], (HELD, C, 2 * F), jnp.float32) / C ** .5
    wd = jax.random.normal(ks[2], (HELD, F, C), jnp.float32) / F ** .5
    co = jax.random.normal(ks[3], (T, C), jnp.bfloat16)
    idx, gate = moe.route(x, jax.random.normal(ks[4], (C, PUB)) / C ** .5,
                          K, 1.0, "softmax_of_picks")
    p = moe.plan(idx, 0, HELD, PUB)

    def plain(x, gate, wgu, wd):
        xf, y = x.astype(jnp.float32), jnp.zeros(x.shape, jnp.float32)
        for e in range(HELD):
            mine = jnp.sum(jnp.where(idx == e, gate, 0.0), -1)
            hid = jax.nn.relu(xf @ wgu[e][:, :F]) * (xf @ wgu[e][:, F:])
            y = y + mine[:, None] * (hid @ wd[e])
        return y
    with jax.default_matmul_precision("highest"):
        want, ref_vjp = jax.vjp(plain, x, gate, wgu, wd)
        wants = [want] + list(ref_vjp(co.astype(jnp.float32)))
    got, vjp = jax.vjp(lambda *a: moe.routed_ffn(*a, p, "relu"), x, gate,
                       wgu, wd)
    # bf16 rows and hidden rows against float32: a gate unit whose
    # product lies within a rounding of zero takes the other side of the
    # ReLU, so single entries differ by a unit's whole term; the arrays
    # agree in the norm.  Read on the chip (PR 44, call 2): norms y 0.46 %,
    # dx 2.30 %, dgate 0.41 %, dwgu 2.28 %, dwd 0.37 %; the worst entry
    # 23 % of the largest (dwgu) — the bounds leave those twice their room
    errs = {}
    for name, a, b in zip(("y", "dx", "dgate", "dwgu", "dwd"),
                          [got] + list(vjp(co)), wants):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        errs[name] = (float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)),
                      float(jnp.abs(a - b).max() / jnp.abs(b).max()))
    print("routed_ffn relu, (norm, max) errors:", errs)
    assert all(norm < 5e-2 for norm, _ in errs.values()), errs
    assert all(worst < 0.5 for _, worst in errs.values()), errs


@pytest.mark.parametrize("b,s,at_once", [(2, 8192, 2), (1, 16384, 1)],
                         ids=["expert_cell_8k", "plain_latent_cell_16k"])
def test_flash_split_at_the_latent_cells_shapes(monkeypatch, b, s, at_once):
    """Latent attention as ``xing4.0-29b-a4b.pretrain-8k-moe`` (2 x 8192)
    and ``kanana-2-30b-a3b.pretrain-16k-mla-moe`` (1 x 16,384) run it:
    heads of 128 | 64 | 128, one shared rotated key — scores from two
    operand pairs, five gradients, the backward in ONE key-major pass (a
    head's fp32 dQ is 4 MiB at 8k: ``ONE_PASS_DQ_BYTES``; its dQ and dQ2
    are 16 MiB at 16k: ``ONE_PASS_DKV_BYTES``, 72 MiB of VMEM asked)
    against the plain form and against the two kernels (both budgets set
    to 0 bytes).  The composite holds [S, S] a head, so it is asked for
    ``at_once`` heads at a time of the first row."""
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    h = 4
    assert (s * 128 * 4 <= fa.ONE_PASS_DQ_BYTES) == (s == 8192) and \
        s * (128 + 128) * 4 <= fa.ONE_PASS_DKV_BYTES
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (b, s, h, 128), bf)
    q2 = jax.random.normal(ks[1], (b, s, h, 64), bf)
    k = jax.random.normal(ks[2], (b, s, h, 128), bf)
    k2 = jax.random.normal(ks[3], (b, s, 64), bf)
    v = jax.random.normal(ks[4], (b, s, h, 128), bf)
    w = jax.random.normal(ks[5], (b, s, h, 128), jnp.float32)
    scale = float(192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)  # weak-typed

    def grads():
        out, vjp = jax.vjp(
            lambda *a: fa.flash_attention_split(*a, scale).astype(
                jnp.float32), q, q2, k, k2, v)
        return [out] + [g.astype(jnp.float32) for g in vjp(w)]

    one = grads()
    monkeypatch.setattr(fa, "ONE_PASS_DQ_BYTES", 0)
    monkeypatch.setattr(fa, "ONE_PASS_DKV_BYTES", 0)
    for got, want in zip(one, grads()):
        # dk, dk2 and dv the same sums, dq and dq2 the same terms from a
        # product turned round
        err = float(jnp.abs(got - want).max())
        assert err <= 2 ** -7 * float(jnp.abs(want).max()), err
    out, dq, dq2, dk, dk2, dv = (x[:1] for x in one)

    def plain(q, q2, k, k2, v):
        qq = jnp.concatenate([q, q2], -1)
        kk = jnp.concatenate([k, jnp.broadcast_to(
            k2[:, :, None], q2.shape)], -1)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qq, kk) * scale
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
    f32 = [x[:1].astype(jnp.float32) for x in (q, q2, k, k2, v)]
    dk2_sum = 0.0
    for heads in (slice(at, at + at_once) for at in range(0, h, at_once)):
        cut = [f32[0][:, :, heads], f32[1][:, :, heads], f32[2][:, :, heads],
               f32[3], f32[4][:, :, heads]]
        want, ref_vjp = jax.vjp(plain, *cut)
        wq, wq2, wk, wk2, wv = ref_vjp(w[:1, :, heads])
        dk2_sum = dk2_sum + wk2
        for got, ref in ((out[:, :, heads], want), (dq[:, :, heads], wq),
                         (dq2[:, :, heads], wq2), (dk[:, :, heads], wk),
                         (dv[:, :, heads], wv)):
            err = float(jnp.abs(got - ref).max())
            assert err < 6e-2 * max(1.0, float(jnp.abs(ref).max())), err
    err = float(jnp.abs(dk2 - dk2_sum).max())
    assert err < 6e-2 * max(1.0, float(jnp.abs(dk2_sum).max())), err


def _grouped_against_a_loop(E, shapes, sizes, spare):
    """Forward, dx and dw of the grouped products at ``shapes`` ([K, N]
    of each product), ``E`` experts with groups of ``sizes`` rows and
    ``spare`` tiles never used behind them, against a loop over the
    experts: fp32 weights cast in VMEM, bf16 rows."""
    from paddle_tpu.ops.pallas.grouped_mm import (TILE_M, grouped_mm,
                                                  grouped_mm_dw)
    M, te_j, n_j, starts, tiles, valid = laid_out(sizes, spare)
    ks = jax.random.split(jax.random.PRNGKey(1), 3 * len(shapes))
    bf = jnp.bfloat16
    for p, (K, N) in enumerate(shapes):
        kk = ks[3 * p:3 * p + 3]
        x = jax.random.normal(kk[0], (M, K), bf)
        dy = jnp.where(valid, jax.random.normal(kk[1], (M, N), bf), 0)
        w = jax.random.normal(kk[2], (E, K, N), jnp.float32) / K ** 0.5
        out = grouped_mm(x, w, te_j, n_j).astype(jnp.float32)
        dx = grouped_mm(dy, w, te_j, n_j, trans_w=True).astype(jnp.float32)
        dw = grouped_mm_dw(x, dy, te_j, n_j, E)
        assert dw.dtype == jnp.float32
        wb = w.astype(bf)
        for e, (e0, t) in enumerate(zip(starts, tiles)):
            rows = slice(int(e0), int(e0) + t * TILE_M)
            pairs = ((out[rows], jnp.dot(x[rows], wb[e],
                                         preferred_element_type=jnp.float32)),
                     (dx[rows], jnp.dot(dy[rows], wb[e].T,
                                        preferred_element_type=jnp.float32)),
                     (dw[e], jnp.dot(x[rows].T, dy[rows],
                                     preferred_element_type=jnp.float32)))
            for got, want in pairs:
                err = float(jnp.abs(got - want).max())
                assert err < 3e-2 * max(1.0, float(jnp.abs(want).max())), \
                    (K, N, e, err)


def test_grouped_mm_at_the_expert_cell_s_shapes():
    """The grouped products of ``ops/moe.py`` at the cell's widths: 8
    experts of 3584 x 2048 (gate | up) and 1024 x 3584, fp32 weights
    cast in VMEM, bf16 rows, groups of uneven sizes with one EMPTY, tiles
    never used behind them — forward, dx and dw against a loop over the
    experts."""
    _grouped_against_a_loop(
        8, ((3584, 2048), (1024, 3584)),
        [1100, 0, 900, 1024, 1, 2047, 513, 700], spare=40)


@pytest.mark.parametrize("cell, shapes", [
    ("convolution", ((2048, 3072), (1536, 2048))),
    ("window", ((2560, 1536), (768, 2560)))])
def test_grouped_mm_at_the_sixteen_expert_cells_shapes(cell, shapes):
    """The same through Mosaic at the other two cells' widths, 16
    experts each — three column panels of 1,024 and two, narrow panels
    of 512 and 640 —: one group EMPTY, one of ONE row, groups of one
    tile next to each other (the next expert's panel is asked for one
    product ahead and has to be waited for), half the tiles never used."""
    _grouped_against_a_loop(
        16, shapes, [1100, 0, 900, 1024, 1, 2047, 513, 700, 256, 255, 257,
                     1536, 3, 1024, 800, 1200], spare=56)


def test_moe_sum_pairs_at_the_expert_cell_s_shapes():
    """The token side of ``ops/moe.py`` through Mosaic at the cell's
    widths — 16,384 tokens of 3584, tokens holding 0 to 4 pairs, on the
    bound that follows the load (18,432 slots) and on the bound of any
    load (65,536) — against the sum of each token's run of rows."""
    from paddle_tpu.ops.pallas.moe_sum_pairs import moe_sum_pairs
    T, C = 16384, 3584
    rng = np.random.default_rng(11)
    for P, p_pair in ((18432, 0.125), (65536, 0.9)):
        counts = rng.binomial(4, p_pair, T)
        first = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
        assert first[-1] <= P
        token = np.full((P,), -1, np.int32)
        token[:first[-1]] = np.repeat(np.arange(T), counts)
        rows = jax.random.normal(jax.random.PRNGKey(P), (P, C), jnp.bfloat16)
        got = moe_sum_pairs(rows, jnp.asarray(token), jnp.asarray(first))
        slot = np.minimum(first[:-1, None] + np.arange(4)[None], P - 1)
        want = jnp.sum(jnp.where((np.arange(4)[None] < counts[:, None])
                                 [..., None], rows[slot].astype(jnp.float32),
                                 0), axis=1)
        err = float(jnp.abs(got.astype(jnp.float32) - want).max())
        assert err <= 2 ** -7 * max(1.0, float(jnp.abs(want).max())), err
        assert not bool(jnp.any(got[counts == 0]))


def _ssd_inputs(b, s, h, p, n, dtype):
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), jnp.float32)
                         - 3.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (h,), jnp.float32, 0.0, 2.7))
    B = (jax.random.normal(ks[3], (b, s, n), jnp.float32) * 0.3).astype(dtype)
    C = (jax.random.normal(ks[4], (b, s, n), jnp.float32) * 0.3).astype(dtype)
    return x, dt, A, B, C


@pytest.mark.parametrize("b,s,h,p,n,q", [(2, 8192, 64, 64, 128, 256),
                                         (1, 512, 4, 128, 64, 128)])
def test_ssd_scan_kernels_match_the_jnp_form(b, s, h, p, n, q):
    """``ssd_scan_fwd`` / ``ssd_scan_bwd`` through Mosaic at the hybrid
    cell's own shape (2 x 8192, 64 heads of 64, state 128, chunk 256)
    against the chunked ``jnp`` form, values and all five gradients."""
    from paddle_tpu.ops import ssd_scan as op
    from paddle_tpu.ops.pallas import ssd_scan as kernel
    x, dt, A, B, C = _ssd_inputs(b, s, h, p, n, jnp.bfloat16)
    cut = lambda a: a.reshape(b, s // q, q, *a.shape[2:])
    xc, dtc, Bc, Cc = cut(x), cut(dt), cut(B), cut(C)
    assert kernel.takes(xc, Bc)
    w = jax.random.normal(jax.random.PRNGKey(9), xc.shape, jnp.float32)

    def run(form):
        def f(x, dt, A, B, C):
            cum = jnp.cumsum(dt * A, axis=2)
            return form(x, dt, cum, B, C).astype(jnp.float32)
        out, vjp = jax.vjp(f, xc, dtc, A, Bc, Cc)
        return [out] + [g.astype(jnp.float32) for g in vjp(w)]
    for got, want in zip(run(kernel.ssd_chunked), run(op.ssd_chunked_xla)):
        err = float(jnp.abs(got - want).max())
        assert err < 3e-2 * max(1.0, float(jnp.abs(want).max())), err


def test_causal_conv_kernels_match_the_jnp_form():
    """``causal_conv_fwd`` / ``causal_conv_bwd`` through Mosaic at the
    hybrid cell's shape: 2 x 8192 positions of 4352 channels, 4 taps."""
    from paddle_tpu.ops.pallas import causal_conv as cc
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (2, 8192, 4352), jnp.bfloat16)
    w = jax.random.uniform(ks[1], (4352, 4), jnp.float32, -0.5, 0.5)
    bias = jax.random.uniform(ks[2], (4352,), jnp.float32, -0.5, 0.5)
    r = jax.random.normal(ks[3], x.shape, jnp.float32)
    assert cc.takes(x, w)

    def run(form):
        out, vjp = jax.vjp(lambda *a: form(*a).astype(jnp.float32),
                           x, w, bias)
        return [out] + [g.astype(jnp.float32) for g in vjp(r)]
    for got, want in zip(run(cc.causal_conv_silu),
                         run(cc.causal_conv_silu_xla)):
        err = float(jnp.abs(got - want).max())
        assert err < 2e-2 * max(1.0, float(jnp.abs(want).max())), err


def _same_bits(got, want):
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert bool(jnp.all(a == b)), float(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)).max())


def test_causal_conv_reads_xbc_inside_the_in_projection():
    """The hybrid cell's offsets: 4352 channels at lane tile 32 of a
    ``[2, 8192, 8512]`` array (NaN in every other channel), through
    Mosaic: bit for bit the kernels on the slice, and the ``jnp`` form
    to rounding."""
    from paddle_tpu.ops.pallas import causal_conv as cc
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    c, at, width = 4352, 4096, 8512
    inside = (jnp.arange(width) >= at) & (jnp.arange(width) < at + c)
    held = jnp.where(inside, jax.random.normal(ks[0], (2, 8192, width),
                                               jnp.float32),
                     jnp.nan).astype(jnp.bfloat16)
    w = jax.random.uniform(ks[1], (c, 4), jnp.float32, -0.5, 0.5)
    bias = jax.random.uniform(ks[2], (c,), jnp.float32, -0.5, 0.5)
    r = jax.random.normal(ks[3], (2, 8192, c), jnp.bfloat16)
    assert cc.takes(held, w, at)

    def run(form):
        out, vjp = jax.vjp(form, held, w, bias)
        return (out,) + vjp(r)
    cut = lambda x: x[..., at:at + c]
    got = run(lambda x, w, b: cc.causal_conv_silu(x, w, b, at))
    _same_bits(got, run(lambda x, w, b: cc.causal_conv_silu(cut(x), w, b)))
    want = run(lambda x, w, b: cc.causal_conv_silu_xla(cut(x), w, b))
    for a, b in zip((got[0], cut(got[1])) + got[2:],
                    (want[0], cut(want[1])) + want[2:]):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        err = float(jnp.abs(a - b).max())
        assert err < 2e-2 * max(1.0, float(jnp.abs(b).max())), err


def test_ssd_scan_reads_x_b_c_inside_the_convolution_s_output():
    """x, B and C in ONE ``[2, 8192, 4352]`` array (x's 32 lane tiles, B
    at tile 32, C at 33), x read again by a skip: y and the gradients
    of the array (dx | dB | dC from one ``ssd_scan_bwd``, the skip's
    share added as it stores), of dt, A and D — through Mosaic, bit for
    bit the kernels on the three slices."""
    from paddle_tpu.ops import ssd_scan as op
    b, s, h, p, n, q = 2, 8192, 64, 64, 128, 256
    x, dt, A, B, C = _ssd_inputs(b, s, h, p, n, jnp.bfloat16)
    xbc = jnp.concatenate([x.reshape(b, s, h * p), B, C], -1)
    D = jnp.linspace(0.5, 1.5, h, dtype=jnp.float32)
    r = jax.random.normal(jax.random.PRNGKey(9), (b, s, h, p), jnp.bfloat16)

    def sliced(xbc, dt, A):
        x = xbc[..., :h * p]
        y = op.ssd_scan(x.reshape(b, s, h, p), dt, A,
                        xbc[..., h * p:h * p + n], xbc[..., h * p + n:], q)
        return y.reshape(b, s, h * p), x

    def run(scan):
        def f(xbc, dt, A, D):
            y, x = scan(xbc, dt, A)
            x = x.reshape(b, s, h, p)
            return (y.reshape(x.shape).astype(jnp.float32) + D[:, None]
                    * x.astype(jnp.float32)).astype(xbc.dtype)
        out, vjp = jax.vjp(f, xbc, dt, A, D)
        return (out,) + vjp(r)
    _same_bits(run(lambda *a: op.ssd_scan_xbc(*a, n, q)), run(sliced))


def test_causal_conv_channel_tile_128_against_256(monkeypatch):
    """The convolution's two kernels on the cell's 2 x 8192 x 4352 with
    a 128- and a 256-lane channel tile: the same bits, and each one's
    time a run (written to ``chiprun_out/causal_conv_tile.json``; the
    module keeps, kernel by kernel, the one that was faster)."""
    import json
    import os
    import time
    from paddle_tpu.ops.pallas import causal_conv as cc
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(ks[0], (2, 8192, 4352), jnp.bfloat16)
    w = jax.random.uniform(ks[1], (4352, 4), jnp.float32, -0.5, 0.5)
    bias = jax.random.uniform(ks[2], (4352,), jnp.float32, -0.5, 0.5)
    g = jax.random.normal(ks[3], x.shape, jnp.bfloat16)

    def ms(fn, *args):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(30):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / 30 * 1e3
    times, outs = {}, {}
    for lanes in (128, 256):
        monkeypatch.setattr(cc, "FWD_TILE", lanes)
        monkeypatch.setattr(cc, "BWD_TILE", lanes)
        fwd = jax.jit(lambda x, w, b: cc._fwd(x, w, b, 0)[0])
        bwd = jax.jit(lambda x, w, b, g: cc._bwd(0, (x, w, b), g))
        outs[lanes] = (fwd(x, w, bias),) + bwd(x, w, bias, g)
        times[lanes] = {"fwd_ms": ms(fwd, x, w, bias),
                        "bwd_ms": ms(bwd, x, w, bias, g)}
    print("causal_conv channel tile:", times)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/causal_conv_tile.json", "w") as f:
        json.dump(times, f)
    _same_bits(outs[128], outs[256])


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("regime", ["weak", "strong", "one_sign"])
def test_kda_chunk_kernels_match_the_recurrence(dtype, tol, regime):
    """``kda_chunk_fwd`` / ``kda_chunk_bwd`` through Mosaic against the
    position-by-position recurrence: outputs and the three gradients,
    seven chunks (two blocks, the second ragged), under a weak decay, a
    strong one (g to -20 a step) and keys that point the same way under
    beta near 2 (what a SiLU leaves: the regime an inverse by its series
    fails in).  Measured on a v5e: fp32 <= 3.3e-5, bf16 <= 4.1e-3 of the
    largest entry (my chip run, PR 52)."""
    from paddle_tpu.ops import kda
    heads, width, s = 2, 128, 448
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    f32 = jnp.float32
    qkv = jax.random.normal(ks[0], (1, s, 3 * heads * width), f32)
    g = -jax.random.uniform(ks[1], (1, s, heads * width), f32, 0.0,
                            20.0 if regime == "strong" else 0.2)
    g = jnp.where(jax.random.uniform(ks[3], g.shape, f32) < 0.5, g * 1e-3, g)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[2], (1, s, heads), f32)
                              + (4.0 if regime == "strong" else 0.0))
    if regime == "one_sign":
        qkv, beta = jax.nn.silu(qkv + 2.0), jnp.full_like(beta, 1.95)
    qkv = qkv.astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(9), (1, s, heads * width), f32)

    def both(fn):
        return jax.jit(lambda *a: jax.value_and_grad(
            lambda qkv, g, beta: jnp.sum(fn(qkv, g, beta, heads).astype(f32)
                                         * w), argnums=(0, 1, 2))(*a))
    assert jax.jit(lambda *a: kda.kda_chunk(*a, heads))(
        qkv, g, beta).dtype == dtype
    (_, want), (_, got) = (both(fn)(qkv, g, beta)
                           for fn in (kda.kda_recurrence, kda.kda_chunk))
    for a, b in zip(got, want):
        b = b.astype(f32)
        err = float(jnp.max(jnp.abs(a.astype(f32) - b)) / jnp.max(jnp.abs(b)))
        assert err < tol, err
