"""The windowed form and the form whose scores come from two operand
pairs (latent attention), forward and gradients (interpret mode on the
CPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _pallas_flash import (_concatenated_attention, _flash_grads,  # noqa: F401
                           _flash_inputs, _flash_module, _interpret_mode,
                           _masked_attention, _split_grads, _split_inputs,
                           _two_kernels)


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


# -- scores from two operand pairs (latent attention) ------------------------
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
    run — AND against the two kernels on the same inputs (the budgets set
    to 0 bytes: the module constants, no flag)."""
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
    _two_kernels(monkeypatch, fa)
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
    reaches the kernels, one pass (2) or two kernels (3: the budgets set
    to 0 bytes): they take the five arrays as they are."""
    fa = _flash_module()
    if kernels == 3:
        _two_kernels(monkeypatch, fa)
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
