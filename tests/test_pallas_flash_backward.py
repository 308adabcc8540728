"""The flash backward's three forms (interpret mode on the CPU):
key-major in one pass, query-major in one pass, the two kernels — each
against the others and the XLA attention, the form following the shapes
and the two VMEM rules (module constants, no flag), for the dense and
windowed entries and for the split one."""

import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _pallas_flash import (_concatenated_attention, _flash_grads,  # noqa: F401
                           _flash_inputs, _flash_module, _interpret_mode,
                           _split_grads, _split_inputs, _two_kernels)


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


@pytest.mark.parametrize("case,b,s,kernels", [
    # (a) the 8k expert cell: a head's fp32 dQ is 4 MiB, the first budget
    ("a", 2, 8192, ["flash_fwd", "flash_bwd_dkv"]),
    # (b) the plain-MLA cell: 8 MiB of dQ; dQ and dQ2, a lane tile each a
    # row, are the second budget's 16 MiB exactly — the same key-major pass
    ("b", 1, 16384, ["flash_fwd", "flash_bwd_dkv"]),
    # (c) a row of 32,768: 32 MiB of dQ and dQ2 — the two kernels
    ("c", 1, 32768, ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
])
def test_split_backward_form_follows_the_shapes(case, b, s, kernels):
    """The rule of ``_flash_bwd_vjp`` for the SPLIT form at the two
    latent-attention cells' shapes and past them (32 heads of 128 | 64 |
    128, a group of one), by the kernels' names in the traced program
    (nothing runs): the module's constants as they are, no flag."""
    fa = _flash_module()
    wide, rot, key2 = (jax.ShapeDtypeStruct(shape, jnp.bfloat16) for shape
                       in ((b, s, 32, 128), (b, s, 32, 64), (b, s, 64)))
    assert case == ("a" if s * 128 * 4 <= fa.ONE_PASS_DQ_BYTES
                    else "b" if s * (128 + 128) * 4 <= fa.ONE_PASS_DKV_BYTES
                    else "c")
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: fa.flash_attention_split(
            *a, 0.07).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4)))(wide, rot, wide, key2, wide))
    assert re.findall(r"(?<!name\[)\bname=(flash_\w+)", text) == kernels


@pytest.mark.parametrize("form", ["dense", "split"])
@pytest.mark.parametrize("fits", ["dq", "dkv", "neither"])
def test_flash_backward_pass_count_follows_the_vmem_rule(monkeypatch, fits,
                                                         form):
    """Key-major in one pass where a group's fp32 dQ, group*S*d*4 B, is
    within ``ONE_PASS_DQ_BYTES``; a byte past it a form still runs one
    pass where what waits across ITS pass's grid steps is within
    ``ONE_PASS_DKV_BYTES`` — the dense form query-major, by a KV head's
    fp32 dK and dV, 2*S*lanes(d)*4 B; the split form key-major as before,
    by the group's fp32 dQ and dQ2, group*S*(lanes(d) + lanes(d2))*4 B —
    and a byte past that the two kernels run, with the same gradients."""
    fa = _flash_module()
    if form == "dense":
        h, nkv, d, s = 4, 2, 128, 256
        args = _flash_inputs(7, 2, s, h, nkv, d)
        run = lambda fn: _flash_grads(fn, *args, True)
        flash, plain = fa.flash_attention, fa._xla_sdpa
        need_dkv = 2 * s * d * 4
    else:
        h, nkv, d, s = 2, 2, 128, 256
        *args, co = _split_inputs(2, s, h, d, 64)
        run = lambda fn: _split_grads(fn, *args, co, 0.137)
        flash, plain = fa.flash_attention_split, _concatenated_attention
        need_dkv = (h // nkv) * s * (d + 128) * 4   # 64 lanes fill a tile
    need = (h // nkv) * s * d * 4
    monkeypatch.setattr(fa, "ONE_PASS_DQ_BYTES",
                        need if fits == "dq" else need - 1)
    monkeypatch.setattr(fa, "ONE_PASS_DKV_BYTES",
                        need_dkv if fits == "dkv" else need_dkv - 1)
    got, kernels = run(flash)
    assert kernels == ["flash_fwd"] + {
        "dq": ["flash_bwd_dkv"],
        "dkv": ["flash_bwd_dq" if form == "dense" else "flash_bwd_dkv"],
        "neither": ["flash_bwd_dq", "flash_bwd_dkv"]}[fits]
    want, _ = run(plain)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(a, b_, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("s,h,dtype", [
    (1024, 2, jnp.float32),     # two 512-row blocks: the off-diagonal loop
    (192, 3, jnp.float32),      # three 64-row blocks, three heads
    (1536, 1, jnp.bfloat16)])   # three 512-row blocks, the cell's dtype
def test_split_backward_in_one_pass_past_the_first_budget(monkeypatch, s, h,
                                                          dtype):
    """The split form past rule (a) — the first budget at 0 bytes, as a
    row of 16,384 is past it — still runs ``flash_bwd_dkv`` ALONE where
    the group's fp32 dQ and dQ2 wait within ``ONE_PASS_DKV_BYTES``: the
    key-major body is ``flash_bwd_dkv``'s own, so dk, dk2 and dv are the
    two kernels' BIT FOR BIT; dq and dq2 are the same terms from a
    product turned round (dS^T contracted on its rows: the same bits on
    the chip — ``tools/time_flash_bwd.py`` prints how far apart — and a
    re-ordered sum in the CPU's products); all five against autodiff of
    the concatenated attention."""
    fa = _flash_module()
    *args, co = _split_inputs(2, s, h, 128, 64, dtype)
    co, scale = co.astype(jnp.float32), 0.137
    monkeypatch.setattr(fa, "ONE_PASS_DQ_BYTES", 0)
    assert s * (128 + 128) * 4 <= fa.ONE_PASS_DKV_BYTES
    one, kernels = _split_grads(fa.flash_attention_split, *args, co, scale)
    assert kernels == ["flash_fwd", "flash_bwd_dkv"]
    monkeypatch.setattr(fa, "ONE_PASS_DKV_BYTES", s * (128 + 128) * 4 - 1)
    two, kernels = _split_grads(fa.flash_attention_split, *args, co, scale)
    assert kernels == ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]
    want, _ = _split_grads(_concatenated_attention,
                           *(a.astype(jnp.float32) for a in args), co, scale)
    tol = 2e-4 if dtype == jnp.float32 else 6e-2
    for name, a, b_, c in zip(("dq", "dq2", "dk", "dk2", "dv"), one, two,
                              want):
        if name in ("dk", "dk2", "dv"):
            np.testing.assert_array_equal(a, b_, err_msg=name)
        elif dtype == jnp.float32:
            np.testing.assert_allclose(a, b_, atol=2e-5, rtol=2e-5,
                                       err_msg=name)
        else:
            assert float(jnp.abs(a - b_).max()) <= \
                2 ** -7 * float(jnp.abs(b_).max()), name
        np.testing.assert_allclose(a, c, atol=tol, rtol=tol, err_msg=name)
