"""Pallas kernel parity tests (interpret mode on CPU; the same kernels
compile natively on TPU): the kernels that are not flash attention —
RMSNorm, AdamW, rope, SwiGLU, the int8 product, the grouped products over
the experts held and the token side's sum.  Flash attention's forms:
``test_pallas_flash.py`` (dense forward and entry points),
``test_pallas_flash_backward.py``, ``test_pallas_flash_window_split.py``.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from _grouped_rows import laid_out
from _pallas_flash import _interpret_mode  # noqa: F401


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


# -- grouped products over the experts held -----------------------------------
# sizes of the groups, K, N, tiles never used behind the last group
GROUPED = {
    "uneven_zero_and_tile_crossing": ((300, 0, 257, 5), 128, 256, 3),
    "every_group_empty": ((0, 0, 0, 0), 128, 256, 3),
    "one_group_of_four_tiles": ((1024, 0, 0, 0), 128, 256, 3),
    # a change of expert EVERY step: the next panel is asked for one
    # step ahead and must be waited for, a block leaves every step
    "every_group_one_tile": ((256, 1, 200, 256, 17, 256, 3), 128, 256, 3),
    "one_expert_only": ((700,), 128, 256, 2),
    # the last expert hands over to the next column panel's first
    "two_column_panels": ((300, 0, 257, 5), 128, 2048, 3),
    "three_column_panels": ((300, 0, 257), 128, 3072, 1),
    "one_expert_three_column_panels": ((300,), 128, 3072, 2),
    "no_empty_step": ((300, 0, 257, 5), 128, 256, 0),
    "no_empty_step_two_panels_one_tile_each": ((256, 9, 256), 128, 2048, 0),
    "only_empty_tiles_behind_the_last_group": ((5, 300), 128, 256, 9),
    # grouped_mm_dw with K and N both in two panels of 1,024
    "dw_two_by_two_panels": ((300, 0, 257, 5), 2048, 2048, 3),
    "dw_two_by_two_panels_one_tile_each": ((256, 9, 256), 2048, 2048, 0),
}


def _grouped_fp32_against_a_loop(case, seed):
    """fp32 rows and weights of ``GROUPED[case]`` through the three
    products, against a loop over the experts."""
    from paddle_tpu.ops.pallas.grouped_mm import (TILE_M, grouped_mm,
                                                  grouped_mm_dw)
    sizes, K, N, spare = GROUPED[case]
    E = len(sizes)
    M, te_j, n_j, starts, tiles, valid = laid_out(sizes, spare)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (M, K), jnp.float32)
    dy = jax.random.normal(ks[1], (M, N), jnp.float32)
    w = jax.random.normal(ks[2], (E, K, N), jnp.float32)
    dy = jnp.where(valid, dy, 0)                # rows without a pair: zero
    out = grouped_mm(x, w, te_j, n_j)
    dx = grouped_mm(dy, w, te_j, n_j, trans_w=True)
    dw = grouped_mm_dw(x, dy, te_j, n_j, E)
    for e, (e0, t) in enumerate(zip(starts, tiles)):
        rows = slice(e0, e0 + t * TILE_M)
        # fp32 sums K (N) long
        np.testing.assert_allclose(out[rows], x[rows] @ w[e],
                                   atol=2e-4 * K / 128, rtol=2e-4)
        np.testing.assert_allclose(dx[rows], dy[rows] @ w[e].T,
                                   atol=2e-4 * N / 256, rtol=2e-4)
        np.testing.assert_allclose(dw[e], x[rows].T @ dy[rows],
                                   atol=2e-3, rtol=2e-4)


@pytest.mark.parametrize("case", list(GROUPED))
def test_grouped_mm_matches_a_loop_over_the_experts(case):
    """Groups of uneven, zero and tile-crossing sizes, laid out as
    ``ops/moe.plan`` lays them: each at a multiple of TILE_M, one tile
    at least — and what the kernels' own walk of the experts' blocks can
    get wrong: a change every step, one expert, more column panels than
    one, no empty step, empty steps only behind the last group."""
    _grouped_fp32_against_a_loop(case, seed=5)


@pytest.mark.parametrize("case", ["uneven_zero_and_tile_crossing",
                                  "every_group_one_tile",
                                  "two_column_panels",
                                  "dw_two_by_two_panels"])
def test_grouped_mm_is_bit_for_bit_a_plain_loop(case):
    """bf16 rows, fp32 weights: the kernels' results are those of a plain
    loop over (column panel, tile) in the same dtypes and order — the
    panel cast to bf16, fp32 accumulation, one rounding at the store; dw
    a group's tiles summed in tile order in fp32 — forward, ``trans_w``
    and dw, whenever the experts' blocks cross between HBM and VMEM."""
    from paddle_tpu.ops.pallas.grouped_mm import (TILE_M, _cols, grouped_mm,
                                                  grouped_mm_dw)
    sizes, K, N, spare = GROUPED[case]
    E, bf, f32 = len(sizes), jnp.bfloat16, jnp.float32
    M, te_j, n_j, starts, tiles, valid = laid_out(sizes, spare)
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    x = jax.random.normal(ks[0], (M, K), bf)
    dy = jnp.where(valid, jax.random.normal(ks[1], (M, N), bf), 0)
    w = jax.random.normal(ks[2], (E, K, N), f32) / K ** 0.5
    out = np.asarray(grouped_mm(x, w, te_j, n_j).astype(f32))
    dx = np.asarray(grouped_mm(dy, w, te_j, n_j, trans_w=True).astype(f32))
    dw = np.asarray(grouped_mm_dw(x, dy, te_j, n_j, E))
    assert dw.dtype == np.float32
    tn, tkT = _cols(N), _cols(K)                # forward's, trans_w's panel
    dk, dn = _cols(K, 1792), _cols(N, 1792)     # dw's block

    def dot(a, b, dims):
        return jax.lax.dot_general(a, b, (dims, ((), ())),
                                   preferred_element_type=f32)
    for e, (e0, t) in enumerate(zip(starts, tiles)):
        for i in range(t):
            rows = slice(e0 + i * TILE_M, e0 + (i + 1) * TILE_M)
            for j in range(N // tn):
                cols = slice(j * tn, (j + 1) * tn)
                want = dot(x[rows], w[e][:, cols].astype(bf),
                           ((1,), (0,))).astype(bf).astype(f32)
                np.testing.assert_array_equal(out[rows, cols], want)
            for j in range(K // tkT):
                cols = slice(j * tkT, (j + 1) * tkT)
                want = dot(dy[rows], w[e][cols].astype(bf),
                           ((1,), (1,))).astype(bf).astype(f32)
                np.testing.assert_array_equal(dx[rows, cols], want)
        for a in range(K // dk):
            for b in range(N // dn):
                ka, nb = slice(a * dk, (a + 1) * dk), slice(b * dn, (b + 1) * dn)
                want = jnp.zeros((dk, dn), f32)
                for i in range(t):
                    rows = slice(e0 + i * TILE_M, e0 + (i + 1) * TILE_M)
                    want = want + dot(x[rows, ka], dy[rows, nb],
                                      ((0,), (0,)))
                np.testing.assert_array_equal(dw[e, ka, nb], want)


@pytest.mark.parametrize("case", ["every_group_one_tile",
                                  "three_column_panels",
                                  "only_empty_tiles_behind_the_last_group",
                                  "dw_two_by_two_panels_one_tile_each"])
def test_grouped_mm_waits_for_every_copy_it_starts(case, monkeypatch):
    """The kernels move the experts' blocks themselves.  Under the TPU
    interpreter a copy lands only WHEN IT IS WAITED FOR (the plain
    interpreter copies at the start, so a product that read its panel
    before the wait, or a block that nobody waited for at the call's
    end, would pass there) and two accesses of one buffer that no wait
    orders are reported as a race."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.ops.pallas import _common
    on_wait = pltpu.InterpretParams(dma_execution_mode="on_wait",
                                    detect_races=True)
    monkeypatch.setattr(_common, "interpret", lambda: on_wait)
    _grouped_fp32_against_a_loop(case, seed=7)
    assert not interpret_pallas_call.races.races_found


# what of a call the stack's layer could get wrong: a panel's address in
# every walk (a change of expert every step, the hand-over to the next
# column panel), a call that fetches nothing
STACKED = [("uneven_zero_and_tile_crossing", "plain"),
           ("uneven_zero_and_tile_crossing", "trans_w"),
           ("uneven_zero_and_tile_crossing", "out_rows"),
           ("every_group_one_tile", "plain"),
           ("two_column_panels", "trans_w"),
           ("no_tile_in_use", "out_rows")]


@pytest.mark.parametrize("case,form", STACKED)
def test_grouped_mm_reads_a_layer_where_the_stack_holds_it(case, form):
    """``grouped_mm(x, stack, te, n, layer=l)`` on the experts of three
    layers stacked is ``grouped_mm(x, stack[l], te, n)`` BIT FOR BIT —
    the first layer, the middle one and the last, the layer data of ONE
    program — and one layer's ``[E, K, N]`` alone goes through the same
    kernel as the stack of one: three scalars, a rank-4 operand."""
    from paddle_tpu.ops.pallas.grouped_mm import grouped_mm
    sizes, K, N, spare = GROUPED.get(case, GROUPED[STACKED[0][0]])
    E, L, bf, f32 = len(sizes), 3, jnp.bfloat16, jnp.float32
    M, te, n, *_ = laid_out(sizes, spare)
    if case == "no_tile_in_use":
        n = jnp.zeros_like(n)
    trans = form == "trans_w"
    kw = dict(trans_w=trans, out_rows=M + 512 if form == "out_rows" else 0)
    ks = jax.random.split(jax.random.PRNGKey(51), 2)
    x = jax.random.normal(ks[0], (M, N if trans else K), bf)
    stack = jax.random.normal(ks[1], (L, E, K, N), f32) / K ** 0.5
    of_stack = jax.jit(lambda w, l: grouped_mm(x, w, te, n, layer=l, **kw))
    of_layer = jax.jit(lambda w: grouped_mm(x, w, te, n, **kw))
    rows = int(n[0]) * 256                      # the rows a call writes
    seen = []
    for l in range(L):
        got = np.asarray(of_stack(stack, jnp.asarray([l], jnp.int32))
                         .astype(f32))[:rows]
        np.testing.assert_array_equal(
            got, np.asarray(of_layer(stack[l]).astype(f32))[:rows])
        seen.append(got)
    assert got.shape == (rows, K if trans else N)
    if rows:
        assert not np.array_equal(seen[0], seen[1]) \
            and not np.array_equal(seen[1], seen[2])
    calls = [eqn for eqn in jax.make_jaxpr(
        lambda w: grouped_mm(x, w, te, n, **kw))(stack[0]).jaxpr.eqns
        if eqn.primitive.name == "pallas_call"]
    assert [v.aval.shape for v in calls[0].invars[2:]] \
        == [(1,), x.shape, (1, E, K, N)]
    with pytest.raises(ValueError, match="rank 4 without a layer"):
        grouped_mm(x, stack, te, n, **kw)
    with pytest.raises(ValueError, match="rank 3 with a layer"):
        grouped_mm(x, stack[0], te, n, layer=jnp.zeros((1,), jnp.int32), **kw)


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
