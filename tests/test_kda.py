"""``ops/kda.py``: the chunked Kimi-Delta-Attention forms — plain ``jnp``
under autodiff, and the Pallas kernels of ``ops/pallas/kda_chunk.py`` in
the interpreter — against the recurrence itself, position by position in
fp32: outputs and every gradient, under a weak and a STRONG decay (g to
-20 a step, where ``1 / Gamma`` overflows), beta near 2, and a row that
is no whole number of chunks."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
from paddle_tpu.ops import kda
from paddle_tpu.ops.pallas import kda_chunk as kernel

F32 = jnp.float32
HEADS, ROW = 2, 160             # two and a half chunks of 64
# the kernels take heads of one lane tile; the jnp form any width
WIDTH = {"kernel": 128, "xla": 32}


def data(form, strong, seed=0, dtype=F32, s=ROW):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    wide = HEADS * WIDTH[form]
    qkv = jax.random.normal(ks[0], (1, s, 3 * wide), F32).astype(dtype)
    g = -jax.random.uniform(ks[1], (1, s, wide), F32, 0.0,
                            20.0 if strong else 0.2)
    # half the channels barely decay: the two regimes side by side
    g = jnp.where(jax.random.uniform(ks[3], g.shape, F32) < 0.5, g * 1e-3, g)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[2], (1, s, HEADS), F32)
                              + (4.0 if strong else 0.0))
    return qkv, g, beta


@functools.lru_cache(maxsize=None)
def both(fn):
    """fn's output and the gradients of a weighted sum of it."""
    def run(qkv, g, beta, w):
        out, pull = jax.vjp(lambda *a: fn(*a, HEADS).astype(F32), qkv, g,
                            beta)
        return (out,) + pull(w)
    return jax.jit(run)


def gaps(got, want):
    return [float(jnp.max(jnp.abs(a.astype(F32) - b)) / jnp.max(jnp.abs(b)))
            for a, b in zip(got, want)]


@pytest.mark.parametrize("strong", [False, True], ids=["weak", "strong"])
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_the_chunked_forms_are_the_recurrence(form, strong):
    """Output, dqkv, dg and dbeta to fp32's rounding: 1e-4 of the
    largest entry (measured: <= 2e-5 under the strong decay, where a
    chunk's exponents span -1,280)."""
    qkv, g, beta = data(form, strong)
    assert kernel.takes(qkv, HEADS, kda.CHUNK) == (form == "kernel")
    w = jax.random.normal(jax.random.PRNGKey(9),
                          (1, ROW, qkv.shape[-1] // 3), F32)
    want = both(kda.kda_recurrence)(qkv, g, beta, w)
    got = both(kda.kda_chunk)(qkv, g, beta, w)
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in got)
    assert max(gaps(got, want)) < 1e-4, gaps(got, want)


def test_a_tie_of_two_running_sums_halves_no_derivative():
    """g = 0 on some channels of some positions: their running sums tie
    with the position before, and a ``minimum(x, 0)`` would hand each
    side half the derivative."""
    qkv, g, beta = data("xla", False, seed=3)
    g = jnp.where(jax.random.uniform(jax.random.PRNGKey(5), g.shape) < 0.3,
                  0.0, g)
    w = jnp.ones((1, ROW, qkv.shape[-1] // 3), F32)
    want = both(kda.kda_recurrence)(qkv, g, beta, w)
    got = both(kda.kda_chunk)(qkv, g, beta, w)
    assert max(gaps(got, want)) < 1e-4, gaps(got, want)


def test_the_kernels_in_bf16_stay_within_bf16():
    """bf16 operands: the four state-sized products round theirs to 8
    bits; 2e-2 of the largest entry (measured 6e-3), and NOT the fp32
    result (> 1e-4)."""
    qkv, g, beta = data("kernel", False, dtype=jnp.bfloat16)
    want = kda.kda_recurrence(qkv, g, beta, HEADS)
    got = jax.jit(lambda *a: kda.kda_chunk(*a, HEADS))(qkv, g, beta)
    assert got.dtype == jnp.bfloat16
    gap, = gaps([got], [want])
    assert 1e-4 < gap < 2e-2, gap


def test_padding_a_row_s_end_changes_no_earlier_output():
    qkv, g, beta = data("xla", True, s=192)
    whole = kda.kda_chunk(qkv, g, beta, HEADS)
    part = kda.kda_chunk(qkv[:, :ROW], g[:, :ROW], beta[:, :ROW], HEADS)
    np.testing.assert_allclose(np.asarray(part), np.asarray(whole[:, :ROW]),
                               rtol=0, atol=1e-6)


def test_the_norms_are_the_operator_s():
    """q and k are l2-normed a head inside: scaling a head's raw q by any
    positive number changes nothing, scaling v scales the output."""
    qkv, g, beta = data("xla", False)
    wide = qkv.shape[-1] // 3
    scale = jnp.repeat(jnp.asarray([7.0, 0.3, 2.0], F32), wide)
    a = kda.kda_chunk(qkv, g, beta, HEADS)
    b = kda.kda_chunk(qkv * scale, g, beta, HEADS)
    np.testing.assert_allclose(np.asarray(b), 2.0 * np.asarray(a),
                               rtol=1e-3, atol=1e-6)


@pytest.mark.parametrize("shape,dtype,ok", [
    ((1, 256, 3 * 4 * 128), jnp.bfloat16, True),
    ((2, 192, 3 * 2 * 128), jnp.float32, True),
    ((1, 256, 3 * 4 * 64), jnp.bfloat16, False),     # heads of half a tile
    ((1, 256, 3 * 4 * 128), jnp.float16, False)])
def test_which_shapes_the_kernels_take(shape, dtype, ok):
    heads = 4 if shape[-1] % 4 == 0 and shape[0] == 1 else 2
    assert kernel.takes(jax.ShapeDtypeStruct(shape, dtype), heads,
                        kda.CHUNK) == ok


@pytest.mark.parametrize("s,rows", [(16384, 256), (160, 192), (64, 64),
                                    (320, 256)])
def test_a_grid_step_s_block_of_chunks(s, rows):
    assert kernel.block_rows(s, kda.CHUNK) == rows


def _blocks_of(a, sub=kda.SUB):
    n = a.shape[0]
    return jnp.stack([a[i:i + sub, i:i + sub] for i in range(0, n, sub)])


@pytest.mark.parametrize("size", [0.3, 1.9], ids=["random", "one_sign"])
def test_the_inverse_of_a_unit_lower_triangle(size):
    """Entries of every sign, and entries ALL near 1.9 (keys that point
    the same way under beta near 2): the series ``sum (-a)^m`` has terms
    of 1e30 there."""
    n = 64
    a = jax.random.normal(jax.random.PRNGKey(0), (n, n), F32) * 0.3
    if size > 1:
        a = size + 0.05 * a
    a = jnp.tril(a, -1)
    inv = kda._unit_lower_inverse(a, _blocks_of(a))
    want = np.linalg.inv(np.eye(n) + np.asarray(a, np.float64))
    np.testing.assert_allclose(np.asarray(inv), want,
                               atol=2e-4 * np.abs(want).max())


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_keys_that_point_the_same_way(form):
    """q, k, v as a SiLU leaves them — mostly positive, so neighbouring
    keys are nearly parallel (cosine ~0.9) — under a slow decay and beta
    near 2: A's entries are all of one sign and near 2 (the cell's first
    chip run: an inverse by its series gave a gradient 13 % off and NaN
    after one step)."""
    qkv, g, beta = data(form, False, seed=4)
    qkv, g = jax.nn.silu(qkv + 2.0), g * 0.1
    beta = jnp.full_like(beta, 1.95)
    w = jnp.ones((1, ROW, qkv.shape[-1] // 3), F32)
    want = both(kda.kda_recurrence)(qkv, g, beta, w)
    got = both(kda.kda_chunk)(qkv, g, beta, w)
    assert max(gaps(got, want)) < 1e-4, gaps(got, want)


def test_pair_sums_form_every_exponent_as_a_difference():
    """cum down to -1,280 at the chunk's end: ``exp(-cum)`` is inf in
    fp32, the pair sums are finite and right."""
    n, k = 64, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, kk = (jax.random.normal(key, (n, k), F32) for key in ks[:2])
    cum = jnp.cumsum(-jax.random.uniform(ks[2], (n, k), F32, 0.0, 20.0), 0)
    assert not bool(jnp.all(jnp.isfinite(jnp.exp(-cum))))
    got_kk, got_qk, blocks = kda._pair_sums(q, kk, cum)
    np.testing.assert_array_equal(np.asarray(blocks),
                                  np.asarray(_blocks_of(got_kk)))
    diff = cum[:, None, :] - cum[None, :, :]
    decay = jnp.exp(jnp.minimum(diff, 0.0).astype(jnp.float64))
    low = np.tril(np.ones((n, n), bool))
    for got, x in ((got_kk, kk), (got_qk, q)):
        want = jnp.einsum("id,jd,ijd->ij", x, kk, decay)
        np.testing.assert_allclose(np.asarray(got)[low],
                                   np.asarray(want)[low], atol=1e-5)
