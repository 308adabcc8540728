"""The residual streams' mixers on the kernels of
``ops/pallas/hc_mix.py`` (the interpreter, toy widths the kernels take,
n = 2 and n = 4): each kernel against the plain formula it stands for,
and the whole sublayer — forward and every cotangent: dX, phi's, alpha's
and b's, and through F's weight, so dy and dh too — against (a) XLA's
form of the same sublayer, which ``_hc_sublayer`` keeps for the shapes
``takes`` refuses, and (b) the mixer of the plain reference
``benchmark/models/xing_mhc_moe_reference.py`` in float32.  Then one
thing is changed at a time in the plain form, and the comparison must
fail."""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (x64 before any array)
from benchmark import reference
from benchmark.models import xing_mhc_moe_reference as plain_model
from paddle_tpu.models import hybrid_trunk
from paddle_tpu.ops.pallas import hc_mix

F32, BF16, HI = jnp.float32, jnp.bfloat16, jax.lax.Precision.HIGHEST
EPS, HC_EPS, ITERS, LO, HI_CLAMP = 1e-6, 1e-6, 20, -30.0, 30.0
ROWS, SEQ = 2, 48                  # 96 tokens: tiles of 32, three a pass
SOUND, BROKEN = 2e-5, 1e-3


def cfg_of(n, c):
    return types.SimpleNamespace(
        hc_mult=n, hidden_size=c, rms_norm_eps=EPS, hc_sinkhorn_iters=ITERS,
        hc_eps=HC_EPS, mhc_h_res_clamp_min=LO, mhc_h_res_clamp_max=HI_CLAMP)


def leaves(n, c, dtype=F32, seed=0):
    """Streams, cotangent and one mixer's leaves (with F's weight and
    its norm's), seeded; alpha away from 0 so that every map moves."""
    k = n * n + 2 * n
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    w = {"hc1_phi": jax.random.normal(ks[0], (n * c, k), F32) * 0.05,
         "hc1_alpha": jnp.array([0.7, -0.4, 0.9], F32),
         "hc1_b": jax.random.normal(ks[1], (k,), F32) * 0.3,
         "ln1": 1.0 + 0.1 * jax.random.normal(ks[2], (c,), F32),
         "w_f": jax.random.normal(ks[3], (c, c), F32) * 0.1}
    x = jax.random.normal(ks[4], (ROWS, SEQ, n * c), F32).astype(dtype)
    g = jax.random.normal(ks[5], (ROWS, SEQ, n * c), F32).astype(dtype)
    return w, x, g


def f_of(w):
    """F of the sublayer: a norm and a product with a weight."""
    return lambda h: jnp.tanh(jnp.matmul(
        reference.rms_norm(h.astype(F32), w["ln1"], EPS), w["w_f"],
        precision=HI)).astype(h.dtype)


def program(w, x, n, kernels=True):
    """``_hc_sublayer`` as the trunk calls it; ``kernels=False``: the
    form for the shapes ``takes`` refuses."""
    real = hc_mix.takes
    hc_mix.takes = lambda *a: kernels and real(*a)
    try:
        return hybrid_trunk._hc_sublayer(
            w, "hc1", x, f_of(w), cfg_of(n, x.shape[-1] // n))
    finally:
        hc_mix.takes = real


def the_reference(w, x, n):
    dims = dict(n=n, eps=EPS, iters=ITERS, hc_eps=HC_EPS, lo=LO,
                hi=HI_CLAMP)
    mm = functools.partial(reference.matmul, precision="f32")
    return plain_model._sublayer(
        x, w, "hc1", "ln1",
        lambda v: jnp.tanh(jnp.matmul(v, w["w_f"], precision=HI)), dims, mm)


def maps_of(m, w, n, iters=ITERS):
    """m [T, n^2 + 2 n] -> H_pre [T, n], H_post [T, n], H_res [T, j, i]."""
    alpha, b = w["hc1_alpha"], w["hc1_b"]
    h_pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + b[n:2 * n])
    r = jnp.exp(jnp.clip(alpha[2] * m[:, 2 * n:] + b[2 * n:], LO, HI_CLAMP))
    r = r.reshape(-1, n, n)
    for _ in range(iters):
        r = r / (jnp.sum(r, 2, keepdims=True) + HC_EPS)             # rows
        r = r / (jnp.sum(r, 1, keepdims=True) + HC_EPS)             # columns
    return h_pre, h_post, r


def m_of(x, w, alter=""):
    """(x . phi) / rms(x) on x [T, n C], and 1 / rms."""
    xf = x.astype(F32)
    rstd = jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + EPS)
    if alter == "norm_not_differentiated":
        rstd = jax.lax.stop_gradient(rstd)
    phi = w["hc1_phi"].astype(x.dtype)
    dot = functools.partial(jnp.dot, preferred_element_type=F32, precision=HI)
    if alter == "rsqrt_before_the_sum":
        return dot((xf * rstd).astype(x.dtype), phi), rstd
    return dot(x, phi) * rstd, rstd


def by_hand(w, x, n, alter=""):
    """The sublayer once more, plainly, with one place to alter."""
    shape, x = x.shape, x.reshape(-1, x.shape[-1])
    t, c = x.shape[0], x.shape[1] // n
    h_pre, h_post, r = maps_of(
        m_of(x, w, alter)[0], w, n, 1 if alter == "one_sinkhorn_round"
        else ITERS)
    if alter == "swapped_h_res_index":
        r = r.transpose(0, 2, 1)
    streams = x.astype(F32).reshape(t, n, c)
    h = jnp.einsum("ti,tic->tc", h_pre, streams, precision=HI)
    y = f_of(w)(h.astype(x.dtype)).astype(F32)
    out = jnp.einsum("tji,tic->tjc", r, streams, precision=HI) \
        + h_post[:, :, None] * y[:, None, :]
    return out.reshape(shape).astype(x.dtype)


def gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def worst(a, b):
    """Worst leaf of two trees of the same structure."""
    return max(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(gap, a, b)))


def everything(fn, w, x, g):
    """The output and every cotangent of ``fn(w, x)`` pulled from g, as
    ONE jitted program: op by op a form is hundreds of dispatches, each
    its own small compile (under six workers' load 23-40 s a case
    where this is 5-8)."""
    def both(w, x, g):
        out, pull = jax.vjp(fn, w, x)
        return out, pull(g.astype(out.dtype))
    return jax.jit(both)(w, x, g)


@functools.lru_cache(maxsize=None)
def of(form, n, c, dtype=F32):
    """``everything`` of one form of the sublayer on the seeded leaves:
    ``kernels``, ``xla`` (the program's two), ``reference``, or the
    plain form ``by_hand`` with the alteration named after the colon."""
    w, x, g = leaves(n, c, dtype)
    if form == "reference":
        x, g = x.astype(F32), g.astype(F32)
    fn = {"kernels": lambda w, x: program(w, x, n),
          "xla": lambda w, x: program(w, x, n, kernels=False),
          "reference": lambda w, x: the_reference(w, x, n)}.get(
        form, lambda w, x: by_hand(w, x, n, form.partition(":")[2]))
    return everything(fn, w, x, g)


# -- the cases ---------------------------------------------------------------
def sublayer_against_xla_s_form(n, c):
    assert hc_mix.takes(leaves(n, c)[1], n, c)
    assert worst(of("kernels", n, c), of("xla", n, c)) < SOUND


def sublayer_against_the_reference(n, c):
    assert worst(of("kernels", n, c), of("reference", n, c)) < SOUND


def sublayer_in_the_compute_dtype(n, c):
    """bf16 streams: the kernels sum in fp32 and round once, XLA's form
    rounds each path of dX on its own — the kernels lie closer to the
    float32 reference in dX, and as close elsewhere."""
    exact = of("reference", n, c, BF16)
    k, xla = of("kernels", n, c, BF16), of("xla", n, c, BF16)
    assert gap(k[0], exact[0]) < 5e-3 and gap(k[1][1], exact[1][1]) < 5e-3
    assert gap(k[1][1], exact[1][1]) <= gap(xla[1][1], exact[1][1])
    for leaf in exact[1][0]:
        assert gap(k[1][0][leaf], exact[1][0][leaf]) < 2e-2, leaf


def a_refused_shape_runs_xla_s_form(n, c):
    """A stream that is no whole number of lane tiles: no kernel is in
    the program, and the values are the XLA form's own."""
    w, x, g = leaves(n, c + 8)
    assert not hc_mix.takes(x, n, c + 8)
    fn = lambda w, x: program(w, x, n)
    assert "pallas_call" not in str(jax.make_jaxpr(fn)(w, x))
    got = everything(fn, w, x, g)
    want = everything(lambda w, x: by_hand(w, x, n), w, x, g)
    assert worst(got, want) < SOUND
    # tokens that are no whole number of row blocks, likewise
    assert not hc_mix.takes(x[:, :SEQ - 1, :n * c], n, c)


def kernel_pre_fwd(n, c, dtype=F32):
    w, x, _ = leaves(n, c, dtype)
    x = x.reshape(-1, n * c)
    k = n * n + 2 * n
    h, mr = hc_mix.hc_pre_fwd(x, w["hc1_phi"], w["hc1_alpha"][0], w["hc1_b"],
                              n, EPS)
    m, rstd = m_of(x, w)
    h_pre = maps_of(m, w, n)[0]
    want = jnp.einsum("ti,tic->tc", h_pre,
                      x.astype(F32).reshape(-1, n, c), precision=HI)
    assert h.dtype == x.dtype and mr.shape == (x.shape[0], hc_mix.LANES)
    assert gap(mr[:, :k], m) < SOUND and gap(mr[:, k:k + 1], rstd) < SOUND
    assert not np.any(np.asarray(mr[:, k + 1:]))
    assert gap(h, want.astype(dtype)) < (SOUND if dtype == F32 else 5e-3)
    return mr, m


def kernel_post(n, c):
    """``hc_post_fwd``, and ``hc_post_bwd`` against autodiff of the
    plain form with respect to y and the maps."""
    w, x, g = leaves(n, c)
    x, g = x.reshape(-1, n * c), g.reshape(-1, n * c)
    t = x.shape[0]
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    y = jax.random.normal(ks[0], (t, c), F32)
    maps = jnp.pad(jax.random.uniform(ks[1], (t, n * n + n), F32),
                   ((0, 0), (0, hc_mix.LANES - n * n - n)))

    def plain(y, maps):
        r = maps[:, :n * n].reshape(t, n, n)
        out = jnp.einsum("tji,tic->tjc", r, x.reshape(t, n, c), precision=HI)
        return (out + maps[:, n * n:n * n + n, None] * y[:, None]).reshape(
            t, n * c)
    want, pull = jax.vjp(plain, y, maps)
    assert gap(hc_mix.hc_post_fwd(x, y, maps, n), want) < SOUND
    dy, dmaps = hc_mix.hc_post_bwd(g, x, y, maps, n)
    assert worst((dy, dmaps), pull(g)) < SOUND


def kernel_pre_bwd(n, c):
    """``hc_pre_bwd`` against autodiff of the plain (h, m), with dX'
    taken through H_res beside it."""
    w, x, g = leaves(n, c)
    x, g = x.reshape(-1, n * c), g.reshape(-1, n * c)
    t, k = x.shape[0], n * n + 2 * n
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    dh = jax.random.normal(ks[0], (t, c), F32)
    dm = jax.random.normal(ks[1], (t, k), F32).at[:, :n].set(0.0)
    maps = jnp.pad(jax.random.uniform(ks[2], (t, n * n + n), F32),
                   ((0, 0), (0, hc_mix.LANES - n * n - n)))
    pad = lambda a: jnp.pad(a, ((0, 0), (0, hc_mix.LANES - a.shape[1])))

    def plain(x, phi, alpha0, b_pre):
        ww = dict(w, hc1_phi=phi)
        m, _ = m_of(x, ww)
        h_pre = jax.nn.sigmoid(alpha0 * m[:, :n] + b_pre)
        return jnp.einsum("ti,tic->tc", h_pre, x.reshape(t, n, c),
                          precision=HI), m
    (_, m), pull = jax.vjp(plain, x, w["hc1_phi"], w["hc1_alpha"][0],
                           w["hc1_b"][:n])
    dx, dphi, dalpha0, db = pull((dh, dm))
    r = maps[:, :n * n].reshape(t, n, n)
    dx = dx + jnp.einsum("tji,tjc->tic", r, g.reshape(t, n, c),
                         precision=HI).reshape(t, n * c)
    _, mr = hc_mix.hc_pre_fwd(x, w["hc1_phi"], w["hc1_alpha"][0], w["hc1_b"],
                              n, EPS)
    got_dx, dz, dphit = hc_mix.hc_pre_bwd(
        g, x, dh, mr, pad(dm), maps, w["hc1_phi"], w["hc1_alpha"][0],
        w["hc1_b"], n)
    assert not np.any(np.asarray(dz[:, n:]))
    got = (got_dx, dphit[:k].T, jnp.sum(dz[:, :n] * m[:, :n]),
           jnp.sum(dz[:, :n], axis=0))
    assert worst(got, (dx, dphi, dalpha0, db)) < SOUND


def altered(alter, dtype=F32):
    """The plain form with one thing changed is told from the kernels;
    unchanged it is not."""
    def case(n, c):
        w, x, g = leaves(n, c, dtype)
        sound = SOUND if dtype == F32 else 2e-2
        if alter == "rsqrt_before_the_sum":
            # what the order decides is WHERE the compute dtype rounds:
            # read where it shows, in m itself
            mr, m = kernel_pre_fwd(n, c, dtype)
            k = n * n + 2 * n
            moved = m_of(x.reshape(-1, n * c), w, alter)[0]
            assert gap(mr[:, :k], m) < SOUND < 20 * SOUND \
                < gap(mr[:, :k], moved)
            return
        got = of("kernels", n, c, dtype)
        assert worst(got, of("by_hand:", n, c, dtype)) < sound
        assert worst(got, of("by_hand:" + alter, n, c, dtype)) > BROKEN
    return case


CASES = {
    "sublayer, against XLA's form": sublayer_against_xla_s_form,
    "sublayer, against the reference": sublayer_against_the_reference,
    "sublayer, bf16 streams": sublayer_in_the_compute_dtype,
    "a refused shape runs XLA's form": a_refused_shape_runs_xla_s_form,
    "hc_pre_fwd": kernel_pre_fwd,
    "hc_pre_fwd, bf16": functools.partial(kernel_pre_fwd, dtype=BF16),
    "hc_post_fwd, hc_post_bwd": kernel_post,
    "hc_pre_bwd": kernel_pre_bwd,
    "altered: a swapped H_res index": altered("swapped_h_res_index"),
    "altered: the rsqrt before the product's sum":
        altered("rsqrt_before_the_sum", BF16),
    "altered: no norm-correction term": altered("norm_not_differentiated"),
    "altered: one Sinkhorn round": altered("one_sinkhorn_round"),
}


@pytest.mark.parametrize("n,c", [(2, 256), (4, 128)])
@pytest.mark.parametrize("case", list(CASES))
def test_hc_mix(case, n, c):
    CASES[case](n, c)


def test_the_halves_hand_dx_through_unmixed():
    """The contract of the two halves: ``_hc_post``'s backward hands dX'
    to its x as it came (``_hc_pre``'s takes it through H_res), so the
    backward writes the streams once, as dX."""
    n, c = 4, 128
    _, x, g = leaves(n, c)
    x, g = x.reshape(-1, n * c), g.reshape(-1, n * c)
    maps = jnp.full((x.shape[0], hc_mix.LANES), 0.25, F32)
    y = jnp.ones((x.shape[0], c), F32)
    _, pull = jax.vjp(lambda x, y, m: hybrid_trunk._hc_post(x, y, m, n),
                      x, y, maps)
    assert np.array_equal(np.asarray(pull(g)[0]), np.asarray(g))


def test_takes_is_a_matter_of_shape():
    z = lambda *s, dtype=BF16: jax.ShapeDtypeStruct(s, dtype)
    assert hc_mix.takes(z(2, 8192, 4 * 3584), 4, 3584)      # the expert cell
    assert hc_mix.takes(z(16, 2 * 128), 2, 128)
    assert not hc_mix.takes(z(2, 8192, 4 * 3584), 4, 3600)  # not n C wide
    assert not hc_mix.takes(z(2, 8192, 4 * 200), 4, 200)    # lane tiles
    assert not hc_mix.takes(z(2, 15, 4 * 128), 4, 128)      # row blocks
    assert not hc_mix.takes(z(64, 12 * 128), 12, 128)       # 168 maps
    assert not hc_mix.takes(z(64, 4 * 65536), 4, 65536)     # VMEM
