"""One place of the PROGRAM changed, and the toy convolution cell's
comparison with the plain reference (``tests/_toy_cell.py``,
``tests/test_lfm2_trunk.py``) must fail: the in-projection's chunks in
another order, the bias gating as well as selecting, the q / k norms
after the rotation, a fourth tap.
"""

import contextlib
import dataclasses
from unittest import mock

import pytest

import jax
import jax.numpy as jnp

from _lfm2_toy import ref, toy  # noqa: F401
from _toy_cell import BROKEN, first_step_gap, follow
from paddle_tpu.models import llama_pretrain
from paddle_tpu.ops import moe
from paddle_tpu.ops.pallas import causal_conv


_plain = causal_conv.short_conv_gated_xla


def _chunks_swapped(bcx, w):
    """The operator with its chunks read as Cg | B | X."""
    c = w.shape[0]
    return _plain(jnp.concatenate(
        [bcx[..., c:2 * c], bcx[..., :c], bcx[..., 2 * c:]], -1), w)


def _bias_gates_too(x, w_router, k, scale, rule, bias):
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)) + jax.lax.stop_gradient(bias)
    top, idx = jax.lax.top_k(s, k)
    return idx.astype(jnp.int32), \
        scale * top / (jnp.sum(top, -1, keepdims=True) + 1e-6)


_qkv = llama_pretrain._qkv


def _norms_after_the_rotation(bp, y, cfg, mesh, rotate):
    bare = {nm: v for nm, v in bp.items() if not nm.endswith("_layernorm")}
    q, k, v = _qkv(bare, y, cfg, mesh, rotate)
    norm = llama_pretrain._rms_norm
    return (norm(q, bp["q_layernorm"], cfg.rms_norm_eps),
            norm(k, bp["k_layernorm"], cfg.rms_norm_eps), v)


def _a_fourth_tap(params):
    """The taps' leaves one tap longer: a tap of the seed's size on the
    position three back."""
    blocks = dict(params["blocks"])
    for kind in ("conv_dense", "conv_moe"):
        w = blocks[kind]["conv_w"]
        blocks[kind] = dict(blocks[kind], conv_w=jnp.concatenate(
            [w[..., 1:2], w], axis=-1))
    return {"blocks": blocks}


PROGRAM = {
    "chunk_order_swapped": (mock.patch.multiple(
        causal_conv, short_conv_gated=_chunks_swapped,
        short_conv_gated_xla=_chunks_swapped), {}, None),
    "bias_gates_as_well_as_selects": (
        mock.patch.object(moe, "route", _bias_gates_too), {}, None),
    "norms_after_the_rotation": (mock.patch.object(
        llama_pretrain, "_qkv", _norms_after_the_rotation), {}, None),
    "a_fourth_tap": (contextlib.nullcontext(), dict(conv_L_cache=4),
                     _a_fourth_tap),
}


@pytest.mark.parametrize("what", sorted(PROGRAM))
def test_a_program_altered_in_one_place_fails(toy, ref, what):
    """Every alteration shows in the FIRST step's loss or gradient, so
    the second step is not followed."""
    patch, keys, leaves = PROGRAM[what]
    cfg = dataclasses.replace(toy.cfg, **keys)
    with patch:
        jax.clear_caches()      # a traced loop body is kept by its avals
        got = follow(toy, cfg, steps=1, extra_leaves=leaves)
    jax.clear_caches()
    assert first_step_gap(got, ref) > BROKEN
