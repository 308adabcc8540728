"""Four one-place alterations of the PROGRAM's delta-rule and gated
attention layers (``models/hybrid_trunk.py``'s kinds ``kda_moe`` /
``gqa_gated_moe``) that must leave the tolerance a sound block keeps
against ``benchmark/models/solar_kda_moe_reference.py``: beta not
doubled, a decay a head and not a channel, the l2 norm skipped, the
attention's gate left out."""

import jax.numpy as jnp

from _solar_toy import BLOCK, F32, layer_of, toy, x_of  # noqa: F401
from benchmark.models import solar_kda_moe_reference as blk
from paddle_tpu.models import hybrid_trunk, llama_pretrain
from paddle_tpu.ops import kda


def _kda_output(toy):
    """The program's second delta-rule layer."""
    return hybrid_trunk._kda_block(layer_of(toy, "kda_moe", 1), x_of(toy, 3),
                                   toy.cfg)


def _reference_output(toy, kind, block, layer):
    """The reference's output on the same layer and input, and its
    largest entry."""
    want, _ = block(x_of(toy, 3), layer_of(toy, kind, layer),
                    blk.dims_of(toy.conf))
    return want, float(jnp.max(jnp.abs(want)))


def test_beta_not_doubled_leaves_the_tolerance(toy, monkeypatch):
    want, scale = _reference_output(toy, "kda_moe", blk.kda_block, 1)
    sound = float(jnp.max(jnp.abs(_kda_output(toy) - want)))
    real = kda.kda_chunk
    monkeypatch.setattr(kda, "kda_chunk", lambda qkv, g, beta, *rest: real(
        qkv, g, beta / 2, *rest))
    broken = float(jnp.max(jnp.abs(_kda_output(toy) - want)))
    assert sound < BLOCK * scale and broken > 100 * BLOCK * scale


def test_a_decay_a_head_and_not_a_channel_leaves_the_tolerance(toy,
                                                               monkeypatch):
    """Every key channel of a head decaying by the head's MEAN log decay
    (the scalar gate of the delta rule this one is no sibling of)."""
    want, scale = _reference_output(toy, "kda_moe", blk.kda_block, 1)
    real = kda.kda_chunk

    def a_head(qkv, g, beta, heads, *rest):
        b, s, wide = g.shape
        mean = jnp.mean(g.reshape(b, s, heads, -1), -1, keepdims=True)
        return real(qkv, jnp.broadcast_to(
            mean, (b, s, heads, wide // heads)).reshape(g.shape), beta,
            heads, *rest)
    monkeypatch.setattr(kda, "kda_chunk", a_head)
    broken = float(jnp.max(jnp.abs(_kda_output(toy) - want)))
    assert broken > 100 * BLOCK * scale


def test_the_l2_norm_skipped_leaves_the_tolerance(toy, monkeypatch):
    want, scale = _reference_output(toy, "kda_moe", blk.kda_block, 1)
    monkeypatch.setattr(kda, "l2norm", lambda x, eps=0.0: x.astype(F32))
    broken = float(jnp.max(jnp.abs(_kda_output(toy) - want)))
    # unnormed keys of norm ~11 make A's entries ~100 and its inverse
    # overflow: not a number is outside every tolerance too
    assert not broken < 100 * BLOCK * scale


def test_the_attention_s_gate_left_out_leaves_the_tolerance(toy,
                                                            monkeypatch):
    want, scale = _reference_output(toy, "gqa_gated_moe", blk.attention_block, 0)
    bp, x = layer_of(toy, "gqa_gated_moe"), x_of(toy, 3)
    sound = float(jnp.max(jnp.abs(
        hybrid_trunk._kda_block(bp, x, toy.cfg) - want)))
    # left out: twice the attention under sigmoid(y . 0), a half
    real = llama_pretrain._attention
    monkeypatch.setattr(llama_pretrain, "_attention",
                        lambda *a, **k: 2 * real(*a, **k))
    bp["wg"] = jnp.zeros_like(bp["wg"])
    broken = float(jnp.max(jnp.abs(
        hybrid_trunk._kda_block(bp, x, toy.cfg) - want)))
    assert sound < BLOCK * scale and broken > 100 * BLOCK * scale
