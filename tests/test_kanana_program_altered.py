"""Six one-place alterations of the PROGRAM's plain latent-attention
block (``models/hybrid_trunk.py``'s ``mla_moe`` on one stream, a direct
query) that must leave the tolerance a sound block keeps against
``benchmark/models/kanana_mla_moe_reference.py``: each sound < 1e-5 AND
altered > 1e-3 (``_toy_cell.block_gap``: one layer of the seed's leaves,
one seeded input)."""

import dataclasses

import pytest

import jax.numpy as jnp

from _kanana_toy import toy  # noqa: F401
from _toy_cell import BROKEN, SOUND, block_gap
from paddle_tpu.models import hybrid_trunk, llama_pretrain


def _pairs_read_as_neighbours(x, cos, sin):
    """The rotation on the pairs (2i, 2i + 1): the published interleaved
    layout read without its permutation."""
    f32 = x.astype(jnp.float32)
    if x.ndim == 4:
        cos, sin = cos[:, None], sin[:, None]
    a, b = f32[..., 0::2], f32[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape).astype(x.dtype)


def _one_shared_expert(bp, x, cfg):
    f = cfg.moe_intermediate_size
    return hybrid_trunk._mla_block(
        dict(bp, ws_gate=bp["ws_gate"][:, :f], ws_up=bp["ws_up"][:, :f],
             ws_down=bp["ws_down"][:f]), x, cfg)


def _kv_norm_left_out(monkeypatch, toy):
    real = llama_pretrain._rms_norm
    monkeypatch.setattr(
        llama_pretrain, "_rms_norm", lambda x, w, eps: x
        if w.shape == (toy.cfg.kv_lora_rank,) else real(x, w, eps))


# what: (keys of the configuration stated otherwise, the block's body,
# a patch of the module)
ALTERED = {
    "score_scale_of_the_nope_dims_alone": (
        {}, None, lambda mp, toy: mp.setattr(
            hybrid_trunk, "mla_score_scale",
            lambda cfg: cfg.qk_nope_head_dim ** -0.5)),
    "interleaved_pairs_read_as_halves": (
        {}, None, lambda mp, toy: mp.setattr(
            hybrid_trunk, "_rotate_half", _pairs_read_as_neighbours)),
    "routed_scaling_factor_dropped": (
        dict(routed_scaling_factor=1.0), None, None),
    "one_shared_expert_in_place_of_two": ({}, _one_shared_expert, None),
    "one_pick_fewer": (dict(num_experts_per_tok=2), None, None),
    "kv_norm_left_out": ({}, None, _kv_norm_left_out),
}


@pytest.mark.parametrize("what", sorted(ALTERED))
def test_a_program_altered_in_one_place_fails(toy, what, monkeypatch):
    change, body, patch = ALTERED[what]
    assert toy.cfg.kv_lora_rank != toy.cfg.hidden_size  # the norms differ
    gap = lambda cfg, body: block_gap(toy, cfg, "mla_moe", body)
    assert gap(toy.cfg, hybrid_trunk._mla_block) < SOUND
    if patch:
        patch(monkeypatch, toy)
    assert gap(dataclasses.replace(toy.cfg, **change),
               body or hybrid_trunk._mla_block) > BROKEN
