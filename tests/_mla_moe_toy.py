"""What the ``tests/test_mla_moe_*.py`` files share: the toy expert cell
(``tests/_toy_cell.py``) — ``make_train_step`` over
``models/hybrid_trunk.py``'s kinds ``mla_dense`` / ``mla_moe`` against
``benchmark/models/xing_mhc_moe_reference.py``, the published PATTERN (a
dense lead, then expert layers), two heads of 128 | 64 | 128, four
streams, two of eight experts held from the third on, top-2 — and the
lists of alterations, each of which stands in more than one file so
that none is a worker's wall (a file takes a slice of the sorted ids;
every id runs once).
"""

import dataclasses
import functools

import _toy_cell
from _toy_cell import (BROKEN, SOUND, altered_reference, block_gap,
                       first_step_gap, follow_reference)
from paddle_tpu.models import hybrid_trunk

toy, sound, ref = _toy_cell.fixtures("xing", "config_xing.json")

# The two-step CHANGE is held looser than loss and gradient: a pick of
# the router is a comparison, and after one step the program's and the
# reference's parameters differ in the seventh digit — enough to turn a
# near-tie of one token's scores the other way.  A mixer's alpha is
# three numbers whose gradient is a sum of terms that nearly cancel, so
# one token's flip shows in its second adafactor step (read: 3.6e-2 on
# this seed; the gradient of the FIRST step agrees to 3e-7).
SOUND_CHANGE = 6e-2

# one thing changed in the PROGRAM's configuration
PROGRAM = {
    "no_yarn": lambda c: dict(rope_scaling=None),
    "gates_not_scaled": lambda c: dict(routed_scaling_factor=1.0),
    "one_sinkhorn_round": lambda c: dict(hc_sinkhorn_iters=1),
    "other_experts_held": lambda c: dict(expert_first=3),
    "top_one": lambda c: dict(num_experts_per_tok=1),
    "clamp_at_a_half": lambda c: dict(mhc_h_res_clamp_max=0.5),
    "eps_of_the_mixers_norm": lambda c: dict(rms_norm_eps=1e-2),
}


def program_altered_fails(toy, what):
    """Every key is read inside an expert layer, so ONE layer shows it:
    the program's ``mla_moe`` block under the altered configuration
    against the reference's on the same leaves and four streams (read:
    6e-7 sound; the least 4.3e-2, ``eps_of_the_mixers_norm``; four
    seconds a case where the whole toy's first step is thirty)."""
    cfg = dataclasses.replace(toy.cfg, **PROGRAM[what](toy.cfg))
    gap = functools.partial(block_gap, toy, kind="mla_moe",
                            body=hybrid_trunk._mla_block,
                            streams=toy.cfg.hc_mult)
    assert gap(toy.cfg) < SOUND and gap(cfg) > BROKEN


# one line changed in the REFERENCE
REFERENCE = {
    "shared_expert_ignored": (
        'return routed + _swiglu(x, w["ws_gate"], w["ws_up"], '
        'w["ws_down"], mm)', "return routed"),
    "gates_over_the_held_picks_only": (
        "g = d[\"gate_scale\"] * top / (jnp.sum(top, -1, keepdims=True) "
        "+ 1e-20)",
        "g = d[\"gate_scale\"] * top / (jnp.sum(jnp.where((idx >= "
        "d[\"first\"]) & (idx < d[\"first\"] + d[\"held\"]), top, 0.0), "
        "-1, keepdims=True) + 1e-20)"),
    "rotated_key_ignored": ("kh = jnp.concatenate([kh, k_r], -1)",
                            "kh = jnp.concatenate([kh, 0.0 * k_r], -1)"),
    "h_post_not_doubled": ("h_post = 2.0 * jax.nn.sigmoid(",
                           "h_post = 1.0 * jax.nn.sigmoid("),
    "columns_not_normalised": (
        'r = r / (jnp.sum(r, 0, keepdims=True) + d["hc_eps"])', "r = r"),
    "streams_not_summed": (
        'x = x.reshape(*x.shape[:-1], -1, d["hidden"]).sum(-2)',
        'x = x.reshape(*x.shape[:-1], -1, d["hidden"])[..., 0, :]'),
}


def reference_altered_fails(toy, sound, what):
    other = follow_reference(
        toy, altered_reference("xing_mhc_moe", *REFERENCE[what]), steps=1)
    assert first_step_gap(sound, other) > BROKEN
