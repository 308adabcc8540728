"""One key of the PROGRAM's configuration changed, and the toy hybrid
cell's comparison with the plain reference (``tests/_hybrid_toy.py``,
``tests/test_hybrid_trunk.py``) must fail.
"""

import dataclasses

import pytest

from _hybrid_toy import ref, toy  # noqa: F401
from _toy_cell import BROKEN, follow, worst_gap


def _untied(params):
    return {"lm_head": params["embed"].T.copy()}


# one thing changed in the PROGRAM's configuration
PROGRAM = {
    "pattern_shifted_by_a_layer": lambda c, full: dict(
        layer_types=tuple(full[1:1 + c.num_hidden_layers])),
    "residual_multiplier_dropped": lambda c, full: dict(
        residual_multiplier=1.0),
    "score_scale_one_over_sqrt_d": lambda c, full: dict(
        attention_multiplier=None),
    "rope_left_on": lambda c, full: dict(position_embedding_type="rope"),
    "embedding_multiplier_dropped": lambda c, full: dict(
        embedding_multiplier=1.0),
    "logits_not_divided": lambda c, full: dict(logits_scaling=1.0),
    "tie_broken": lambda c, full: dict(tie_word_embeddings=False),
}


@pytest.mark.parametrize("what", sorted(PROGRAM))
def test_a_program_altered_in_one_place_fails(toy, ref, what):
    change = PROGRAM[what](toy.cfg, toy.conf["layer_types"])
    cfg = dataclasses.replace(toy.cfg, **change)
    prog = follow(toy, cfg,
                  extra_leaves=_untied if what == "tie_broken" else None)
    assert worst_gap(prog, ref) > BROKEN
