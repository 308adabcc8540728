"""One key of the PROGRAM's configuration changed, and the comparison
with the plain reference must fail: an attention layer's with the
reference's block where the layer reads the key
(``_toy_cell.block_gap``), the toy hybrid cell's two steps where the
pattern, the table or the head does (``tests/_hybrid_toy.py``,
``tests/test_hybrid_trunk.py``).
"""

import dataclasses
import functools

import pytest

from _hybrid_toy import ref, toy  # noqa: F401
from _toy_cell import BROKEN, SOUND, block_gap, follow, worst_gap
from paddle_tpu.models import llama_pretrain


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


# the keys an attention layer reads: ONE layer against the reference's
# block shows them (read: 4e-8 sound; 0.68, 0.030 and 0.0075 altered)
IN_A_BLOCK = ("residual_multiplier_dropped", "rope_left_on",
              "score_scale_one_over_sqrt_d")


@pytest.mark.parametrize("what", sorted(PROGRAM))
def test_a_program_altered_in_one_place_fails(toy, ref, what):
    change = PROGRAM[what](toy.cfg, toy.conf["layer_types"])
    cfg = dataclasses.replace(toy.cfg, **change)
    if what in IN_A_BLOCK:
        gap = functools.partial(block_gap, toy, kind="attention",
                                body=llama_pretrain._block_forward)
        assert gap(toy.cfg) < SOUND and gap(cfg) > BROKEN
        return
    # the pattern, the table, the head: the whole toy's two steps
    prog = follow(toy, cfg,
                  extra_leaves=_untied if what == "tie_broken" else None)
    assert worst_gap(prog, ref) > BROKEN
