"""One key of the PROGRAM's configuration changed, and a window layer's
comparison with the plain reference's block (``_toy_cell.block_gap``;
the toy: ``tests/_smallthinker_toy.py``) must fail.
"""

import dataclasses
import functools

import pytest

from _smallthinker_toy import toy  # noqa: F401
from _toy_cell import BROKEN, SOUND, block_gap
from paddle_tpu.models import hybrid_trunk

# one thing changed in the PROGRAM's configuration
PROGRAM = {
    # 63 divides into no block: the composite's mask, one key short
    "window_one_key_short": lambda c: dict(sliding_window_size=63),
    "other_experts_held": lambda c: dict(expert_first=3),
    "top_two": lambda c: dict(num_experts_per_tok=2),
}


@pytest.mark.parametrize("what", sorted(PROGRAM))
def test_a_program_altered_in_one_place_fails(toy, what):
    """Every key is read inside a window layer, so ONE layer shows it
    (read: 1e-7 sound; the least 2.4e-2, the window one key short)."""
    cfg = dataclasses.replace(toy.cfg, **PROGRAM[what](toy.cfg))
    gap = functools.partial(
        block_gap, toy, kind="gqa_moe_window",
        body=functools.partial(hybrid_trunk._gqa_moe_block, window=True))
    assert gap(toy.cfg) < SOUND and gap(cfg) > BROKEN
