"""One key of the PROGRAM's configuration changed, and the toy window
cell's comparison with the plain reference (``tests/_toy_cell.py``,
``tests/test_smallthinker_trunk.py``) must fail.
"""

import dataclasses

import pytest

from _smallthinker_toy import ref, toy  # noqa: F401
from _toy_cell import BROKEN, first_step_gap, follow

# one thing changed in the PROGRAM's configuration
PROGRAM = {
    # 63 divides into no block: the composite's mask, one key short
    "window_one_key_short": lambda c: dict(sliding_window_size=63),
    "other_experts_held": lambda c: dict(expert_first=3),
    "top_two": lambda c: dict(num_experts_per_tok=2),
}


@pytest.mark.parametrize("what", sorted(PROGRAM))
def test_a_program_altered_in_one_place_fails(toy, ref, what):
    """Every alteration shows in the FIRST step's gradient (the least:
    4.0e-3, the window one key short), so the second step is not
    followed."""
    cfg = dataclasses.replace(toy.cfg, **PROGRAM[what](toy.cfg))
    assert first_step_gap(follow(toy, cfg, steps=1), ref) > BROKEN
