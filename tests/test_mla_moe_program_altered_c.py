"""One thing changed in the PROGRAM's configuration, and an expert layer's
comparison with the plain reference's block (``tests/_mla_moe_toy.py``,
``_toy_cell.block_gap``) must fail: alterations 5 to 7 of the
sorted list (the others: ``test_mla_moe_program_altered_*.py``).
"""

import pytest

from _mla_moe_toy import PROGRAM, program_altered_fails, toy  # noqa: F401


@pytest.mark.parametrize("what", sorted(PROGRAM)[5:7])
def test_a_program_altered_in_one_place_fails(toy, what):
    program_altered_fails(toy, what)
