"""One thing changed in the PROGRAM's configuration, and the toy expert
cell's comparison with the plain reference (``tests/_mla_moe_toy.py``,
``tests/test_mla_moe_trunk.py``) must fail: alterations 5 to 7 of the
sorted list (the others: ``test_mla_moe_program_altered_*.py``).
"""

import pytest

from _mla_moe_toy import PROGRAM, program_altered_fails, ref, toy  # noqa: F401


@pytest.mark.parametrize("what", sorted(PROGRAM)[5:7])
def test_a_program_altered_in_one_place_fails(toy, ref, what):
    program_altered_fails(toy, ref, what)
