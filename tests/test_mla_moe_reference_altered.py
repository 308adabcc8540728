"""One line changed in the REFERENCE, and the toy expert cell's
comparison with the sound program (``tests/_mla_moe_toy.py``) must fail:
alterations 2 onward of the sorted list (the first two stand in
``tests/test_mla_moe_trunk.py``, which builds the sound program anyway).
"""

import pytest

from _mla_moe_toy import (REFERENCE, reference_altered_fails, sound,  # noqa: F401
                          toy)


@pytest.mark.parametrize("what", sorted(REFERENCE)[2:])
def test_a_reference_altered_in_one_line_fails(toy, sound, what):
    reference_altered_fails(toy, sound, what)
