"""What the ``tests/test_smallthinker_*.py`` trunk files share: the toy
window cell (``tests/_toy_cell.py``) — ``make_train_step`` over
``models/hybrid_trunk.py``'s kinds ``gqa_moe_global`` / ``gqa_moe_window``
against ``benchmark/models/smallthinker_moe_reference.py``, the published
PATTERN (global, three window layers, global), four query / two KV heads
of 128, a window of 64 on rows of 256, two of eight experts held from
the third on, top-3 of the logits.
"""

import _toy_cell

toy, sound, ref = _toy_cell.fixtures(
    "smallthinker", "config_smallthinker.json", "train_job_smallthinker.json")
