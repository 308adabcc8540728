"""What ``tests/test_hybrid_trunk.py`` and
``tests/test_hybrid_program_altered.py`` share: the toy hybrid cell
(``tests/_toy_cell.py``) — ``make_train_step`` over
``models/hybrid_trunk.py``'s kinds ``mamba`` / ``attention`` against
``benchmark/models/granite_hybrid_reference.py``, the published PATTERN
(one period of ten: five state-space layers, one attention layer, four
more), two state-space heads, a GQA group of 2, two chunks a row so that
the state crosses a chunk.
"""

import _toy_cell

toy, sound, ref = _toy_cell.fixtures("granite", "config_granite.json")
