"""What the ``tests/test_kanana_*.py`` files share: the toy cell
(``tests/_toy_cell.py``) of the family ``kanana_mla_moe`` —
``make_train_step`` over ``models/hybrid_trunk.py``'s kinds ``mla_dense``
/ ``mla_moe`` on ONE residual stream (``hc_mult`` 1) with a query that
has no latent (``q_lora_rank`` 0), against
``benchmark/models/kanana_mla_moe_reference.py``: the published PATTERN
(a dense lead, then expert layers), hidden 128, two heads of 128 | 64 |
128 behind a 64-wide kv latent, plain rotation at theta 1e6, two of
eight experts held from the third on beside TWO shared ones, top-3 of
the sigmoid scores scaled by 2.448, an untied head.
"""

import _toy_cell
from _toy_cell import layer_of  # noqa: F401

toy, sound, ref = _toy_cell.fixtures("kanana", "config_kanana.json",
                                     "train_job_kanana.json")
