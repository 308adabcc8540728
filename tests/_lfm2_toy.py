"""What the ``tests/test_lfm2_*.py`` trunk files share: the toy
convolution cell (``tests/_toy_cell.py``) — ``make_train_step`` over
``models/hybrid_trunk.py``'s kinds ``conv_dense`` / ``conv_moe`` /
``gqa_qknorm_moe`` against ``benchmark/models/lfm2_conv_moe_reference.py``,
the published PATTERN cut to one dense lead and one period (conv,
attention, three conv), hidden 128, four query / two KV heads of 32 with
q / k norms, three taps, two of eight experts held from the third on,
top-3 of the biased sigmoid scores, one tied table.
"""

import _toy_cell

toy, sound, ref = _toy_cell.fixtures("lfm2", "config_lfm2.json",
                                     "train_job_lfm2.json")
