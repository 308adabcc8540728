"""What the ``tests/test_solar_*.py`` trunk files share: the toy
delta-rule cell (``tests/_toy_cell.py``) — ``make_train_step`` over
``models/hybrid_trunk.py``'s kinds ``kda_moe`` / ``gqa_gated_moe`` against
``benchmark/models/solar_kda_moe_reference.py``, the published PATTERN
cut to one period (gated GQA, three KDA), hidden 128, four query / two
KV heads of 32 without rotation, two KDA heads of 64 x 64 behind four
taps (no lane tile: the recurrence is ``kda_chunked_xla``, the
``chunk_step`` the kernels run too), two of eight experts held from the third on beside a
shared one, top-3 of the sigmoid scores, an untied head.
"""

import jax
import jax.numpy as jnp

import _toy_cell
from _toy_cell import layer_of  # noqa: F401

toy, sound, ref = _toy_cell.fixtures("solar", "config_solar.json",
                                     "train_job_solar.json")


F32 = jnp.float32
# a block alone, fp32 against fp32: rounding (measured <= 1.6e-5 of the
# largest output, <= 7e-6 of a leaf's gradient norm)
BLOCK = 1e-4


def x_of(toy, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (1, 256, toy.cfg.hidden_size), F32)
