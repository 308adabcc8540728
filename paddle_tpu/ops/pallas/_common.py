"""Shared Pallas helpers.

The framework enables jax_enable_x64 globally (paddle_tpu/__init__.py) for
int64/float64 API parity.  Under x64, Python int literals in BlockSpec
index maps lower as i64 and Mosaic fails to legalize the mixed-width
index tuple (``func.return (i32, i32, i64)``).  Every index map in our
kernels therefore goes through :func:`idx32`, which pins each component
to int32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["idx32", "interpret", "nbytes"]


def interpret() -> bool:
    """Whether Pallas kernels run in interpret mode: yes when
    ``FLAGS_pallas_interpret`` is set or the backend is ``cpu``, no
    (compiled by Mosaic) on ``tpu``.  Any other backend raises — a
    kernel must never quietly run somewhere nobody chose.  Every
    kernel module calls this through the module (``_common.interpret()``)
    so a compile-for-TPU rehearsal can steer all of them at one name."""
    from ...flags import flags
    if flags.FLAGS_pallas_interpret:
        return True
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels support the 'tpu' and 'cpu' backends, not "
        f"{backend!r}")


def idx32(*idx):
    return tuple(jnp.int32(i) for i in idx)


def nbytes(shape, dtype) -> int:
    """Bytes of one array or block of ``shape``: what a kernel's
    ``cost_estimate`` counts a fetch or a write of it as."""
    return math.prod(shape) * jnp.dtype(dtype).itemsize
