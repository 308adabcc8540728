"""Pallas TPU kernels — the hot-op set (SURVEY.md §7 step 10).

``register_pallas_ops()`` installs them in the op dispatch table; called
at package import.  Each kernel has an interpret-mode path so the same
code runs (slowly) on CPU for tests (FLAGS_pallas_interpret)."""

from __future__ import annotations

from ..dispatch import register_op_impl
from .flash_attention import flash_attention
from .rms_norm import rms_norm
from .fused_adamw import fused_adamw
from .rope import fused_rope, rope_tables
from .int8_matmul import int8_matmul, quantize_int8

__all__ = ["flash_attention", "rms_norm", "fused_adamw", "fused_rope",
           "rope_tables", "int8_matmul", "quantize_int8",
           "register_pallas_ops"]


def register_pallas_ops() -> None:
    # Compiled-path correctness of these kernels on real TPU is covered
    # by tests/test_pallas_tpu.py (interpret=False lane); flash_attention
    # routes unsupported static shapes to its internal XLA fallback.
    register_op_impl("flash_attention", flash_attention)
    register_op_impl("fused_adamw",
                     lambda p, g, m, v, t, lr, b1, b2, eps, wd:
                     fused_adamw(p, g, m, v, t, lr, b1, b2, eps, wd))
    register_op_impl("rms_norm", rms_norm)
    register_op_impl("fused_rope", fused_rope)
    register_op_impl("int8_matmul", int8_matmul)


register_pallas_ops()
