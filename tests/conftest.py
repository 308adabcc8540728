"""Tests run on the CPU backend (``JAX_PLATFORMS=cpu``; forced below so a bare
``pytest`` works too) with 8 virtual devices for the sharding tests.  The chip
is reached through ``python chip_smoke.py`` [``--chips 4``], never from here.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
# XLA aborts the PROCESS when a CPU collective waits 40 s for a peer; with
# six workers on eight cores the 8-device programs of test_graft_entry
# wait 20-35 s (rendezvous.cc's "may be stuck ... unstuck"), and an abort
# takes the xdist worker down with it (met twice in four whole runs).
if "--xla_cpu_collective_call_terminate_timeout_seconds" not in \
        os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += \
        " --xla_cpu_collective_call_terminate_timeout_seconds=600"

import jax  # noqa: E402

# PADDLE_TPU_TESTS_ON_TPU=1 leaves the platform to jax so the on-chip
# numeric-parity lane (tests/test_pallas_tpu.py) runs Mosaic-compiled
# kernels:  PADDLE_TPU_TESTS_ON_TPU=1 python -m pytest tests/test_pallas_tpu.py
# on the machine with the chip.  tests/test_tpu_compile.py guards the
# main-path kernels' LOWERING here, with no chip.
if os.environ.get("PADDLE_TPU_TESTS_ON_TPU") != "1":
    jax.config.update("jax_platforms", "cpu")
# Entry points a test may start (GenerationServer.start) name the cache
# directory; under test nothing is read from or written to it.
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _deterministic_seed():
    import paddle_tpu as paddle
    paddle.seed(1234)
    np.random.seed(1234)
    yield
