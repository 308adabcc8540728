"""A REAL 2-process distributed test (round-3 item 7): two OS processes
spawned through ``paddle_tpu.distributed.launch`` controllers rendezvous
via jax.distributed (the PjRt coordination service = TCPStore analog),
run a cross-process allreduce and a data-parallel train step over a
global 2-device mesh, and the loss matches the single-process run.

Reference model: test/legacy_test/test_dist_base.py:952 (TestDistBase
spawning two trainers and comparing losses).
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "workers", "dp_worker.py")

# Real-OS-process launch tests: each spawns python workers and waits on
# a TCP rendezvous — tens of seconds per test even when the workers die
# at startup (as they do on hosts whose jax build lacks multi-process
# support).  Tier-1's 870 s budget can't carry that; run them with
# `pytest -m slow` on a host with a working multi-process backend.
pytestmark = pytest.mark.slow


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _reference_losses():
    rng = np.random.RandomState(0)
    X = rng.randn(16, 4).astype(np.float32)
    Y = X @ np.array([[1.5], [-2.0], [0.7], [0.3]], np.float32)
    w = np.zeros((4, 1), np.float32)
    losses = []
    for _ in range(5):
        pred = X @ w
        losses.append(float(np.mean((pred - Y) ** 2)))
        g = 2.0 * X.T @ (pred - Y) / X.shape[0]
        w = w - 0.1 * g
    return losses


def test_two_process_launch_allreduce_and_dp_step(tmp_path):
    port = _free_port()
    out = tmp_path / "rank0.json"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)          # one CPU device per process
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    procs = []
    for rank in range(2):
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nnodes", "2", "--master", f"127.0.0.1:{port}",
             "--rank", str(rank), "--job_id", "twoproc",
             "--max_restart", "0", "--log_dir", str(tmp_path),
             WORKER, str(out)],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outputs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outputs.append(stdout.decode(errors="replace"))
    for p, text in zip(procs, outputs):
        assert p.returncode == 0, text[-2000:]

    data = json.loads(out.read_text())
    assert data["allreduce"] == 3.0
    np.testing.assert_allclose(data["losses"], _reference_losses(),
                               rtol=1e-5)
