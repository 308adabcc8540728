"""Multi-host pod launch (round-3 verdict item 2): two launch
controllers (emulated hosts) each spawning --nproc_per_node 2 workers
assemble a 4-process world — coordinator address distribution, per-host
process/device ranks (PADDLE_TRAINER_ID = node_rank * nproc + local),
and the DCN/ICI-aware global mesh (mesh.build_pod_mesh): mp pairs land
on intra-node processes, dp crosses nodes, and a dp×mp hybrid train
step over the process-spanning mesh matches the dense single-process
run.

Reference: python/paddle/distributed/launch/controllers/collective.py,
fleet/base/topology.py:65.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn

# Real-OS-process launch tests: each spawns python workers and waits on
# a TCP rendezvous — tens of seconds per test even when the workers die
# at startup (as they do on hosts whose jax build lacks multi-process
# support).  Tier-1's 870 s budget can't carry that; run them with
# `pytest -m slow` on a host with a working multi-process backend.
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "workers", "pod_worker.py")

STEPS = 3
B, IN, HID, OUT = 8, 8, 16, 4


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _dense_reference():
    rng = np.random.RandomState(0)
    w1 = rng.randn(IN, HID).astype(np.float32) * 0.3
    b1 = rng.randn(HID).astype(np.float32) * 0.1
    w2 = rng.randn(HID, OUT).astype(np.float32) * 0.3
    x = rng.randn(B, IN).astype(np.float32)
    y = rng.randn(B, OUT).astype(np.float32)
    lin1 = nn.Linear(IN, HID)
    lin2 = nn.Linear(HID, OUT, bias_attr=False)
    lin1.weight.set_value(paddle.to_tensor(w1))
    lin1.bias.set_value(paddle.to_tensor(b1))
    lin2.weight.set_value(paddle.to_tensor(w2))
    opt = paddle.optimizer.SGD(
        learning_rate=0.1,
        parameters=list(lin1.parameters()) + list(lin2.parameters()))
    xt, yt = paddle.to_tensor(x), paddle.to_tensor(y)
    losses = []
    for _ in range(STEPS):
        loss = ((lin2(lin1(xt)) - yt) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    return losses


def test_two_node_pod_launch_hybrid_dp_mp(tmp_path):
    port = _free_port()
    out = tmp_path / "pod.json"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    # files not pipes: a filled 64KB pipe deadlocks ranks mid-collective
    procs, logs = [], []
    for node in range(2):
        lf = open(tmp_path / f"node{node}.log", "wb")
        logs.append(lf)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nnodes", "2", "--nproc_per_node", "2",
             "--master", f"127.0.0.1:{port}",
             "--rank", str(node), "--job_id", "podtest",
             "--max_restart", "0", "--log_dir", str(tmp_path),
             WORKER, str(out)],
            env=env, cwd=REPO, stdout=lf, stderr=subprocess.STDOUT))
    try:
        for p in procs:
            p.wait(timeout=360)
    except subprocess.TimeoutExpired:
        for q in procs:
            q.kill()
        raise
    finally:
        for lf in logs:
            lf.close()
    for node, p in enumerate(procs):
        text = (tmp_path / f"node{node}.log").read_text(errors="replace")
        assert p.returncode == 0, text[-3000:]

    data = json.loads(out.read_text())
    # tensor-parallel pairs are intra-node; data-parallel crosses nodes
    assert data["mp_groups"] == [[0, 1], [2, 3]]
    assert data["dp_groups"] == [[0, 2], [1, 3]]
    np.testing.assert_allclose(data["losses"], _dense_reference(),
                               atol=1e-4)
