"""Launcher stack: job model, KV rendezvous, controller restart policy,
elastic manager, watchdog.

Reference models: distributed/launch/controllers/*, fleet/elastic/
manager.py:124, phi comm_task_manager.h:37 (watchdog role).
"""

import os
import sys
import time
import types

import pytest

from paddle_tpu.distributed.launch import (Container, Job, KVClient,
                                           KVServer, Master, Pod,
                                           Watchdog)
from paddle_tpu.distributed.launch.controllers import CollectiveController
from paddle_tpu.distributed.fleet.elastic import (ElasticManager,
                                                  ElasticStatus)


# -- job model -------------------------------------------------------------
def test_container_lifecycle(tmp_path):
    out = str(tmp_path / "log.txt")
    c = Container([sys.executable, "-c", "print('hello-worker')"], out=out)
    assert c.status == "init"
    c.start()
    assert c.wait(30) == 0
    assert c.status == "completed"
    assert "hello-worker" in open(out).read()


def test_pod_failure_detection():
    p = Pod()
    p.add_container([sys.executable, "-c", "import sys; sys.exit(3)"])
    p.add_container([sys.executable, "-c", "pass"])
    p.deploy()
    for c in p.containers:          # join() itself waits without a bound
        assert c.wait(60) is not None
    p.join()
    failed = p.failed_containers()
    assert len(failed) == 1 and failed[0].exit_code == 3


def test_job_elastic_range():
    j = Job(nnodes="2:4")
    assert j.replicas_min == 2 and j.replicas_max == 4 and j.elastic
    assert not Job(nnodes="2").elastic


# -- KV master / rendezvous ------------------------------------------------
def test_kv_server_roundtrip():
    srv = KVServer().start()
    try:
        cli = KVClient(f"127.0.0.1:{srv.port}")
        assert cli.put("/a/x", "1")
        assert cli.get("/a/x") == "1"
        cli.put("/a/y", "2")
        assert cli.prefix("/a") == {"/a/x": "1", "/a/y": "2"}
        assert cli.delete("/a/x")
        assert cli.get("/a/x") is None
    finally:
        srv.stop()


def test_kv_ttl_expiry():
    srv = KVServer().start()
    try:
        cli = KVClient(f"127.0.0.1:{srv.port}")
        cli.put("/hb/n0", "t")
        time.sleep(0.3)
        dropped = srv.expire("/hb", ttl=0.1)
        assert dropped == ["/hb/n0"]
        assert cli.prefix("/hb") == {}
    finally:
        srv.stop()


def test_master_sync_peers():
    m = Master(None, is_master=True)
    try:
        import threading
        results = {}

        def worker(rank):
            cli_master = Master(m.endpoint, is_master=False)
            peers, r = cli_master.sync_peers(
                "/rdzv/test", str(rank), f"node{rank}", size=3,
                timeout=10)
            results[rank] = (peers, r)

        ts = [threading.Thread(target=worker, args=(r,)) for r in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(15)
        assert len(results) == 3
        peers, _ = results[0]
        assert sorted(peers) == ["node0", "node1", "node2"]
    finally:
        m.stop()


# -- controller restart policy ---------------------------------------------
def _args(tmp_path, script, max_restart=2):
    return types.SimpleNamespace(
        nnodes="1", nproc_per_node=None, ips=None, master=None, rank=-1,
        devices=None, log_dir=str(tmp_path), log_to_file=False,
        job_id="t", run_mode="collective", max_restart=max_restart,
        elastic_timeout=5.0, training_script=script,
        training_script_args=[])


def test_controller_restarts_then_fails(tmp_path):
    script = str(tmp_path / "always_fail.py")
    with open(script, "w") as f:
        f.write("import sys; sys.exit(7)\n")
    c = CollectiveController(_args(tmp_path, script, max_restart=2))
    rc = c.run()
    assert rc == 7
    assert c.pod.restart_count == 2


def test_controller_restart_recovers(tmp_path):
    # fails on first run, succeeds once a marker file exists
    marker = str(tmp_path / "marker")
    script = str(tmp_path / "flaky.py")
    with open(script, "w") as f:
        f.write(
            "import os, sys\n"
            f"m = {marker!r}\n"
            "if not os.path.exists(m):\n"
            "    open(m, 'w').close(); sys.exit(1)\n"
            "sys.exit(0)\n")
    c = CollectiveController(_args(tmp_path, script))
    assert c.run() == 0
    assert c.pod.restart_count == 1
    # restart count visible to the worker via env
    assert c.pod.containers[0].env["PADDLE_RESTART_COUNT"] == "1"


# -- elastic ---------------------------------------------------------------
def test_elastic_scale_down_detected():
    srv = KVServer().start()
    try:
        ep = f"127.0.0.1:{srv.port}"
        events = []
        m0 = ElasticManager(ep, "job", "n0", (1, 3),
                            heartbeat_interval=0.1, heartbeat_ttl=0.5,
                            on_scale=lambda a: events.append(list(a)),
                            server=srv).start()
        m1 = ElasticManager(ep, "job", "n1", (1, 3),
                            heartbeat_interval=0.1,
                            heartbeat_ttl=0.5).start()
        assert m0.wait_for_np(2, timeout=5) == ["n0", "n1"]
        time.sleep(0.5)   # let both watch loops settle on the 2-node set
        # n1 leaves; n0 must notice within the TTL window
        m1.stop()
        m1.leave()
        assert "n1" not in m0.alive_nodes()
        deadline = time.time() + 5
        while time.time() < deadline and \
                (not events or events[-1] != ["n0"]):
            time.sleep(0.1)
        assert events and events[-1] == ["n0"]
        assert m0.status == ElasticStatus.RESTART
        m0.stop()
    finally:
        srv.stop()


def test_elastic_scale_up_detected():
    srv = KVServer().start()
    try:
        ep = f"127.0.0.1:{srv.port}"
        events = []
        m0 = ElasticManager(ep, "j2", "a", (1, 3),
                            heartbeat_interval=0.1, heartbeat_ttl=1.0,
                            on_scale=lambda a: events.append(list(a)),
                            server=srv).start()
        time.sleep(0.3)
        m1 = ElasticManager(ep, "j2", "b", (1, 3),
                            heartbeat_interval=0.1,
                            heartbeat_ttl=1.0).start()
        deadline = time.time() + 5
        while time.time() < deadline and not events:
            time.sleep(0.1)
        assert events and events[-1] == ["a", "b"]
        m0.stop()
        m1.stop()
    finally:
        srv.stop()


# -- watchdog --------------------------------------------------------------
def test_watchdog_ticks_prevent_stall():
    fired = []
    wd = Watchdog(timeout=0.5, on_stall=lambda e: fired.append(e),
                  poll_interval=0.1)
    with wd:
        for _ in range(5):
            time.sleep(0.2)
            wd.tick()
    assert not fired and not wd.stalled


def test_watchdog_fires_on_stall():
    fired = []
    wd = Watchdog(timeout=0.3, on_stall=lambda e: fired.append(e),
                  poll_interval=0.1)
    wd.start()
    time.sleep(0.8)
    wd.stop()
    assert fired and wd.stalled


def test_controller_elastic_restarts_on_scale_up(tmp_path):
    """--nnodes 1:3 with a master: a new node joining mid-run restarts
    the pod with the larger world size."""
    from paddle_tpu.distributed.launch.master import KVServer

    script = str(tmp_path / "train.py")
    with open(script, "w") as f:
        f.write("import time, os\n"
                "time.sleep(1.5)\n")
    args = _args(tmp_path, script)
    args.nnodes = "1:3"
    # controller will host the KV server at this port
    import socket
    s = socket.socket(); s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]; s.close()
    args.master = f"127.0.0.1:{port}"

    c = CollectiveController(args)
    import threading
    rc_box = {}

    def run():
        rc_box["rc"] = c.run()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    # second "node" joins after the pod is up
    time.sleep(0.7)
    m2 = ElasticManager(args.master, "t", "node-extra", (1, 3),
                        heartbeat_interval=0.1,
                        heartbeat_ttl=1.0).start()
    t.join(20)
    m2.stop()
    c.stop()
    assert rc_box.get("rc") == 0
    # the restarted pod saw the grown world
    assert c._world == 2
    assert c.pod.containers[0].env["PADDLE_TRAINERS_NUM"] == "2"
