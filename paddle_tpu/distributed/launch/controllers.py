"""Launch controllers (reference: distributed/launch/controllers/
{controller.py, collective.py, watcher.py}).

``CollectiveController`` builds this host's Pod, deploys it, and runs
the watch loop: poll container status, restart failed pods up to
``max_restarts`` (the reference's replicas/restart policy), propagate
the final exit code.  Failure detection is process-level here;
in-process collective hangs are covered by ``watchdog.Watchdog``.
"""

from __future__ import annotations

import os
import sys
import time
from typing import List, Optional

from ...framework.compile_cache import compile_cache_dir
from .job import Job, Pod

__all__ = ["Controller", "CollectiveController"]


class Controller:
    def __init__(self, args):
        self.args = args
        self.job = Job(jid=args.job_id, mode=args.run_mode,
                       nnodes=str(args.nnodes))
        self.pod = Pod()
        self.restart_count = 0
        self.max_restarts = getattr(args, "max_restart", 3)
        self._elastic = None
        self._world = self.job.replicas_min

    # -- hooks ------------------------------------------------------------
    def build_pod(self):  # pragma: no cover - abstract
        raise NotImplementedError

    def run(self) -> int:
        if self.job.elastic and self.args.master:
            self._start_elastic()
        self.build_pod()
        self.pod.deploy()
        return self.watch()

    def _start_elastic(self):
        """Join the elastic membership group and size the world to the
        CURRENT quorum (>= replicas_min); membership changes flip the
        manager to RESTART, which the watch loop acts on."""
        import os
        from ..fleet.elastic import ElasticManager
        node_id = os.environ.get("PADDLE_TRAINER_ID", None) or \
            f"node-{os.getpid()}"
        is_master = os.environ.get("PADDLE_TRAINER_ID", "0") == "0"
        server = None
        if is_master:
            from .master import KVServer
            port = int(self.args.master.split(":")[1])
            try:
                server = KVServer(port).start()
            except OSError:
                server = None   # another local controller already hosts
        self._elastic = ElasticManager(
            self.args.master, self.job.id, str(node_id),
            (self.job.replicas_min, self.job.replicas_max),
            server=server).start()
        alive = self._elastic.wait_for_np(
            self.job.replicas_min,
            timeout=getattr(self.args, "elastic_timeout", 60.0))
        self._world = max(self.job.replicas_min,
                          min(len(alive), self.job.replicas_max))

    def watch(self) -> int:
        """Reference controller.py watch loop + watcher.py: act on the
        FIRST failed container — siblings may be blocked in collectives
        waiting for the dead peer, so is_done() alone would hang."""
        from ..fleet.elastic import ElasticStatus
        while True:
            if self._elastic is not None and \
                    self._elastic.status == ElasticStatus.RESTART:
                alive = self._elastic.alive_nodes()
                self._world = max(self.job.replicas_min,
                                  min(len(alive), self.job.replicas_max))
                self._elastic.status = ElasticStatus.HOLD
                sys.stderr.write(
                    f"[launch] elastic membership change -> world size "
                    f"{self._world}; restarting pod\n")
                self.pod.stop(force=True)
                self.build_pod()
                self.pod.deploy()
                continue
            failed = self.pod.failed_containers()
            if failed or self.pod.is_done():
                if not failed:
                    return 0
                if self.restart_count < self.max_restarts:
                    self.restart_count += 1
                    sys.stderr.write(
                        f"[launch] container failed (exit "
                        f"{failed[0].exit_code}); restart "
                        f"{self.restart_count}/{self.max_restarts}\n")
                    self.pod.stop(force=True)
                    self.build_pod()
                    self.pod.deploy()
                    continue
                return failed[0].exit_code or 1
            time.sleep(0.5)

    def stop(self):
        if self._elastic is not None:
            self._elastic.stop()
        self.pod.stop(force=True)


class CollectiveController(Controller):
    """One container per local worker process; multi-node wires the
    jax.distributed coordination env (reference collective.py:31).

    Pod topology (reference launch/controllers/collective.py — the
    trainer-rank/endpoint assembly): the global world is
    ``nnodes × nproc_per_node`` processes; this host's node rank comes
    from ``--rank`` (or PADDLE_TRAINER_ID), each local worker ``j``
    gets global rank ``node_rank * nproc_per_node + j``.  The
    coordinator address is ``--master``, or derived from the first
    entry of ``--ips`` — the reference's "first trainer is the master"
    convention.  On a TPU pod the normal shape is one process per host
    (``nproc_per_node=1``, SPMD over all local chips);
    ``nproc_per_node>1`` is the CPU-hosts / test shape.
    """

    def _master(self, world: int):
        args = self.args
        if args.master:
            return args.master
        if args.ips:
            first = args.ips.split(",")[0].strip()
            return first if ":" in first else f"{first}:8701"
        if world > 1 and self.job.replicas_min == 1:
            # single node, several local workers: rendezvous locally.
            # Bind-then-close has a TOCTOU window before worker rank
            # 0's coordinator rebinds the port; acceptable for the
            # local-test shape (real pods pass --master explicitly)
            import socket
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                return f"127.0.0.1:{s.getsockname()[1]}"
        return None

    def build_pod(self):
        args = self.args
        self.pod = Pod(name=f"{self.job.id}-pod")
        self.pod.restart_count = self.restart_count
        nnodes = self._world
        nproc = args.nproc_per_node or 1
        world = nnodes * nproc
        node_rank = args.rank if args.rank >= 0 else int(
            os.environ.get("PADDLE_TRAINER_ID", "0"))
        master = self._master(world)
        if world > 1 and not master:
            raise SystemExit(
                "--master host:port (or --ips) is required for "
                "multi-node")
        endpoints = None
        if args.ips:
            hosts = [h.strip().split(":")[0]
                     for h in args.ips.split(",")]
            endpoints = ",".join(
                f"{h}:{6170 + j}" for h in hosts for j in range(nproc))
        base = {
            # workers share one persistent compile cache (the
            # environment's directory, else <checkout>/.jax_cache)
            "JAX_COMPILATION_CACHE_DIR": compile_cache_dir(),
            "PADDLE_JOB_ID": self.job.id,
            "PADDLE_RESTART_COUNT": str(self.restart_count),
            "PADDLE_NNODES": str(nnodes),
            "PADDLE_LOCAL_SIZE": str(nproc),
        }
        if endpoints:
            base["PADDLE_TRAINER_ENDPOINTS"] = endpoints
        for j in range(nproc):
            env = dict(base)
            if world > 1:
                # distributed/env.py's init_parallel_env reads
                # PADDLE_MASTER / PADDLE_TRAINERS_NUM /
                # PADDLE_TRAINER_ID and feeds them to
                # jax.distributed.initialize
                env["PADDLE_TRAINERS_NUM"] = str(world)
                env["PADDLE_MASTER"] = master
                env["PADDLE_TRAINER_ID"] = str(node_rank * nproc + j)
            else:
                # operator-preset coordination env wins in the
                # single-worker path (per-host launches with external
                # coordination)
                env["PADDLE_TRAINERS_NUM"] = os.environ.get(
                    "PADDLE_TRAINERS_NUM", "1")
                env["PADDLE_TRAINER_ID"] = os.environ.get(
                    "PADDLE_TRAINER_ID", "0")
            env["PADDLE_RANK_IN_NODE"] = str(j)
            out = os.path.join(args.log_dir, f"workerlog.{j}")
            self.pod.add_container(
                [sys.executable, args.training_script,
                 *args.training_script_args],
                env=env, out=out if getattr(args, "log_to_file", False)
                else None)
