"""Multi-process replicas: the agent process and its fleet-side handle.

The in-process fleet tier (PR 8) and disaggregated handoff (PR 9) are
the ORACLE: every routing, failover, backpressure and degradation
decision was pinned with all replicas in one process behind seams the
ROADMAP reserved for "a real sockets transport".  This module is that
transport's two endpoints:

* :class:`ReplicaAgent` — hosts ONE supervisor-wrapped engine and
  speaks the frame protocol of :mod:`paddle_tpu.fleet.transport`
  over TCP.  A drive thread steps the engine continuously; RPC
  handler threads serialize against it on the agent lock (the
  ``GenerationServer`` discipline).  Runs in-thread (tests, CPU
  smoke), or as a real OS process via :func:`spawn_agent_process` —
  which dies by ``SIGKILL`` like production replicas do, not by a
  Python exception.
* :class:`RemoteReplicaHandle` — drops into
  :class:`~paddle_tpu.fleet.FleetRouter` beside the in-process
  :class:`~paddle_tpu.fleet.router.ReplicaHandle`\\ s: same lifecycle
  states, same ``handoff_transport`` seam, same failover semantics,
  so a socket fleet is pinned token-exact against the in-process one.

Liveness is LEASE-based: every successful RPC renews the lease; a
failed round-trip is a heartbeat miss that turns the replica
DEGRADED (routing steers around it, the next tick retries), and a
lease that stays unrenewed past ``lease_s`` raises
:class:`~paddle_tpu.fleet.transport.LeaseExpiredError` out of the
handle's step — which the router's EXISTING death triage turns into
transparent failover (zero-streamed orphans re-place token-exact with
their fleet rid and absolute deadline intact; mid-stream ones error
honestly).  Half-open connections, stalled peers, truncated frames
and ``SIGKILL``\\ ed agents all funnel into that one audited path.

Delivery is CURSOR-acknowledged: the agent buffers every streamed
token and finished result under a sequence number and only prunes
what the handle has acked, so a sync response lost to a connection
drop is re-served on the retry — at-least-once transport, exactly-once
delivery.  Submission is IDEMPOTENT: every submit carries a key
(client id + fleet rid), and the agent's dedup table returns the
original local rid for a retried frame — an ambiguous timeout can
never double-generate.

KV handoffs ship as raw numpy buffers (fp pools and int8 scale planes
alike) through the same header+blobs frames — wire round-trips are
bitwise, pinned by tests/test_transport.py.  See docs/TRANSPORT.md.
"""

from __future__ import annotations

import importlib
import os
import signal
import socket
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..models.serving_engine import (EngineDeadError, EngineSupervisor,
                                     QueueFullError, Request)
from ..testing import faults
from .transport import (Connection, LeaseExpiredError, ProtocolError,
                        TransportError, open_connection, pack_array,
                        recv_frame, send_frame, unpack_array)

__all__ = ["ReplicaAgent", "RemoteSpec", "RemoteReplicaHandle",
           "spawn_agent_process", "arm_fault_spec"]


# ---------------------------------------------------------------------------
# wire form of a Request (clock-re-anchored on receive)
# ---------------------------------------------------------------------------
def wire_request(req: Request, trace_id=None) -> dict:
    """JSON-able form of a ``Request``.  Monotonic timestamps are
    meaningless across processes, so the dict carries the sender's
    ``now`` and the receiver shifts every clock field by its own
    offset — relative structure (phase durations, deadline headroom)
    survives the hop exactly."""
    return {"rid": int(req.rid),
            "max_new_tokens": int(req.max_new_tokens),
            "generated": [int(t) for t in req.generated],
            "stop_sequences": req.stop_sequences,
            "done": bool(req.done),
            "status": req.status, "error": req.error,
            "preempted": int(req.preempted),
            "deadline": float(req.deadline),
            "t_submit": float(req.t_submit),
            "t_admit": float(req.t_admit),
            "t_first_token": float(req.t_first_token),
            "t_finish": float(req.t_finish),
            "spec": req.spec,
            "priority": req.priority, "tenant": req.tenant,
            "degraded": bool(req.degraded),
            "phase": req.phase, "t_phase": float(req.t_phase),
            "phase_log": [[p, float(a), float(b)]
                          for p, a, b in req.phase_log],
            "trace_id": trace_id,
            "now": time.monotonic()}


def request_from_wire(d: dict, prompt: np.ndarray) -> Request:
    off = time.monotonic() - d["now"]

    def shift(t):
        return (t + off) if t else 0.0

    req = Request(int(d["rid"]), np.asarray(prompt, np.int64),
                  int(d["max_new_tokens"]),
                  generated=[int(t) for t in d["generated"]],
                  stop_sequences=d.get("stop_sequences"),
                  t_submit=shift(d["t_submit"]),
                  t_admit=shift(d["t_admit"]),
                  t_first_token=shift(d["t_first_token"]),
                  t_finish=shift(d["t_finish"]),
                  deadline=shift(d["deadline"]),
                  spec=d.get("spec"))
    req.done = bool(d["done"])
    req.status = d["status"]
    req.error = d["error"]
    req.preempted = int(d.get("preempted", 0))
    req.priority = d.get("priority", "normal")
    req.tenant = d.get("tenant")
    req.degraded = bool(d.get("degraded", False))
    req.phase = d["phase"]
    req.t_phase = shift(d["t_phase"])
    req.phase_log = [(p, shift(a), shift(b))
                     for p, a, b in d["phase_log"]]
    return req


class _WireHandoffRecord:
    """A HandoffRecord reconstructed from the wire: blobs already
    materialized (idempotent ``materialize()`` returns them), staging
    pages long since freed on the source side (``discard()`` is a
    local no-op).  ``poisoned`` marks a record whose source-side
    materialization failed — the router's ship path then degrades it
    to a colocated re-prefill exactly like an in-process ship fault."""

    __slots__ = ("request", "blobs", "pages", "nbytes", "poisoned")

    def __init__(self, request: Request, blobs, pages: int,
                 nbytes: int, poisoned: Optional[str] = None):
        self.request = request
        self.blobs = blobs
        self.pages = int(pages)
        self.nbytes = int(nbytes)
        self.poisoned = poisoned

    def materialize(self):
        if self.poisoned is not None:
            raise RuntimeError(
                f"handoff ship failed on the source agent: "
                f"{self.poisoned}")
        return self.blobs

    def discard(self) -> None:
        self.blobs = None


def arm_fault_spec(spec) -> None:
    """Arm a JSON-able fault schedule into THIS process's plane —
    the agent half of the fault-plane gap fix: ``testing/faults.py``
    is process-global, so a schedule armed in the router process
    silently does nothing inside a spawned agent.  Agents accept
    ``fault_spec=[{"site": ..., "exc": "RuntimeError:boom",
    "every"/"nth"/"times"/"p"/"seed": ...}, ...]`` in their spawn
    config and arm it locally at start (docs/FAULT_TOLERANCE.md,
    "Remote-agent fault injection")."""
    if not spec:
        return
    fp = faults.get()
    if fp is None:
        fp = faults.install()
    import builtins
    for f in spec:
        exc = None
        if f.get("exc"):
            etype, _, msg = str(f["exc"]).partition(":")
            cls = getattr(builtins, etype, None)
            if not (isinstance(cls, type)
                    and issubclass(cls, BaseException)):
                cls = RuntimeError
            exc = cls(msg or "injected")
        fp.inject(f["site"], exc, nth=f.get("nth"),
                  every=f.get("every"), times=f.get("times"),
                  p=f.get("p"), seed=f.get("seed", 0))


# ---------------------------------------------------------------------------
# the agent (server side)
# ---------------------------------------------------------------------------
class ReplicaAgent:
    """One engine replica served over TCP.

    A drive thread steps the supervisor whenever it has work and
    harvests stream/finished into a cursor-acknowledged event buffer;
    handler threads (one per client connection) answer RPCs.  Every
    engine touch — drive step, submit, cancel, handoff admission —
    serializes on ``_lock``, preserving the engine-thread-only
    contract exactly the way ``GenerationServer`` does.

    ``shutdown(graceful=True)`` stops admission, lets the drive
    thread finish every in-flight stream, keeps answering syncs until
    the last result is acked, then exits — a rolling restart never
    truncates a generation.  :meth:`die` is the opposite: an abrupt
    in-process stand-in for ``SIGKILL`` (sockets torn down, engine
    abandoned mid-step) used by chaos tests that cannot afford a real
    process per case; :func:`spawn_agent_process` covers the real
    thing."""

    # bounds the idempotency dedup table (oldest keys evicted
    # first): retries arrive within a call's bounded backoff
    # window, so thousands of retained keys is already paranoia —
    # but a long-lived agent must never grow with request count
    _KEY_CAP = 4096

    def __init__(self, factory: Callable, *, host: str = "127.0.0.1",
                 port: int = 0, role: str = "unified",
                 lease_s: float = 2.0, poll_s: float = 0.002,
                 fault_spec=None, max_restarts: int = 3,
                 window_s: float = 60.0, backoff_s: float = 0.0):
        self._factory = factory
        self.host, self.port = host, int(port)
        self.role = role
        self.lease_s = float(lease_s)
        self.poll_s = float(poll_s)
        self.fault_spec = fault_spec
        self._sup_kw = dict(max_restarts=max_restarts,
                            window_s=window_s, backoff_s=backoff_s)
        # TWO locks, strictly ordered _lock > _buf_lock: the engine
        # lock is held across jitted steps INCLUDING their first
        # compile (seconds on a cold engine), and a sync heartbeat
        # that had to wait for a compile would expire a healthy
        # replica's lease — so sync serves from the buffer lock
        # alone, and the drive thread publishes into it after every
        # step.  The lease answers "is the PROCESS alive", never
        # "is the engine fast".
        self._lock = threading.Lock()
        self._buf_lock = threading.Lock()
        self._sup: Optional[EngineSupervisor] = None
        self._listener: Optional[socket.socket] = None
        self._threads: List[threading.Thread] = []
        self._conns: List[socket.socket] = []
        self._events: List[tuple] = []     # (seq, kind, payload...)
        self._next_seq = 0
        self._snap_cache: dict = {}        # last published snapshot
        # idempotency dedup: key -> rid, BOUNDED — keys are retained
        # long enough to absorb any realistic retry (including one
        # landing after the request finished) but a long-lived agent
        # must not grow RSS with its lifetime request count
        self._by_key: Dict[str, int] = {}
        self._key_order: deque = deque()
        self._trace_ids: Dict[int, object] = {}
        # taken-but-unacked handoff batch: take_handoffs drains
        # records OUT of the engine, so a response lost on the wire
        # would lose the only copy of their KV blobs and strand the
        # requests — the last batch is stashed and re-served until
        # the client's next call acks it (bounded: one batch)
        self._ho_seq = 0
        self._ho_last: Optional[tuple] = None
        # mutation counter: bumped by every state-mutating RPC and
        # published with the snapshot, so a sync served from a
        # snapshot OLDER than a mutation the client already got an
        # ack for can never read as "idle" (the two-lock split makes
        # sync responses up to one drive-loop iteration stale)
        self._mut = 0
        self._closing = False              # graceful: refuse submits
        self._stop = False                 # hard: threads exit
        self._fatal: Optional[str] = None  # escaped EngineDeadError

    # -- lifecycle --------------------------------------------------------
    def start(self) -> int:
        """Arm the local fault spec, build the engine, bind, serve.
        Returns the bound port."""
        arm_fault_spec(self.fault_spec)
        self._sup = EngineSupervisor(self._factory, **self._sup_kw)
        self._listener = socket.socket(socket.AF_INET,
                                       socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET,
                                  socket.SO_REUSEADDR, 1)
        self._listener.bind((self.host, self.port))
        self._listener.listen(8)
        self.port = self._listener.getsockname()[1]
        for fn in (self._accept_loop, self._drive_loop):
            t = threading.Thread(target=fn, daemon=True,
                                 name=f"agent-{fn.__name__}")
            t.start()
            self._threads.append(t)
        return self.port

    def join(self, timeout: Optional[float] = None) -> None:
        for t in self._threads:
            t.join(timeout)

    def stop(self) -> None:
        """Cooperative teardown (tests): stop threads, close
        sockets.  In-flight work is abandoned — use ``shutdown``
        over the wire for the graceful form."""
        self._stop = True
        self._close_sockets()
        self.join(timeout=5.0)

    def die(self) -> None:
        """Abrupt death for chaos tests running the agent in-thread:
        sockets torn down mid-frame, threads told to exit, the engine
        abandoned wherever it was — the closest an in-process agent
        gets to ``SIGKILL`` (spawned agents get the real signal)."""
        self._stop = True
        self._close_sockets()

    def _close_sockets(self) -> None:
        if self._listener is not None:
            try:
                self._listener.close()
            except OSError:
                pass
        for c in list(self._conns):
            try:
                c.close()
            except OSError:
                pass

    # -- drive thread -----------------------------------------------------
    def _drive_loop(self) -> None:
        while not self._stop:
            with self._lock:
                work = self._sup.has_work() and self._fatal is None
                if work:
                    try:
                        self._sup.step()
                    except Exception as e:
                        # past the restart budget (or an unrecoverable
                        # engine): the agent keeps ANSWERING — syncs
                        # report state DEAD so the fleet side triages
                        # through its ordinary death path instead of
                        # guessing at a silent peer
                        self._fatal = (f"{type(e).__name__}: {e}")
                new = self._harvest_locked()
                snap = self._snapshot_locked()
                still = self._sup.has_work()
            with self._buf_lock:
                self._events.extend(
                    (self._next_seq + i, *ev)
                    for i, ev in enumerate(new))
                self._next_seq += len(new)
                self._snap_cache = snap
                done = (self._closing and not self._events
                        and not still)
            if done:
                self._stop = True
                # graceful exit owns its own teardown: without this
                # the accept thread blocks in accept() and the bound
                # listener FD outlives the agent (one leak per
                # rolling restart)
                self._close_sockets()
                break
            if not work:
                time.sleep(self.poll_s)

    def _harvest_locked(self) -> List[tuple]:
        """Drain stream/finished into seq-less event tuples (the
        drive loop stamps sequence numbers under the buffer lock);
        CONTRACT: caller holds ``_lock`` (registered in analysis/
        annotations.py locked_methods)."""
        out: List[tuple] = []
        for rid, tok in self._sup.drain_stream():
            out.append(("tok", int(rid), int(tok)))
        for req in self._sup.finished():
            d = wire_request(req, self._trace_ids.pop(req.rid, None))
            out.append(("fin", d))
        return out

    # -- accept / RPC threads ---------------------------------------------
    def _accept_loop(self) -> None:
        while not self._stop:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return                     # listener closed
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._conns.append(conn)
            # connection churn (every reconnect lands here) must not
            # grow the thread list with the agent's lifetime
            self._threads = [t for t in self._threads
                             if t.is_alive()]
            t = threading.Thread(target=self._handle_conn,
                                 args=(conn,), daemon=True,
                                 name="agent-conn")
            t.start()
            self._threads.append(t)

    def _handle_conn(self, conn: socket.socket) -> None:
        try:
            while not self._stop:
                try:
                    header, blobs, _ = recv_frame(conn)
                except (ProtocolError, TransportError):
                    return   # truncated/garbage frame or peer gone:
                    #          drop THIS connection, keep serving
                resp, rblobs = self._dispatch(header, blobs)
                resp["seq"] = header.get("seq")
                try:
                    send_frame(conn, resp, rblobs)
                except TransportError:
                    return   # peer vanished mid-reply: the event
                    #          buffer keeps its items for the retry
        finally:
            try:
                conn.close()
            except OSError:
                pass
            if conn in self._conns:
                self._conns.remove(conn)

    def _dispatch(self, header: dict, blobs) -> Tuple[dict, list]:
        op = header.get("op")
        try:
            if op == "sync":
                # the heartbeat path NEVER touches the engine lock: a
                # first-compile step can hold it for seconds, and a
                # lease that expired behind a compile would SIGKILL a
                # healthy replica (found the hard way)
                with self._buf_lock:
                    resp, rblobs = self._rpc_sync_buf(header)
            else:
                with self._lock:
                    fn = getattr(self, f"_rpc_{op}", None)
                    if fn is None:
                        raise RuntimeError(f"unknown op {op!r}")
                    resp, rblobs = fn(header, blobs)
            resp.setdefault("ok", True)
            return resp, rblobs
        except Exception as e:
            return ({"ok": False, "etype": type(e).__name__,
                     "error": str(e),
                     "retry_after": getattr(e, "retry_after", None)},
                    [])

    # -- RPC ops (CONTRACT: _dispatch holds _lock; registered in
    #    analysis/annotations.py locked_methods) --------------------------
    def _rpc_hello(self, header, blobs):
        from ..models.serving_engine import _count_params
        eng = self._sup.engine
        if getattr(eng, "_n_params", None) is None:
            eng._n_params = _count_params(eng.params)
        cache = eng.cache
        return ({"role": self.role, "pid": os.getpid(),
                 "lease_s": self.lease_s,
                 "page": int(cache.page), "B": int(eng.B),
                 # cost-model constants: the router's bytes-vs-FLOPs
                 # disaggregation verdict runs against the mirror, so
                 # remote and in-process lanes price identically
                 "pages_max": int(cache.pages_max),
                 "num_pages": int(cache.num_pages),
                 "page_bytes": int(cache.page_bytes),
                 "n_params": int(eng._n_params),
                 "mixed": bool(getattr(eng, "_mixed", False)),
                 "caps": {
                     "prefill": hasattr(eng, "take_handoffs"),
                     "decode": hasattr(eng, "admit_handoff")},
                 "now": time.monotonic()}, [])

    def _rpc_ping(self, header, blobs):
        return ({"now": time.monotonic(),
                 "state": self._sup.state}, [])

    def _rpc_submit(self, header, blobs):
        if self._closing:
            raise RuntimeError("agent shutting down: not admitting")
        key = header.get("key")
        if key is not None and key in self._by_key:
            # idempotent resubmission (ambiguous timeout retry): the
            # original placement answers — never a second generation
            return ({"rid": self._by_key[key], "dedup": True,
                     "mut": self._mut}, [])
        prompt = np.frombuffer(blobs[0], np.int64)
        rid = self._sup.submit(
            prompt, max_new_tokens=header["max_new_tokens"],
            stop_sequences=header.get("stop_sequences"),
            deadline_s=header.get("deadline_s"),
            spec=header.get("spec"),
            priority=header.get("priority", "normal"),
            tenant=header.get("tenant"))
        self._mut += 1
        self._remember_key_locked(key, rid)
        if header.get("trace_id") is not None:
            self._trace_ids[rid] = header["trace_id"]
        return ({"rid": rid, "mut": self._mut}, [])

    def _rpc_cancel(self, header, blobs):
        out = bool(self._sup.cancel(int(header["rid"])))
        self._mut += 1
        return ({"cancelled": out, "mut": self._mut}, [])

    def _rpc_sync_buf(self, header):
        """The heartbeat/delivery op, served ENTIRELY from the
        buffer side; CONTRACT: caller holds ``_buf_lock`` (never
        ``_lock`` — see _dispatch).  The snapshot may be one step
        stale; the events are exact and cursor-acked."""
        ack = header.get("ack", -1)
        self._events = [e for e in self._events if e[0] > ack]
        events = [[e[0], e[1], *e[2:]] for e in self._events]
        snap = dict(self._snap_cache)
        snap["events_pending"] = bool(self._events)
        snap["closing"] = self._closing
        return ({"events": events, "snap": snap,
                 "now": time.monotonic()}, [])

    def _rpc_audit(self, header, blobs):
        out = self._sup.engine.cache.audit()
        return ({"audit": {k: int(v) if isinstance(v, (int,
                           np.integer)) else v
                           for k, v in (out or {}).items()}}, [])

    def _rpc_drain(self, header, blobs):
        self._sup.drain()
        self._mut += 1
        return ({"mut": self._mut}, [])

    def _rpc_resume(self, header, blobs):
        self._sup.resume()
        self._mut += 1
        return ({"mut": self._mut}, [])

    def _rpc_shutdown(self, header, blobs):
        if header.get("graceful", True):
            self._closing = True     # drive loop exits once drained
        else:
            self._stop = True
        return ({}, [])

    def _rpc_take_handoffs(self, header, blobs):
        eng = self._sup.engine
        if not hasattr(eng, "take_handoffs"):
            raise RuntimeError(
                f"role {self.role!r} agent has no handoffs to take")
        if self._ho_last is not None:
            if header.get("ack", -1) >= self._ho_seq:
                self._ho_last = None   # delivered: drop the stash
            else:
                # unacked batch (the reply was lost on the wire):
                # re-serve it verbatim — these records already left
                # the engine, so losing the frame must not lose them
                resp, rblobs = self._ho_last
                return dict(resp), list(rblobs)
        recs, degraded, out_blobs, deg_blobs = [], [], [], []
        for rec in eng.take_handoffs():
            d = wire_request(
                rec.request, self._trace_ids.pop(rec.request.rid,
                                                 None))
            try:
                k, v, ks, vs, L = rec.materialize()
            except Exception as e:
                # ship-half failure (kv_handoff fault, staging flush
                # error): reclaim here, let the router degrade the
                # request to a colocated re-prefill — never dropped
                rec.discard()
                meta, blob = pack_array(rec.request.prompt)
                degraded.append({"req": d, "prompt_meta": meta,
                                 "error": f"{type(e).__name__}: {e}"})
                deg_blobs.append(blob)
                continue
            metas = []
            for a in (rec.request.prompt, k, v, ks, vs):
                m, b = pack_array(a)
                metas.append(m)
                out_blobs.append(b)
            recs.append({"req": d, "pages": rec.pages,
                         "nbytes": rec.nbytes, "ctx_len": int(L),
                         "metas": metas})
        self._ho_seq += 1
        resp = {"records": recs, "degraded": degraded,
                "ho_seq": self._ho_seq}
        rblobs = out_blobs + deg_blobs
        if recs or degraded:
            self._ho_last = (resp, rblobs)
        return resp, rblobs

    def _rpc_admit_handoff(self, header, blobs):
        eng = self._sup.engine
        if not hasattr(eng, "admit_handoff"):
            raise RuntimeError(
                f"role {self.role!r} agent cannot adopt a KV handoff")
        key = header.get("key")
        if key is not None and key in self._by_key:
            return ({"rid": self._by_key[key], "dedup": True,
                     "mut": self._mut}, [])
        arrays = [unpack_array(m, b)
                  for m, b in zip(header["metas"], blobs)]
        prompt, k, v, ks, vs = arrays
        src = request_from_wire(header["req"], prompt)
        rec = _WireHandoffRecord(src, (k, v, ks, vs,
                                       header["ctx_len"]),
                                 header["pages"], header["nbytes"])
        rid = eng.admit_handoff(rec)
        self._mut += 1
        self._remember_key_locked(key, rid)
        if header["req"].get("trace_id") is not None:
            self._trace_ids[rid] = header["req"]["trace_id"]
        return ({"rid": rid, "mut": self._mut}, [])

    def _rpc_admit_degraded(self, header, blobs):
        eng = self._sup.engine
        if not hasattr(eng, "admit_degraded"):
            raise RuntimeError(
                f"role {self.role!r} agent cannot admit a degraded "
                f"handoff")
        key = header.get("key")
        if key is not None and key in self._by_key:
            return ({"rid": self._by_key[key], "dedup": True,
                     "mut": self._mut}, [])
        prompt = unpack_array(header["prompt_meta"], blobs[0])
        src = request_from_wire(header["req"], prompt)
        rid = eng.admit_degraded(src)
        self._mut += 1
        self._remember_key_locked(key, rid)
        if header["req"].get("trace_id") is not None:
            self._trace_ids[rid] = header["req"]["trace_id"]
        return ({"rid": rid, "mut": self._mut}, [])

    def _remember_key_locked(self, key, rid) -> None:
        """Record an idempotency key, evicting the oldest past
        ``_KEY_CAP``; CONTRACT: caller holds ``_lock``."""
        if key is None or key in self._by_key:
            return
        self._by_key[key] = rid
        self._key_order.append(key)
        while len(self._key_order) > self._KEY_CAP:
            self._by_key.pop(self._key_order.popleft(), None)

    def _snapshot_locked(self) -> dict:
        """Load/capacity/lifecycle snapshot the handle mirrors;
        CONTRACT: caller holds ``_lock``."""
        sup = self._sup
        eng = sup.engine
        snap = {"active": len(eng._active),
                "queued": len(eng._queue),
                "queued_tokens": eng.queued_tokens(),
                "max_queue_len": eng.max_queue_len,
                "max_queued_tokens": eng.max_queued_tokens,
                "overload_factor": float(getattr(
                    getattr(eng, "policy", None),
                    "overload_factor", 2.0)),
                "has_priorities": bool(getattr(
                    eng, "_has_priorities", False)),
                "retry_after_s": eng.retry_after_s(),
                "decode_steps": eng.decode_steps,
                "tokens_generated": eng.tokens_generated,
                "requests_finished": eng.requests_finished,
                "prefix_hits": int(eng.cache.prefix_hits),
                "restarts": sup.restarts,
                "state": ("DEAD" if self._fatal is not None
                          else sup.state),
                "drained": sup.drained,
                "fatal": self._fatal,
                "mut": self._mut,
                "has_work": sup.has_work()}
        if hasattr(eng, "pending_handoffs"):
            snap["pending_handoffs"] = eng.pending_handoffs()
        if hasattr(eng, "_handoff_ready"):
            snap["handoff_ready"] = len(eng._handoff_ready)
        return snap


# ---------------------------------------------------------------------------
# process spawn (the real multi-process form)
# ---------------------------------------------------------------------------
def _agent_proc_main(spec: dict, q) -> None:
    """Entry point of a spawned agent process: resolve the engine
    factory by import path (closures over device arrays cannot cross
    a process boundary), build the agent, report the bound port, and
    serve until told to stop — or until SIGKILL, which is the point.
    The child runs on the device the SPEC names (``"jax_platforms"``);
    without one it takes jax's default backend, as its parent did —
    it never picks the CPU for itself."""
    if spec.get("jax_platforms"):
        import jax
        jax.config.update("jax_platforms", spec["jax_platforms"])
    mod, _, fn = spec["factory"].partition(":")
    factory_fn = getattr(importlib.import_module(mod), fn)
    kwargs = spec.get("factory_kwargs") or {}
    agent = ReplicaAgent(lambda: factory_fn(**kwargs),
                         **(spec.get("agent_kwargs") or {}))
    try:
        port = agent.start()
    except Exception as e:                    # pragma: no cover
        q.put(("error", f"{type(e).__name__}: {e}"))
        return
    q.put(("ok", port))
    while not agent._stop:
        time.sleep(0.05)


def spawn_agent_process(spec: dict, timeout_s: float = 180.0):
    """Launch a :class:`ReplicaAgent` in a REAL OS process
    (``multiprocessing`` spawn context — a fresh interpreter, no
    inherited JAX state) and return ``(process, (host, port))``.
    ``spec``: ``{"factory": "module:function", "factory_kwargs":
    {...}, "agent_kwargs": {...}, "jax_platforms": "cpu"}`` (the last
    optional: the jax platform the child initialises; a chip belongs
    to one process, so a child of a chip-holding parent must name
    another) — everything JSON-able, because it crosses the process
    boundary.  Kill it with
    ``os.kill(proc.pid, signal.SIGKILL)`` to exercise the real
    failure mode (no atexit, no socket FIN handshake beyond the
    kernel's RST)."""
    import multiprocessing as mp
    import queue as _queue
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    proc = ctx.Process(target=_agent_proc_main, args=(spec, q),
                       daemon=True)
    proc.start()
    try:
        status, payload = q.get(timeout=timeout_s)
    except _queue.Empty:
        # a hung factory (stalled compile/device init): never leak
        # the child, and diagnose instead of surfacing queue.Empty
        proc.terminate()
        raise RuntimeError(
            f"agent process {proc.pid} did not report a port within "
            f"{timeout_s:.0f}s (factory hung?)") from None
    if status != "ok":
        proc.terminate()
        raise RuntimeError(f"agent process failed to start: {payload}")
    host = (spec.get("agent_kwargs") or {}).get("host", "127.0.0.1")
    return proc, (host, int(payload))


# ---------------------------------------------------------------------------
# the fleet-side handle
# ---------------------------------------------------------------------------
@dataclass
class RemoteSpec:
    """How a :class:`~paddle_tpu.fleet.FleetRouter` reaches one
    remote replica.  Exactly one of:

    * ``agent`` — zero-arg callable returning an UNSTARTED
      :class:`ReplicaAgent` (in-thread mode: a real localhost socket,
      no process spawn — the CPU-smoke and test workhorse; replace()
      builds a fresh agent from the same callable);
    * ``spawn`` — a :func:`spawn_agent_process` spec (real OS
      process; replace() re-spawns);
    * ``connect`` — ``(host, port)`` of an externally managed agent
      (replace() re-dials the same address).
    """

    agent: Optional[Callable] = None
    spawn: Optional[dict] = None
    connect: Optional[Tuple[str, int]] = None
    role: Optional[str] = None
    lease_s: float = 2.0
    rpc_timeout_s: float = 5.0
    # engine-touching ops (submit / cancel / handoff admission /
    # audit / lifecycle) serialize on the agent's engine lock, which
    # a first jit COMPILE can hold for minutes — they get their own,
    # much longer per-attempt budget so an aggressive heartbeat
    # timeout (tuned for liveness) cannot starve a placement behind
    # a compiling-but-healthy engine.  None = max(rpc_timeout_s, 60)
    data_timeout_s: Optional[float] = None
    max_retries: int = 3
    backoff_s: float = 0.01
    heartbeat_s: Optional[float] = None    # default: lease_s / 3
    jitter_seed: int = 0
    is_remote_spec: bool = field(default=True, repr=False)

    def __post_init__(self):
        if sum(x is not None
               for x in (self.agent, self.spawn, self.connect)) != 1:
            raise ValueError(
                "RemoteSpec needs exactly one of agent= (in-thread), "
                "spawn= (process), connect= ((host, port))")


class _Sized:
    """``len()``-only stand-in for a remote engine's containers (the
    router only ever sizes them; iteration is meaningless across a
    process boundary)."""

    __slots__ = ("n",)

    def __init__(self, n):
        self.n = int(n or 0)

    def __len__(self) -> int:
        return self.n


class _RemoteCache:
    def __init__(self, h: "RemoteReplicaHandle"):
        self._h = h
        self.page = h.page
        # geometry mirrored from the hello handshake: the router's
        # cost model and row-capacity guards price a remote lane
        # exactly like an in-process one
        self.pages_max = h.hello.get("pages_max", 1)
        self.num_pages = h.hello.get("num_pages", 2)
        self.page_bytes = h.hello.get("page_bytes", 1)

    @property
    def prefix_hits(self) -> int:
        return int(self._h.snap.get("prefix_hits", 0))

    def audit(self) -> dict:
        """Remote page-accounting audit: the agent runs the REAL
        ``PagedKVCache.audit()`` and ships the result — an invariant
        violation raises there and surfaces here."""
        resp, _ = self._h.conn.call("audit", idempotent=True,
                                    timeout=self._h.data_timeout_s)
        return resp["audit"]


class _RemoteEngine:
    """Snapshot-backed mirror of the engine attributes the router
    reads (≤ one fleet tick stale; every VERDICT that matters —
    backpressure, admission — is re-checked authoritatively on the
    agent when the actual RPC lands)."""

    metrics = None                         # no in-process instruments

    def __init__(self, h: "RemoteReplicaHandle"):
        self._h = h
        self.cache = _RemoteCache(h)
        # cost-model mirror (handoff_wins reads these): set from
        # hello so the verdict never needs the remote params tree
        self._n_params = h.hello.get("n_params") or None
        self._mixed = bool(h.hello.get("mixed", False))

    # -- sized containers -------------------------------------------------
    @property
    def _active(self):
        return _Sized(self._h.snap.get("active"))

    @property
    def _queue(self):
        return _Sized(self._h.snap.get("queued"))

    # -- host counters ----------------------------------------------------
    @property
    def B(self) -> int:
        return self._h.B

    @property
    def decode_steps(self) -> int:
        return int(self._h.snap.get("decode_steps", 0))

    @property
    def tokens_generated(self) -> int:
        return int(self._h.snap.get("tokens_generated", 0))

    @property
    def requests_finished(self) -> int:
        return int(self._h.snap.get("requests_finished", 0))

    def queued_tokens(self) -> int:
        return int(self._h.snap.get("queued_tokens", 0))

    def retry_after_s(self) -> float:
        return float(self._h.snap.get("retry_after_s", 1.0))

    def queue_capacity_reason(self, prompt_len: int = 0,
                              factor: float = 1.0,
                              priority: Optional[str] = None,
                              ) -> Optional[str]:
        """The engine's backpressure predicate over the mirrored
        counters — same arithmetic, ≤ one tick stale; ``submit()``
        re-checks on the agent, so a stale None costs one steered
        retry, never an over-admission.  Mirrors the class-aware
        form: a non-shed class probes against the agent's hard bound
        (``overload_factor`` rides the snapshot; the agent-side shed
        policy stays authoritative)."""
        snap = self._h.snap
        if priority is not None and priority != "low" and \
                (snap.get("has_priorities") or priority != "normal"):
            factor = max(factor,
                         float(snap.get("overload_factor", 2.0)))
        mql = snap.get("max_queue_len")
        if mql is not None and \
                snap.get("queued", 0) >= int(mql * factor):
            return (f"admission queue full: {snap.get('queued')} "
                    f"waiting >= max_queue_len {int(mql * factor)}")
        mqt = snap.get("max_queued_tokens")
        if mqt is not None:
            bound = int(mqt * factor)
            waiting = snap.get("queued_tokens", 0)
            need = max(int(prompt_len), 1)
            if waiting + need > bound:
                return (f"queued tokens {waiting} + prompt {need} "
                        f"> max_queued_tokens {bound}")
        return None


class _RemotePrefillEngine(_RemoteEngine):
    @property
    def _handoff_ready(self):
        return _Sized(self._h.snap.get("handoff_ready"))

    def take_handoffs(self) -> List[_WireHandoffRecord]:
        """Drain the agent's exported records over the wire.  The
        blobs arrive MATERIALIZED (the ship half ran on the agent,
        its fault site included); source-side ship failures come
        back as poisoned records the router's existing degrade path
        turns into colocated re-prefills.  Batch-acked so a reply
        lost to a connection drop re-serves the SAME records on the
        retry — taking is destructive on the agent, and an unacked
        batch is the only copy of its KV blobs."""
        h = self._h
        resp, blobs = h.conn.call("take_handoffs",
                                  {"ack": h.ho_ack}, idempotent=True,
                                  timeout=h.data_timeout_s)
        h.ho_ack = int(resp.get("ho_seq", h.ho_ack))
        out: List[_WireHandoffRecord] = []
        it = iter(blobs)
        for rec in resp["records"]:
            arrays = [unpack_array(m, next(it))
                      for m in rec["metas"]]
            prompt, k, v, ks, vs = arrays
            req = request_from_wire(rec["req"], prompt)
            out.append(_WireHandoffRecord(
                req, (k, v, ks, vs, rec["ctx_len"]), rec["pages"],
                rec["nbytes"]))
        for d in resp["degraded"]:
            prompt = unpack_array(d["prompt_meta"], next(it))
            req = request_from_wire(d["req"], prompt)
            out.append(_WireHandoffRecord(req, None, 0, 0,
                                          poisoned=d["error"]))
        if out:
            h.supervisor.mark_dirty()
        return out


class _RemoteDecodeEngine(_RemoteEngine):
    def pending_handoffs(self) -> int:
        return int(self._h.snap.get("pending_handoffs", 0))

    def admit_handoff(self, rec) -> int:
        """Ship a record's blobs to the agent and adopt them there
        (the restore-half ``kv_handoff`` fault fires on the AGENT).
        Idempotent: keyed on the source rid, a retried frame returns
        the original decode-local rid."""
        h = self._h
        k, v, ks, vs, L = rec.materialize()
        metas, blobs = [], []
        for a in (rec.request.prompt, k, v, ks, vs):
            m, b = pack_array(a)
            metas.append(m)
            blobs.append(b)
        trace_id = None
        if rec.request.trace is not None:
            trace_id = rec.request.trace.trace_id
        header = {"req": wire_request(rec.request, trace_id),
                  "pages": rec.pages, "nbytes": rec.nbytes,
                  "ctx_len": int(L), "metas": metas,
                  "key": f"{h.client_id}:h{rec.request.rid}"}
        resp, _ = h.conn.call("admit_handoff", header, blobs,
                              idempotent=True,
                              timeout=h.data_timeout_s)
        rid = int(resp["rid"])
        h.prompts[rid] = np.asarray(rec.request.prompt, np.int64)
        h.note_mut(resp)
        h.supervisor.mark_dirty()
        return rid

    def admit_degraded(self, src: Request) -> int:
        h = self._h
        meta, blob = pack_array(src.prompt)
        trace_id = src.trace.trace_id if src.trace is not None \
            else None
        header = {"req": wire_request(src, trace_id),
                  "prompt_meta": meta,
                  "key": f"{h.client_id}:d{src.rid}"}
        resp, _ = h.conn.call("admit_degraded", header, [blob],
                              idempotent=True,
                              timeout=h.data_timeout_s)
        rid = int(resp["rid"])
        h.prompts[rid] = np.asarray(src.prompt, np.int64)
        h.note_mut(resp)
        h.supervisor.mark_dirty()
        return rid


class _RemoteSupervisor:
    """The handle's supervisor-shaped face to the router: submits,
    cancels and the per-tick sync all translate to RPCs; lifecycle
    verbs ride the wire; liveness failures surface exactly where the
    router already looks (a raised exception from ``step()``)."""

    def __init__(self, h: "RemoteReplicaHandle"):
        self._h = h
        self._dirty = False        # unsynced mutation: sync soon
        self._nsub = 0

    # -- placement --------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 64,
               stop_sequences=None, deadline_s=None, trace=None,
               fleet_rid=None, spec=None, priority="normal",
               tenant=None) -> int:
        h = self._h
        prompt = np.ascontiguousarray(np.asarray(prompt, np.int64))
        self._nsub += 1
        key_part = fleet_rid if fleet_rid is not None \
            else f"s{self._nsub}"
        header = {"max_new_tokens": int(max_new_tokens),
                  "stop_sequences": stop_sequences,
                  "deadline_s": deadline_s,
                  "spec": spec,
                  "priority": priority,
                  "tenant": tenant,
                  "key": f"{h.client_id}:{key_part}",
                  "trace_id": trace.trace_id
                  if trace is not None else None}
        deadline = None if deadline_s is None \
            else time.monotonic() + float(deadline_s)
        resp, _ = h.conn.call("submit", header, [prompt.data],
                              idempotent=True, deadline=deadline,
                              timeout=h.data_timeout_s)
        rid = int(resp["rid"])
        h.prompts[rid] = prompt
        h.pending_since_sync += 1
        h.note_mut(resp)
        self._dirty = True
        return rid

    def cancel(self, rid: int) -> bool:
        try:
            resp, _ = self._h.conn.call(
                "cancel", {"rid": int(rid)}, idempotent=True,
                timeout=self._h.data_timeout_s)
        except TransportError:
            # the router keeps its own cancelled mark: if the agent
            # is gone, death triage honours it; if merely degraded,
            # the retry next tick does
            return False
        self._h.note_mut(resp)
        self._dirty = True
        return bool(resp["cancelled"])

    def mark_dirty(self) -> None:
        self._dirty = True

    # -- the fleet tick ---------------------------------------------------
    def step(self) -> int:
        h = self._h
        if faults.active("agent_kill"):
            # chaos: SIGKILL the agent process (or tear down the
            # in-thread one) RIGHT NOW — the sync below then fails
            # and the lease machinery takes over
            h.hard_kill_agent("agent_kill fault")
        try:
            resp, _ = h.conn.call("sync", {"ack": h.cursor},
                                  idempotent=True)
        except TransportError as e:
            if h.conn.lease_expired():
                raise LeaseExpiredError(
                    f"replica {h.idx} lease expired "
                    f"({h.conn.lease_age():.2f}s since last "
                    f"successful round-trip > lease "
                    f"{h.conn.lease_s:.2f}s): {e}") from e
            # a missed heartbeat, not yet a death: DEGRADED steers
            # routing away while the lease still has headroom
            if h.state == "READY":
                h.state = "DEGRADED"
            return int(h.snap.get("active", 0))
        h.apply_sync(resp)
        self._dirty = False
        if not resp["events"] and h.snap.get("has_work"):
            # the agent is computing (possibly a first COMPILE) and
            # nothing new arrived: pace the poll instead of letting a
            # tight drive loop burn its step budget on empty syncs
            time.sleep(0.002)
        if h.snap.get("fatal"):
            # the agent's ENGINE died past its restart budget — the
            # process answers, but nothing behind it can serve
            raise EngineDeadError(
                f"remote engine dead: {h.snap['fatal']}")
        return int(h.snap.get("active", 0))

    def has_work(self) -> bool:
        h = self._h
        if self._dirty or h.stream_buf or h.finished_buf:
            return True
        if h.mut_sent > h.mut_seen:
            # an acked mutation the synced snapshot predates: the
            # agent HAS the work even if the (one-iteration-stale)
            # snapshot can't show it yet
            return True
        if h.snap.get("has_work") or h.snap.get("events_pending"):
            return True
        # heartbeat: an idle replica still needs periodic contact or
        # its lease goes stale without meaning — due-ness IS work
        return (time.monotonic() - h.last_sync) >= h.heartbeat_s

    def finished(self) -> List[Request]:
        h = self._h
        out, h.finished_buf = h.finished_buf, []
        return out

    def drain_stream(self) -> List:
        h = self._h
        out, h.stream_buf = h.stream_buf, []
        return out

    # -- lifecycle verbs --------------------------------------------------
    def drain(self) -> None:
        resp, _ = self._h.conn.call("drain", idempotent=True,
                                    timeout=self._h.data_timeout_s)
        self._h.note_mut(resp)

    def resume(self) -> None:
        resp, _ = self._h.conn.call("resume", idempotent=True,
                                    timeout=self._h.data_timeout_s)
        self._h.note_mut(resp)

    @property
    def drained(self) -> bool:
        h = self._h
        return (bool(h.snap.get("drained"))
                and h.mut_seen >= h.mut_sent
                and not h.snap.get("events_pending")
                and not h.stream_buf and not h.finished_buf)

    @property
    def restarts(self) -> int:
        return int(self._h.snap.get("restarts", 0))

    @property
    def engine(self):
        return self._h.engine


class RemoteReplicaHandle:
    """Drop-in sibling of :class:`~paddle_tpu.fleet.router.
    ReplicaHandle` whose engine lives behind a socket.  Same
    surface — ``state``/``load()``/``kill()``/``replace()``/
    ``drain()``/``local_rids`` — so every router decision (routing,
    fleet-wide admission, failover, drain-and-replace, handoff
    shipping) applies unchanged; all access runs under the router's
    lock, like the in-process handle."""

    remote = True
    retiring = False    # scale-down mark (see ReplicaHandle.retiring)

    def __init__(self, idx: int, spec: RemoteSpec, *,
                 role: Optional[str] = None, metrics=None):
        self.idx = idx
        self.spec = spec
        self.role = spec.role or role or "unified"
        self.state = "STARTING"
        self.error: Optional[str] = None
        self.deaths = 0
        self.replaces = 0
        self.drains = 0
        self.slow_ticks = 0
        self.local_rids: Dict[int, int] = {}
        self.transport_metrics = metrics
        # idempotency namespace: one client identity per handle
        # LIFETIME (a replace() re-mints it — a rebuilt agent has a
        # fresh dedup table anyway, and a stale key must never alias)
        self.client_id = uuid.uuid4().hex[:12]
        self.heartbeat_s = spec.heartbeat_s \
            if spec.heartbeat_s is not None else spec.lease_s / 3.0
        self.data_timeout_s = spec.data_timeout_s \
            if spec.data_timeout_s is not None \
            else max(spec.rpc_timeout_s, 60.0)
        self.snap: dict = {}
        self.cursor = -1
        self.last_sync = 0.0
        # mutation accounting: `mut_sent` is the highest agent
        # mutation counter any acked RPC carried, `mut_seen` the
        # counter of the last synced snapshot — until they agree the
        # replica HAS WORK by definition (the snapshot predates a
        # mutation we know landed), so a drive loop can never go
        # idle between a submit and the snapshot that reflects it
        self.mut_sent = 0
        self.mut_seen = 0
        self.ho_ack = -1           # take_handoffs batch cursor
        # placements since the last sync: the snapshot cannot see
        # them yet, so load() adds them or every submit in a wave
        # would pile onto the same "empty" replica
        self.pending_since_sync = 0
        self.stream_buf: List = []
        self.finished_buf: List[Request] = []
        self.prompts: Dict[int, np.ndarray] = {}
        self._agent: Optional[ReplicaAgent] = None   # in-thread mode
        self._proc = None                            # process mode
        self.conn: Optional[Connection] = None
        self.hello: dict = {}
        self.page = 0
        self.B = 1
        self.caps: dict = {}
        self._clock_off = 0.0
        self.supervisor = _RemoteSupervisor(self)
        self.engine: _RemoteEngine = _RemoteEngine(self)
        self._spawn_and_connect()
        self.state = "READY"

    # -- connect / spawn --------------------------------------------------
    def _halt_backend(self) -> None:
        """Put whatever agent THIS handle started down and forget it
        (an externally managed ``connect=`` peer is not ours to
        stop); connection teardown is the caller's job."""
        if self._agent is not None:
            self._agent.die()
            self._agent = None
        if self._proc is not None:
            if self._proc.is_alive():
                self._proc.terminate()
            self._proc = None

    def _spawn_and_connect(self) -> None:
        spec = self.spec
        if spec.agent is not None:
            self._agent = spec.agent()
            port = self._agent.start()
            addr = (self._agent.host, port)
        elif spec.spawn is not None:
            self._proc, addr = spawn_agent_process(spec.spawn)
        else:
            addr = spec.connect
        try:
            conn = open_connection(
                addr, timeout_s=spec.rpc_timeout_s,
                lease_s=spec.lease_s,
                max_retries=spec.max_retries,
                backoff_s=spec.backoff_s,
                jitter_seed=spec.jitter_seed,
                metrics=self.transport_metrics)
            try:
                resp, _ = conn.call("hello", idempotent=True,
                                    timeout=self.data_timeout_s)
            except BaseException:
                conn.close()
                raise
        except BaseException:
            # a failed dial/handshake must not leak the agent it
            # just started (one OS process / listener FD per failed
            # construction or replace retry, forever)
            self._halt_backend()
            raise
        self.conn = conn
        self.hello = resp
        self.page = int(resp["page"])
        self.B = int(resp["B"])
        self.caps = resp.get("caps", {})
        self._clock_off = time.monotonic() - resp["now"]
        agent_role = resp.get("role", "unified")
        if agent_role != self.role:
            self.role = agent_role if spec.role is None else self.role
        if self.caps.get("prefill"):
            self.engine = _RemotePrefillEngine(self)
        elif self.caps.get("decode"):
            self.engine = _RemoteDecodeEngine(self)
        else:
            self.engine = _RemoteEngine(self)
        self.snap = {}
        self.cursor = -1
        self.mut_sent = 0
        self.mut_seen = 0
        self.ho_ack = -1
        self.last_sync = time.monotonic()

    def note_mut(self, resp: dict) -> None:
        """Record the agent mutation counter an RPC response carried
        (see ``mut_sent`` above)."""
        self.mut_sent = max(self.mut_sent, int(resp.get("mut") or 0))

    def set_transport_metrics(self, metrics) -> None:
        self.transport_metrics = metrics
        if self.conn is not None:
            self.conn.metrics = metrics

    # -- sync bookkeeping -------------------------------------------------
    def apply_sync(self, resp: dict) -> None:
        off = time.monotonic() - resp["now"]
        for ev in resp["events"]:
            seq = ev[0]
            if seq <= self.cursor:
                continue               # re-served after a lost reply
            self.cursor = seq
            if ev[1] == "tok":
                self.stream_buf.append((int(ev[2]), int(ev[3])))
            else:
                d = ev[2]
                prompt = self.prompts.pop(int(d["rid"]), None)
                if prompt is None:
                    prompt = np.zeros(0, np.int64)
                req = request_from_wire(d, prompt)
                self.finished_buf.append(req)
        self.snap = resp["snap"]
        self.mut_seen = int(self.snap.get("mut") or 0)
        self.last_sync = time.monotonic()
        self.pending_since_sync = 0
        self._clock_off = off
        if self.state == "DEGRADED":
            self.state = "READY"

    # -- router-facing surface -------------------------------------------
    def load(self):
        return (int(self.snap.get("active", 0))
                + int(self.snap.get("queued", 0))
                + self.pending_since_sync,
                int(self.snap.get("queued_tokens", 0)))

    @property
    def admitting(self) -> bool:
        return self.state in ("READY", "DEGRADED")

    def hard_kill_agent(self, why: str) -> None:
        """SIGKILL (process mode) / abrupt teardown (in-thread mode)
        of the agent — no drain, no FIN handshake beyond the
        kernel's.  The lease machinery discovers the death; this
        method never touches the handle's own state."""
        if self._proc is not None and self._proc.is_alive():
            try:
                os.kill(self._proc.pid, signal.SIGKILL)
            except OSError:
                pass
        if self._agent is not None:
            self._agent.die()

    def kill(self, error: str) -> None:
        """Mark DEAD after a lease expiry / escaped failure: close
        the connection (lease-expire form), put the agent down hard
        (a half-dead peer must not keep generating for clients that
        failed over), clear the rid map.  The router triages the
        orphaned requests before calling this."""
        self.state = "DEAD"
        self.error = error
        self.deaths += 1
        orphan_rids = list(self.local_rids)
        if self.conn is not None:
            if self.conn.lease_expired():
                self.conn.lease_expire()
            else:
                self.conn.close()
        self.hard_kill_agent(error)
        if (self._agent is None and self._proc is None
                and self.spec.connect is not None and orphan_rids):
            # an externally managed peer is not ours to SIGKILL — the
            # closest honest substitute for "put it down" is a
            # best-effort cancel sweep over a fresh short-timeout
            # dial, so a peer that was merely PARTITIONED does not
            # keep generating for clients that already failed over
            # (connect-mode replaces also keep the client id, so a
            # re-placed rid that lands back here dedups instead of
            # double-generating)
            self._cancel_remote_orphans(orphan_rids)
        self.local_rids.clear()
        self.stream_buf = []
        self.finished_buf = []
        self.prompts.clear()
        self.snap = {}
        self.pending_since_sync = 0
        self.mut_sent = 0
        self.mut_seen = 0
        self.ho_ack = -1

    def _cancel_remote_orphans(self, rids) -> None:
        """Best-effort cancel of a dead-to-us external agent's
        orphaned local rids (see :meth:`kill`): one quick dial, one
        cancel per rid, swallow everything — a genuinely dead or
        unreachable peer makes this a fast no-op."""
        try:
            conn = open_connection(
                self.spec.connect,
                timeout_s=min(1.0, self.spec.rpc_timeout_s),
                max_retries=0)
        except Exception:
            return                   # nothing acquired, nothing owed
        try:
            for rid in rids:
                conn.call("cancel", {"rid": int(rid)},
                          idempotent=True)
        except Exception:
            pass
        finally:
            conn.close()

    def replace(self) -> None:
        """Rebuild: tear down whatever is left, re-spawn/re-dial a
        fresh agent.  A failed respawn leaves the handle DEAD with
        the error recorded — ``auto_replace`` retries next tick
        instead of killing the router step."""
        self.state = "STARTING"
        self.local_rids.clear()
        self.stream_buf = []
        self.finished_buf = []
        self.prompts.clear()
        self.pending_since_sync = 0
        if self.conn is not None:
            self.conn.close()
            self.conn = None
        self._halt_backend()
        if self.spec.connect is None:
            # a rebuilt agent starts with a fresh dedup table, so the
            # namespace re-mints; a CONNECT-mode replace re-dials the
            # SAME (surviving) agent — keeping the client id means a
            # re-placed fleet rid still dedups against a generation
            # the peer may have kept running through the partition
            self.client_id = uuid.uuid4().hex[:12]
        try:
            self._spawn_and_connect()
        except Exception as e:
            self.error = (f"replace failed: "
                          f"{type(e).__name__}: {e}")
            self.state = "DEAD"
            return
        self.replaces += 1
        self.error = None
        self.state = "READY"

    def drain(self) -> None:
        try:
            self.supervisor.drain()
        except TransportError:
            pass          # degraded/dead: the tick machinery decides
        self.state = "DRAINING"
        self.drains += 1

    @property
    def drained(self) -> bool:
        return self.state == "DRAINING" and self.supervisor.drained

    def retire(self) -> None:
        """Terminal scale-down for a socket replica: shut the
        (already drained) agent down, close the connection, park the
        handle in RETIRED.  Teardown is best-effort — a retiring
        replica that died first has nothing left to shut down."""
        self.state = "RETIRED"
        self.retiring = False
        try:
            self.shutdown_agent(graceful=True)
        except Exception:
            pass
        if self.conn is not None:
            try:
                self.conn.close()
            except Exception:
                pass
            self.conn = None
        self._halt_backend()
        self.local_rids.clear()

    def shutdown_agent(self, graceful: bool = True) -> None:
        """Ask the agent to exit — gracefully (finish in-flight
        streams, wait for the last ack) or immediately."""
        self.conn.call("shutdown", {"graceful": graceful},
                       idempotent=True, timeout=self.data_timeout_s)

    def transport_snapshot(self) -> dict:
        """Per-replica transport health for ``/fleet``."""
        c = self.conn
        out = {"mode": ("thread" if self._agent is not None else
                        "process" if self._proc is not None
                        else "connect"),
               "lease_s": self.spec.lease_s}
        if self._proc is not None:
            out["agent_pid"] = self._proc.pid
        if c is not None:
            out.update(addr=list(c.addr),
                       reconnects=c.reconnects, retries=c.retries,
                       heartbeat_misses=c.heartbeat_misses,
                       frames=c.frames,
                       bytes_sent=c.bytes_sent,
                       bytes_recv=c.bytes_recv,
                       lease_age_s=round(c.lease_age(), 3))
        return out
