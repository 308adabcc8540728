"""Sockets transport for multi-process fleets (paddle_tpu/fleet/
transport.py + remote.py) — framing, leases, retries, idempotency,
and chaos-proof failover over a real wire.

Contract under test:
* the frame protocol round-trips numpy KV blobs BITWISE (fp pools and
  int8 scale planes alike) and fails loudly on bad magic / truncation
  (`ProtocolError`), never guessing at a resync point;
* a fleet of `RemoteReplicaHandle`s — real TCP sockets to in-thread /
  spawned-process `ReplicaAgent`s — serves the same request set
  TOKEN-EXACT vs the in-process fleet (the PR-8 oracle), including a
  disaggregated prefill→decode handoff whose blobs cross the wire;
* delivery is cursor-acked (a reply lost to a connection drop is
  re-served, duplicates discarded) and submission is IDEMPOTENT
  (keyed on the fleet rid): a retried submit after an ambiguous
  timeout can never double-generate;
* liveness is lease-based: a missed heartbeat degrades (routing
  steers around), an expired lease is a DEATH that rides the
  router's existing failover path — zero-streamed victims re-place
  token-exact with rid and absolute deadline intact, mid-stream ones
  error honestly, `PagedKVCache.audit()` clean on every survivor;
* seeded `conn_drop` / `frame_truncate` / `net_delay` / `agent_kill`
  schedules — plus one REAL `SIGKILL` of an agent process mid-decode
  — never silently drop a request;
* graceful agent shutdown finishes in-flight streams before exiting.

In-thread agents speak over real localhost sockets (`RemoteSpec
(agent=...)`); the SIGKILL test spawns a real OS process.
"""

import json
import os
import signal
import socket
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.fleet import (FleetRouter, FleetServer, ReplicaAgent,
                              RemoteSpec)
from paddle_tpu.fleet.remote import arm_fault_spec, request_from_wire, \
    wire_request
from paddle_tpu.fleet.transport import (Connection, ProtocolError,
                                        TransportError, open_connection,
                                        pack_array, recv_frame,
                                        send_frame, unpack_array)
from paddle_tpu.models.disagg import DecodeEngine, PrefillEngine
from paddle_tpu.models.llama_pretrain import (LlamaPretrainConfig,
                                              init_params)
from paddle_tpu.models.paged_decode import PagedKVCache
from paddle_tpu.models.serving_engine import (ContinuousBatchingEngine,
                                              Request)
from paddle_tpu.observability import MetricsRegistry
from paddle_tpu.testing import faults

WORKERS = os.path.join(os.path.dirname(__file__), "workers")


@pytest.fixture(scope="module")
def cfg():
    return LlamaPretrainConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_seq_len=256, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False, loss_chunks=1,
        use_pallas_attention=False)


@pytest.fixture(scope="module")
def params(cfg):
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "pp", "sharding", "sep", "mp"))
    return init_params(cfg, jax.random.PRNGKey(0), mesh)


_RNG = np.random.RandomState(7)
_PROMPTS = [_RNG.randint(1, 128, (L,)) for L in (10, 21, 8, 17)]


def _factory(cfg, params, engine_cls=ContinuousBatchingEngine, **kw):
    def mk():
        cache_kw = dict(num_pages=64, pages_max=8, batch=2, page=16)
        for k in ("num_pages", "pages_max", "batch", "page",
                  "host_pages", "kv_quant"):
            if k in kw:
                cache_kw[k] = kw.pop(k)
        cache = PagedKVCache(cfg, **cache_kw)
        return engine_cls(cfg, params, cache, metrics_registry=False,
                          **kw)
    return mk


_REF = {}


def _ref(cfg, params, prompts, new=6, kv_quant=None):
    key = (tuple(tuple(p) for p in prompts), new, kv_quant)
    if key not in _REF:
        mk = _factory(cfg, params, kv_quant=kv_quant) \
            if kv_quant else _factory(cfg, params)
        eng = mk()
        rids = [eng.submit(p, max_new_tokens=new) for p in prompts]
        done = {r.rid: list(r.generated)
                for r in eng.run_to_completion()}
        _REF[key] = [done[r] for r in rids]
    return _REF[key]


def _spec(cfg, params, *, lease=2.0, timeout=5.0, retries=3,
          backoff=0.01, role="unified", engine_cls=None, seed=0,
          **ekw):
    mk = _factory(cfg, params,
                  engine_cls=engine_cls or ContinuousBatchingEngine,
                  **ekw)
    return RemoteSpec(
        agent=lambda: ReplicaAgent(mk, role=role, lease_s=lease),
        role=role, lease_s=lease, rpc_timeout_s=timeout,
        max_retries=retries, backoff_s=backoff, jitter_seed=seed)


def _teardown(router):
    for h in router._replicas:
        if getattr(h, "_agent", None) is not None:
            h._agent.die()
        if getattr(h, "_proc", None) is not None and \
                h._proc.is_alive():
            h._proc.terminate()


def _audit_all(router):
    for h in router._replicas:
        if h.state in ("READY", "DEGRADED", "DRAINING"):
            h.engine.cache.audit()


# ---------------------------------------------------------------------------
# frame layer: bitwise blobs, loud protocol failures
# ---------------------------------------------------------------------------
def test_pack_unpack_bitwise_all_dtypes():
    rng = np.random.RandomState(0)
    arrays = [
        rng.standard_normal((2, 3, 4)).astype(np.float32),
        rng.standard_normal((5,)).astype(np.float16),
        rng.randint(-128, 127, (3, 7), dtype=np.int8),
        rng.randint(0, 1 << 40, (4,), dtype=np.int64),
        np.asfortranarray(rng.standard_normal((6, 6))),  # non-contig
        None,                       # an fp pool's absent scale plane
    ]
    for a in arrays:
        meta, buf = pack_array(a)
        b = unpack_array(meta, buf)
        if a is None:
            assert b is None
            continue
        assert b.dtype == a.dtype and b.shape == a.shape
        assert b.tobytes() == np.ascontiguousarray(a).tobytes(), \
            "wire round-trip must be bitwise"


def test_frame_roundtrip_over_socketpair():
    a, b = socket.socketpair()
    try:
        blobs = [np.arange(17, dtype=np.int64).data, b"xyz",
                 np.float32([1.5, -2.25]).data]
        header = {"op": "probe", "seq": 3, "nested": {"k": [1, 2]}}
        sent = send_frame(a, header, blobs)
        got, rblobs, read = recv_frame(b)
        assert got == header and read == sent
        assert bytes(rblobs[0]) == bytes(blobs[0])
        assert bytes(rblobs[1]) == b"xyz"
        assert np.array_equal(
            unpack_array({"dtype": "<f4", "shape": [2]}, rblobs[2]),
            np.float32([1.5, -2.25]))
    finally:
        a.close()
        b.close()


def test_frame_bad_magic_and_truncation_raise_protocol_error():
    a, b = socket.socketpair()
    try:
        a.sendall(b"JUNKxxxxxxxxxxxx")
        a.close()
        with pytest.raises(ProtocolError, match="magic"):
            recv_frame(b)
    finally:
        b.close()
    a, b = socket.socketpair()
    try:
        send_frame(a, {"op": "x"}, [b"0123456789"])
        # a second frame cut mid-payload: the reader of frame 2 sees
        # the close mid-frame, never a silent short read
        hdr = json.dumps({"op": "y"}).encode()
        import struct
        pre = struct.pack("<4sII", b"PTF1", len(hdr), 1) + \
            struct.pack("<Q", 100) + hdr
        a.sendall(pre + b"short")
        a.close()
        recv_frame(b)                       # frame 1 intact
        with pytest.raises(ProtocolError, match="mid-frame"):
            recv_frame(b)
    finally:
        b.close()


def test_wire_request_shifts_clocks_preserves_structure():
    req = Request(5, np.arange(4, dtype=np.int64), 8,
                  t_submit=time.monotonic() - 3.0,
                  deadline=time.monotonic() + 7.0)
    req.generated = [1, 2, 3]
    req.status = "ok"
    req.phase_log = [("queued", time.monotonic() - 3.0,
                      time.monotonic() - 2.0)]
    d = json.loads(json.dumps(wire_request(req)))   # wire-safe JSON
    back = request_from_wire(d, req.prompt)
    assert back.rid == 5 and back.generated == [1, 2, 3]
    assert back.status == "ok"
    now = time.monotonic()
    assert abs((back.deadline - now) - (req.deadline - now)) < 0.05, \
        "deadline headroom must survive the hop"
    (p, t0, t1), = back.phase_log
    assert p == "queued" and abs((t1 - t0) - 1.0) < 0.05


# ---------------------------------------------------------------------------
# one agent, one connection: RPC semantics
# ---------------------------------------------------------------------------
def test_agent_rpc_end_to_end_cursor_delivery(cfg, params):
    ref = _ref(cfg, params, _PROMPTS[:1])
    agent = ReplicaAgent(_factory(cfg, params), lease_s=5.0)
    port = agent.start()
    conn = open_connection(("127.0.0.1", port))
    try:
        hello, _ = conn.call("hello", idempotent=True)
        assert hello["role"] == "unified"
        assert hello["pid"] == os.getpid()      # in-thread agent
        assert hello["page"] == 16 and hello["B"] == 2
        assert hello["n_params"] > 0 and hello["page_bytes"] > 0
        prompt = np.ascontiguousarray(_PROMPTS[0].astype(np.int64))
        resp, _ = conn.call("submit",
                            {"max_new_tokens": 6, "key": "c:0"},
                            [prompt.data], idempotent=True)
        rid = resp["rid"]
        toks, fin, t0 = [], None, time.monotonic()
        ack = -1
        while fin is None:
            assert time.monotonic() - t0 < 60.0
            resp, _ = conn.call("sync", {"ack": ack},
                                idempotent=True)
            for ev in resp["events"]:
                ack = ev[0]
                if ev[1] == "tok":
                    assert ev[2] == rid
                    toks.append(ev[3])
                else:
                    fin = ev[2]
            time.sleep(0.005)
        assert fin["rid"] == rid and fin["status"] == "ok"
        assert fin["generated"] == ref[0] and toks == ref[0]
        # an UN-acked re-sync re-serves nothing new but the buffer
        # only prunes what was acked: replaying with an older cursor
        # re-serves the same events (at-least-once wire, the cursor
        # filter on the handle makes delivery exactly-once)
        resp, _ = conn.call("sync", {"ack": -1}, idempotent=True)
        assert [ev[2] for ev in resp["events"]
                if ev[1] == "fin"] == [fin]
        audit, _ = conn.call("audit", idempotent=True)
        assert audit["audit"].get("leaked_pages", 0) == 0
    finally:
        conn.close()
        agent.die()


def test_ambiguous_timeout_retry_never_double_generates(cfg, params):
    """THE idempotency pin: a submit frame that LANDED but whose
    reply was lost (the ambiguous-timeout case) is retried with the
    same key — the agent's dedup table answers with the original
    placement and exactly one generation runs."""
    agent = ReplicaAgent(_factory(cfg, params), lease_s=5.0)
    port = agent.start()
    addr = ("127.0.0.1", port)
    prompt = np.ascontiguousarray(_PROMPTS[0].astype(np.int64))
    raw = socket.create_connection(addr)
    send_frame(raw, {"op": "submit", "seq": 1, "max_new_tokens": 6,
                     "key": "cli:r1"}, [prompt.data])
    raw.close()                    # reply lost: outcome ambiguous
    t0 = time.monotonic()
    while "cli:r1" not in agent._by_key:     # the frame DID land
        assert time.monotonic() - t0 < 30.0
        time.sleep(0.005)
    first_rid = agent._by_key["cli:r1"]
    conn = open_connection(addr)
    try:
        resp, _ = conn.call("submit",
                            {"max_new_tokens": 6, "key": "cli:r1"},
                            [prompt.data], idempotent=True)
        assert resp["rid"] == first_rid and resp.get("dedup")
        t0 = time.monotonic()
        while True:
            assert time.monotonic() - t0 < 60.0
            s, _ = conn.call("sync", {"ack": -1}, idempotent=True)
            fins = [ev for ev in s["events"] if ev[1] == "fin"]
            if fins:
                break
            time.sleep(0.005)
        assert len(fins) == 1, "a retried submit double-generated"
        assert s["snap"]["requests_finished"] == 1
        # retrying AFTER completion still dedups — never a re-run
        resp, _ = conn.call("submit",
                            {"max_new_tokens": 6, "key": "cli:r1"},
                            [prompt.data], idempotent=True)
        assert resp["rid"] == first_rid and resp.get("dedup")
    finally:
        conn.close()
        agent.die()


def test_agent_survives_garbage_frames(cfg, params):
    """A client speaking garbage gets ITS connection dropped; the
    agent keeps serving everyone else (ProtocolError recovery)."""
    agent = ReplicaAgent(_factory(cfg, params), lease_s=5.0)
    port = agent.start()
    addr = ("127.0.0.1", port)
    bad = socket.create_connection(addr)
    bad.sendall(b"NOT A FRAME AT ALL" * 3)
    conn = open_connection(addr)
    try:
        resp, _ = conn.call("ping", idempotent=True)
        assert isinstance(resp["state"], str) and resp["state"]
        # the garbage connection is gone (agent closed it; a clean
        # FIN or a kernel RST both prove the drop)
        bad.settimeout(5.0)
        try:
            assert bad.recv(1) == b""
        except ConnectionResetError:
            pass
    finally:
        bad.close()
        conn.close()
        agent.die()


@pytest.mark.slow
def test_graceful_shutdown_finishes_inflight_streams(cfg, params):
    ref = _ref(cfg, params, _PROMPTS[:2])
    agent = ReplicaAgent(_factory(cfg, params), lease_s=5.0)
    port = agent.start()
    conn = open_connection(("127.0.0.1", port))
    try:
        rids = []
        for i, p in enumerate(_PROMPTS[:2]):
            prompt = np.ascontiguousarray(p.astype(np.int64))
            r, _ = conn.call("submit",
                             {"max_new_tokens": 6, "key": f"g:{i}"},
                             [prompt.data], idempotent=True)
            rids.append(r["rid"])
        conn.call("shutdown", {"graceful": True}, idempotent=True)
        # no NEW admissions while closing
        with pytest.raises(RuntimeError, match="shutting down"):
            conn.call("submit", {"max_new_tokens": 2, "key": "g:x"},
                      [np.int64([1, 2]).data])
        fins, ack, t0 = {}, -1, time.monotonic()
        while len(fins) < 2:
            assert time.monotonic() - t0 < 60.0
            resp, _ = conn.call("sync", {"ack": ack},
                                idempotent=True)
            for ev in resp["events"]:
                ack = ev[0]
                if ev[1] == "fin":
                    fins[ev[2]["rid"]] = ev[2]
            time.sleep(0.005)
        assert [fins[r]["generated"] for r in rids] == ref
        assert all(fins[r]["status"] == "ok" for r in rids)
        # the agent keeps answering until the last result is ACKED —
        # one final ack lets it drain and exit
        conn.call("sync", {"ack": ack}, idempotent=True)
        agent.join(timeout=30.0)    # drained -> drive thread exited
        assert agent._stop
    finally:
        conn.close()
        agent.die()


def test_arm_fault_spec_local_plane():
    """The remote half of the fault-plane gap fix: a JSON-able spec
    arms this process's plane (agents run it at start)."""
    with faults.plane():
        arm_fault_spec([
            {"site": "conn_drop", "exc": "ConnectionError:boom",
             "nth": 1},
            {"site": "net_delay", "every": 2, "times": 1},
        ])
        with pytest.raises(ConnectionError, match="boom"):
            faults.fire("conn_drop")
        faults.fire("conn_drop")            # nth=1 only
        assert not faults.active("net_delay")   # consult 1: no
        assert faults.active("net_delay")       # consult 2: match
        assert not faults.active("net_delay")   # times=1: disarmed
    assert faults.get() is None


# ---------------------------------------------------------------------------
# the oracle: socket fleet ≡ in-process fleet
# ---------------------------------------------------------------------------
def test_remote_fleet_token_exact_vs_in_process(cfg, params):
    ref = _ref(cfg, params, _PROMPTS)
    inproc = FleetRouter([_factory(cfg, params)] * 2,
                         metrics_registry=False)
    rids = [inproc.submit(p, max_new_tokens=6) for p in _PROMPTS]
    via_inproc = {r.rid: r for r in inproc.run_to_completion()}
    assert [list(via_inproc[r].generated) for r in rids] == ref
    router = FleetRouter([_spec(cfg, params), _spec(cfg, params)])
    try:
        rids = [router.submit(p, max_new_tokens=6) for p in _PROMPTS]
        done = {r.rid: r
                for r in router.run_to_completion(
                    max_steps=1_000_000)}
        assert set(done) == set(rids), "request lost or invented"
        assert [list(done[r].generated) for r in rids] == ref, \
            "socket fleet must match the in-process oracle"
        assert all(done[r].status == "ok" for r in rids)
        _audit_all(router)          # audit() rides the wire
        snap = router.fleet_snapshot()
        assert snap["transport"]["frames"] > 0
        assert snap["transport"]["bytes"] > 0
        for rep in snap["replicas"]:
            t = rep["transport"]
            assert t["mode"] == "thread" and t["lease_age_s"] >= 0.0
    finally:
        _teardown(router)


def test_remote_disagg_handoff_round_trips_the_wire(cfg, params):
    """Remote prefill → remote decode: the KV blobs (int8 pools AND
    their fp scale planes) cross the wire and the decode side adopts
    them without one prefill dispatch — token-exact vs the unified
    in-process engine, which pins the round-trip bitwise (a single
    flipped bit in pool or scale plane changes the logits)."""
    ref = _ref(cfg, params, _PROMPTS, kv_quant="int8")
    router = FleetRouter(
        [_spec(cfg, params, role="prefill", engine_cls=PrefillEngine,
               kv_quant="int8", host_pages=32),
         _spec(cfg, params, role="decode", engine_cls=DecodeEngine,
               kv_quant="int8", host_pages=32)],
        handoff_gbps=1e9)
    try:
        rids = [router.submit(p, max_new_tokens=6) for p in _PROMPTS]
        done = {r.rid: r
                for r in router.run_to_completion(
                    max_steps=1_000_000)}
        assert [list(done[r].generated) for r in rids] == ref
        assert all(done[r].status == "ok" for r in rids)
        assert router.routed["disagg"] == len(_PROMPTS)
        assert router.handoffs_shipped == len(_PROMPTS)
        # the decode agent never prefilled: zero-prefill adoption
        de = router._replicas[1]._agent._sup.engine
        assert de.prefill_calls == 0
        assert de.cache.kv_quant == "int8"
        _audit_all(router)
        snap = router.fleet_snapshot()
        assert snap["roles"] == {"unified": 0, "prefill": 1,
                                 "decode": 1}
        # KV payloads moved real bytes over the loopback
        assert snap["transport"]["bytes"] > sum(
            p.size for p in _PROMPTS) * 8
    finally:
        _teardown(router)


def test_remote_fleet_server_http_and_metrics(cfg, params):
    from paddle_tpu.inference.serving import generate_http
    ref = _ref(cfg, params, _PROMPTS[:1])
    reg = MetricsRegistry()
    router = FleetRouter([_spec(cfg, params), _spec(cfg, params)],
                         metrics_registry=reg)
    srv = FleetServer(router)
    port = srv.start()
    url = f"http://127.0.0.1:{port}"
    try:
        toks = generate_http(url, [int(t) for t in _PROMPTS[0]],
                             max_new_tokens=6)
        assert toks == ref[0]
        import urllib.request
        with urllib.request.urlopen(url + "/fleet", timeout=10) as r:
            doc = json.loads(r.read())
        assert "transport" in doc
        assert doc["transport"]["frames"] > 0
        assert all("transport" in rep for rep in doc["replicas"])
        with urllib.request.urlopen(url + "/metrics",
                                    timeout=10) as r:
            text = r.read().decode()
        assert "paddle_tpu_transport_frames_total" in text
        assert "paddle_tpu_transport_rtt_seconds" in text
        assert reg.get(
            "paddle_tpu_transport_frames_total").value > 0
    finally:
        srv.stop()
        _teardown(router)


# ---------------------------------------------------------------------------
# chaos pins: every degradation seeded and replayable
# ---------------------------------------------------------------------------
def test_chaos_conn_drop_retries_token_exact(cfg, params):
    ref = _ref(cfg, params, _PROMPTS)
    with faults.plane() as fp:
        fp.inject("conn_drop", ConnectionResetError("injected"),
                  every=5)
        router = FleetRouter([_spec(cfg, params),
                              _spec(cfg, params, seed=1)])
        try:
            rids = [router.submit(p, max_new_tokens=6)
                    for p in _PROMPTS]
            done = {r.rid: r
                for r in router.run_to_completion(
                    max_steps=1_000_000)}
            assert set(done) == set(rids)
            assert [list(done[r].generated) for r in rids] == ref
            assert all(done[r].status == "ok" for r in rids)
            snap = router.fleet_snapshot()["transport"]
            assert snap["retries"] > 0, "drops must have been retried"
            assert snap["reconnects"] > 0
            _audit_all(router)
        finally:
            _teardown(router)


def test_chaos_frame_truncate_peer_recovers(cfg, params):
    """A truncated frame hits the agent mid-read: it drops that
    connection (ProtocolError path) and keeps serving; the client
    re-dials and the run stays token-exact."""
    ref = _ref(cfg, params, _PROMPTS)
    with faults.plane() as fp:
        fp.inject("frame_truncate", nth=3, times=2)
        router = FleetRouter([_spec(cfg, params)])
        try:
            rids = [router.submit(p, max_new_tokens=6)
                    for p in _PROMPTS]
            done = {r.rid: r
                for r in router.run_to_completion(
                    max_steps=1_000_000)}
            assert set(done) == set(rids)
            assert [list(done[r].generated) for r in rids] == ref
            snap = router.fleet_snapshot()["transport"]
            assert snap["reconnects"] > 0
            _audit_all(router)
        finally:
            _teardown(router)


def test_chaos_net_delay_degrades_then_recovers(cfg, params):
    """A stalled link trips the aggressive RPC timeout: the replica
    goes DEGRADED (missed heartbeat, lease still live), traffic
    steers around it, and it recovers to READY when the delay
    clears — no death, no failover, nothing dropped."""
    ref = _ref(cfg, params, _PROMPTS)
    with faults.plane() as fp:
        # NET_DELAY_S (0.05) >> rpc timeout (0.02): each matched
        # frame is a deterministic heartbeat miss
        fp.inject("net_delay", every=3, times=4)
        router = FleetRouter(
            [_spec(cfg, params, lease=30.0, timeout=0.02,
                   retries=0),
             _spec(cfg, params, lease=30.0, timeout=0.02,
                   retries=0, seed=1)])
        try:
            rids = [router.submit(p, max_new_tokens=6)
                    for p in _PROMPTS]
            saw_degraded = False
            done = {}
            t0 = time.monotonic()
            while router.has_work():
                assert time.monotonic() - t0 < 120.0
                router.step()
                saw_degraded |= any(h.state == "DEGRADED"
                                    for h in router._replicas)
                for r in router.finished():
                    done[r.rid] = r
            assert set(done) == set(rids)
            assert [list(done[r].generated) for r in rids] == ref
            assert saw_degraded, "a tripped timeout must degrade"
            assert all(h.state in ("READY", "DEGRADED")
                       for h in router._replicas), "no false death"
            snap = router.fleet_snapshot()
            assert snap["deaths"] == 0
            assert snap["transport"]["heartbeat_misses"] > 0
            _audit_all(router)
        finally:
            _teardown(router)


def test_chaos_agent_kill_lease_death_failover_token_exact(cfg,
                                                           params):
    """`agent_kill` tears an agent down before a sync: the lease
    expires, the router's EXISTING death triage fails zero-streamed
    victims over token-exact — rid AND absolute deadline intact —
    and auto-replace rebuilds the replica."""
    ref = _ref(cfg, params, _PROMPTS)
    with faults.plane() as fp:
        fp.inject("agent_kill", RuntimeError("chaos"), nth=1,
                  times=1)
        router = FleetRouter(
            [_spec(cfg, params, lease=0.4, timeout=0.3, retries=2),
             _spec(cfg, params, lease=0.4, timeout=0.3, retries=2,
                   seed=1)])
        try:
            deadlines = {}
            rids = []
            for p in _PROMPTS:
                rid = router.submit(p, max_new_tokens=6,
                                    deadline_s=300.0)
                rids.append(rid)
                deadlines[rid] = router._requests[rid].deadline
            done = {r.rid: r
                    for r in router.run_to_completion(
                        max_steps=1_000_000)}
            assert set(done) == set(rids), "silent drop under chaos"
            for rid in rids:
                assert done[rid].status in ("ok", "error")
                if done[rid].status == "ok":
                    assert list(done[rid].generated) == \
                        ref[rids.index(rid)], \
                        "failover must be token-exact"
                    # wire clock re-anchoring is exact up to the
                    # RPC's half-RTT (which can spike to ~100ms on a
                    # loaded CPU): the deadline must come back
                    # unextended — a failover that re-derived it
                    # from "now" would be off by ~300s, not
                    # fractions of a second
                    assert abs(done[rid].deadline
                               - deadlines[rid]) < 1.0, \
                        "absolute deadline must survive failover"
            snap = router.fleet_snapshot()
            assert snap["deaths"] >= 1
            assert snap["replaces"] >= 1       # auto-replace rebuilt
            # both replicas are serving again (a just-replaced one
            # may sit DEGRADED for one missed-sync tick on a loaded
            # CPU — that is a steering state, not a death)
            assert snap["states"]["DEAD"] == 0
            assert snap["states"]["READY"] >= 1
            _audit_all(router)
        finally:
            _teardown(router)


def test_chaos_mixed_schedule_soak_no_silent_drops(cfg, params):
    """All four transport sites armed at once under a 12-request
    load: every request finishes ok/cancelled/expired/error, ok ⇒
    token-exact, audits clean on every surviving replica (seeds the
    ROADMAP item-5 connection-chaos soak)."""
    prompts = [_RNG.randint(1, 128, (L,))
               for L in (10, 21, 8, 17, 12, 25, 9, 14, 19, 7, 23,
                         11)]
    ref = _ref(cfg, params, prompts)
    with faults.plane() as fp:
        fp.inject("conn_drop", ConnectionResetError("injected"),
                  every=11)
        fp.inject("frame_truncate", nth=20, times=1)
        fp.inject("net_delay", p=0.03, seed=5)
        fp.inject("agent_kill", RuntimeError("chaos"), nth=7,
                  times=1)
        router = FleetRouter(
            [_spec(cfg, params, lease=0.4, timeout=0.3, retries=2),
             _spec(cfg, params, lease=0.4, timeout=0.3, retries=2,
                   seed=1),
             _spec(cfg, params, lease=0.4, timeout=0.3, retries=2,
                   seed=2)])
        try:
            rids = [router.submit(p, max_new_tokens=6)
                    for p in prompts]
            done = {r.rid: r
                    for r in router.run_to_completion(
                        max_steps=1_000_000)}
            assert set(done) == set(rids), "silent drop under chaos"
            allowed = {"ok", "cancelled", "expired", "error"}
            for i, rid in enumerate(rids):
                assert done[rid].status in allowed
                if done[rid].status == "ok":
                    assert list(done[rid].generated) == ref[i]
            _audit_all(router)
            assert router.fleet_snapshot()["states"]["READY"] >= 2
        finally:
            _teardown(router)


@pytest.mark.slow
def test_sigkill_process_agent_mid_decode(cfg, params):
    """THE real-wire acceptance pin: an agent in its own OS process
    is SIGKILLed mid-decode — no Python exception, no FIN beyond the
    kernel's.  The lease expires, death triage fails zero-streamed
    victims over token-exact onto the surviving in-thread replica,
    mid-stream ones error honestly, nothing is silently dropped."""
    if WORKERS not in sys.path:
        sys.path.insert(0, WORKERS)
    ref = _ref(cfg, params, _PROMPTS)
    spawn_spec = RemoteSpec(
        spawn={"factory": "remote_agent_worker:make_engine",
               "agent_kwargs": {"lease_s": 0.6},
               "jax_platforms": "cpu"},
        lease_s=0.6, rpc_timeout_s=0.5, max_retries=1,
        backoff_s=0.01)
    surv = _spec(cfg, params)
    router = FleetRouter([spawn_spec, surv], auto_replace=False)
    try:
        h = router._replicas[0]
        assert h._proc is not None and h._proc.is_alive()
        assert h.transport_snapshot()["mode"] == "process"
        # place everything on the PROCESS replica (survivor briefly
        # refuses admission), then let it actually start decoding
        router._replicas[1].state = "DRAINING"
        rids = [router.submit(p, max_new_tokens=6) for p in _PROMPTS]
        router._replicas[1].state = "READY"
        t0 = time.monotonic()
        while h.snap.get("decode_steps", 0) == 0:
            assert time.monotonic() - t0 < 120.0, \
                "agent never reached decode"
            router.step()
            time.sleep(0.01)
        streamed_before = {rid: router._requests[rid].streamed
                           for rid in rids
                           if rid in router._requests}
        os.kill(h._proc.pid, signal.SIGKILL)
        done = {r.rid: r
                for r in router.run_to_completion(
                    max_steps=1_000_000)}
        assert set(done) == set(rids), "SIGKILL silently dropped"
        for rid in rids:
            assert done[rid].status in ("ok", "error")
            if done[rid].status == "ok" and \
                    streamed_before.get(rid, 0) == 0:
                assert list(done[rid].generated) == \
                    ref[rids.index(rid)], \
                    "zero-streamed victims fail over token-exact"
        snap = router.fleet_snapshot()
        assert snap["deaths"] >= 1
        assert not h._proc.is_alive() and h._proc.exitcode == -9
        # the survivor is healthy and audit-clean
        router._replicas[1].engine.cache.audit()
    finally:
        _teardown(router)


def test_remote_drain_and_replace_lifecycle(cfg, params):
    """drain() over the wire: the agent finishes in-flight work,
    reports drained through the sync snapshot, and the router
    replaces it with a FRESH agent that serves correctly."""
    ref = _ref(cfg, params, _PROMPTS[:2])
    router = FleetRouter([_spec(cfg, params)])
    try:
        first_agent = router._replicas[0]._agent
        rids = [router.submit(p, max_new_tokens=6)
                for p in _PROMPTS[:2]]
        router.drain(0)
        done = {r.rid: r
                for r in router.run_to_completion(max_steps=100000)}
        assert [list(done[r].generated) for r in rids] == ref
        h = router._replicas[0]
        t0 = time.monotonic()
        while h.state != "READY" or h.replaces < 1:
            assert time.monotonic() - t0 < 60.0
            router.step()
            time.sleep(0.005)
        assert h._agent is not first_agent, "replace built a fresh one"
        rid = router.submit(_PROMPTS[2], max_new_tokens=6)
        done = {r.rid: r
                for r in router.run_to_completion(max_steps=100000)}
        assert done[rid].status == "ok"
        _audit_all(router)
    finally:
        _teardown(router)


def test_metrics_dump_renders_transport(cfg, params):
    """tools/metrics_dump.py transport <url>: per-replica wire table
    + aggregate counters + the registry transport slice."""
    import importlib
    sys.path.insert(0, "tools")
    try:
        md = importlib.import_module("metrics_dump")
    finally:
        sys.path.pop(0)
    reg = MetricsRegistry()
    router = FleetRouter([_spec(cfg, params)], metrics_registry=reg)
    try:
        router.submit(_PROMPTS[0], max_new_tokens=4)
        router.run_to_completion(max_steps=1_000_000)
        text = md._render_transport(router.fleet_snapshot(),
                                    reg.snapshot())
        assert "transport:" in text and "frames=" in text
        assert "thread" in text and "127.0.0.1" in text
        assert "paddle_tpu_transport_rtt_seconds" in text
        assert "rtt ms/rpc" in text
        # an in-process fleet renders the explanatory fallback
        inproc = FleetRouter([_factory(cfg, params)],
                             metrics_registry=False)
        assert "no transport section" in md._render_transport(
            inproc.fleet_snapshot())
    finally:
        _teardown(router)
