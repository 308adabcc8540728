"""Deterministic fault-injection plane for the serving stack.

The degraded paths of a serving system — a poisoned decode dispatch, a
failed host-tier swap, a full host pool, a client that vanishes
mid-stream — are unreachable from ordinary tests: they depend on
hardware faults, race timing, or remote peers.  This module gives them
a switchboard.  Production code consults *named sites* at the exact
points where those failures would surface:

=====================  ==================================================
site                   consulted by
=====================  ==================================================
``step_dispatch``      ``ContinuousBatchingEngine`` immediately before
                       dispatching the jitted decode step (sync and
                       overlap lanes; the speculative engine's rounds
                       ride the same seam)
``prefill_dispatch``   the engine's admission lanes immediately before
                       the jitted prefill program (packed / batched /
                       per-chunk) — slots and pages are already
                       claimed, so this exercises the mid-admission
                       quarantine path

``swap_in``            ``PagedKVCache.swap_in_row`` before any mutation
                       (the engine falls back to recompute resumption)
``swap_out``           ``PagedKVCache.swap_out_row`` before any mutation
                       (the engine falls back to recompute preemption)
``host_pool_full``     condition rule: ``PagedKVCache.host_available``
                       reports zero capacity while armed (cost model
                       and swap preconditions degrade to recompute);
                       exception rule: ``HostPagePool.alloc`` raises
                       (hard exhaustion at the allocator)
``stream_write``       the ``/generate_stream`` chunk writer — simulates
                       a client disconnect (``BrokenPipeError``) without
                       a real socket close
``route_dispatch``     ``FleetRouter`` immediately before handing an
                       accepted request to the chosen replica — the
                       router steers to the next candidate; with no
                       candidate left the submit fails loudly
``replica_death``      the router's per-replica step seam (consulted
                       once per stepped replica) — an exception rule
                       simulates a replica process death: state DEAD,
                       un-streamed requests fail over, mid-stream ones
                       error, ``auto_replace`` rebuilds
``replica_slow``       condition rule at the same per-replica step
                       seam — while active the replica STALLS (no step
                       this tick) and is marked DEGRADED so routing
                       steers around it; it recovers to READY when the
                       rule stops matching
``conn_drop``          the sockets transport's client connection
                       (``fleet/transport.py``), once per RPC frame —
                       an exception rule resets the connection
                       mid-call: idempotent ops reconnect and retry
                       with backoff, others surface the ambiguity
``frame_truncate``     the same per-frame seam, condition-style:
                       while matched the client sends a deliberately
                       CUT frame and drops — the agent exercises its
                       ``ProtocolError`` recovery (drop that
                       connection, keep serving) and the client
                       retries over a fresh dial
``net_delay``          the same per-frame seam, condition-style: a
                       matched frame leaves ``NET_DELAY_S`` late, so
                       deadline-aware RPC timeouts trip
                       deterministically (stalled-link simulation)
``agent_kill``         ``RemoteReplicaHandle``'s per-tick sync seam
                       (``fleet/remote.py``): while matched the
                       handle SIGKILLs its agent process (or tears
                       down the in-thread agent) before syncing —
                       the lease expires and the router's existing
                       death/failover path takes over.  For faults
                       INSIDE a remote agent process, arm the
                       agent's own plane via ``fault_spec`` in its
                       spawn config (this module is process-global —
                       see docs/FAULT_TOLERANCE.md, "Remote-agent
                       fault injection")
``kv_handoff``         the disaggregated prefill/decode handoff, TWO
                       halves per handoff: the SHIP half fires in
                       ``HandoffRecord.materialize`` (the staging
                       flush committing the async D2H copies) and the
                       RESTORE half in ``DecodeEngine.admit_handoff``
                       (before the record adopts into the receiving
                       host tier).  Either failure degrades the
                       request to a colocated re-prefill on the
                       decode side — token-exact, counted in
                       ``disagg_colocated_fallback_total``, never a
                       dropped request
=====================  ==================================================

Faults are DETERMINISTIC: rules match by call index (``nth`` = exactly
the n-th consult, ``every`` = every K-th consult, the default = every
consult), disarm after ``times`` matches, and probabilistic rules
(``p=``) draw from a private ``random.Random(seed)`` so a seeded run
replays exactly.  No rule ever relies on wall-clock time.

The plane is OFF unless installed: the production hot path pays one
``is None`` check per consulted site.  Tests use the context manager::

    from paddle_tpu.testing import faults

    with faults.plane() as fp:
        fp.inject("step_dispatch", RuntimeError("injected"), nth=3)
        ...                     # 3rd decode dispatch raises
    assert fp.counts["step_dispatch"] >= 3

Stdlib only.
"""

from __future__ import annotations

import random
import threading
from contextlib import contextmanager
from typing import Dict, List, Optional

__all__ = ["FaultPlane", "FaultRule", "plane", "install", "uninstall",
           "get", "fire", "active"]


class FaultRule:
    """One armed fault: which consults it matches and what it does.

    ``exc``: exception instance or class to raise at :meth:`FaultPlane.
    fire` (``None`` = a pure condition flag, visible through
    :meth:`FaultPlane.active` — e.g. ``host_pool_full``).
    ``nth``: match exactly the n-th consult of the site (1-based).
    ``every``: match every K-th consult.
    ``p``/``seed``: match each consult with probability ``p`` drawn
    from a private deterministic stream.
    ``times``: disarm after this many matches (``None`` = unlimited).
    """

    def __init__(self, exc=None, nth: Optional[int] = None,
                 every: Optional[int] = None, times: Optional[int] = None,
                 p: Optional[float] = None, seed: int = 0):
        if nth is not None and nth < 1:
            raise ValueError("nth is 1-based")
        if every is not None and every < 1:
            raise ValueError("every must be >= 1")
        self.exc = exc
        self.nth = nth
        self.every = every
        self.p = p
        self.times = times
        self.matches = 0
        self._rng = random.Random(seed)

    def _matches_call(self, n: int) -> bool:
        """Does consult #``n`` (1-based, per site) trip this rule?"""
        if self.times is not None and self.matches >= self.times:
            return False
        if self.nth is not None and n != self.nth:
            return False
        if self.every is not None and n % self.every != 0:
            return False
        if self.p is not None and self._rng.random() >= self.p:
            return False
        self.matches += 1
        return True

    def _make_exc(self):
        exc = self.exc
        return exc() if isinstance(exc, type) else exc


class FaultPlane:
    """A set of armed :class:`FaultRule` per site plus per-site consult
    counters.  Thread-safe: the serving stack consults from the engine
    thread and HTTP handler threads concurrently."""

    def __init__(self):
        self._rules: Dict[str, List[FaultRule]] = {}
        self.counts: Dict[str, int] = {}
        self.fired: Dict[str, int] = {}       # site -> rules tripped
        self._lock = threading.Lock()

    def inject(self, site: str, exc=None, *, nth: Optional[int] = None,
               every: Optional[int] = None, times: Optional[int] = None,
               p: Optional[float] = None, seed: int = 0) -> FaultRule:
        """Arm a rule; returns it (its ``matches`` count is live)."""
        rule = FaultRule(exc, nth=nth, every=every, times=times, p=p,
                         seed=seed)
        with self._lock:
            self._rules.setdefault(site, []).append(rule)
        return rule

    def clear(self, site: Optional[str] = None) -> None:
        """Disarm ``site``'s rules (all sites when ``None``).  Consult
        counters survive — they are observability, not state."""
        with self._lock:
            if site is None:
                self._rules.clear()
            else:
                self._rules.pop(site, None)

    def _consult(self, site: str) -> Optional[FaultRule]:
        with self._lock:
            n = self.counts.get(site, 0) + 1
            self.counts[site] = n
            for rule in self._rules.get(site, ()):
                if rule._matches_call(n):
                    self.fired[site] = self.fired.get(site, 0) + 1
                    return rule
        return None

    def fire(self, site: str) -> None:
        """Count one consult of ``site``; raise if an armed
        exception-rule matches this call."""
        rule = self._consult(site)
        if rule is not None and rule.exc is not None:
            raise rule._make_exc()

    def active(self, site: str) -> bool:
        """Count one consult of ``site``; True when a condition rule
        matches this call (exception rules also read as active — a
        site may consult state-style)."""
        return self._consult(site) is not None


# -- process-wide installation (OFF by default: hot paths pay one
#    ``is None`` check per consulted site) --------------------------------
_PLANE: Optional[FaultPlane] = None


def install(p: Optional[FaultPlane] = None) -> FaultPlane:
    """Install ``p`` (or a fresh plane) process-wide and return it."""
    global _PLANE
    _PLANE = p if p is not None else FaultPlane()
    return _PLANE


def uninstall() -> None:
    global _PLANE
    _PLANE = None


def get() -> Optional[FaultPlane]:
    """The installed plane, or ``None`` when fault injection is off."""
    return _PLANE


@contextmanager
def plane():
    """``with faults.plane() as fp: fp.inject(...)`` — installs a fresh
    plane for the block and uninstalls it on exit (exception-safe, so a
    failing test never leaks armed faults into the next one)."""
    fp = install()
    try:
        yield fp
    finally:
        if _PLANE is fp:
            uninstall()


# -- the consult seams production code calls ------------------------------
def fire(site: str) -> None:
    """No-op unless a plane is installed; otherwise consult ``site``
    and raise if an exception rule matches."""
    if _PLANE is not None:
        _PLANE.fire(site)


def active(site: str) -> bool:
    """False unless a plane is installed; otherwise consult ``site``
    and report whether a rule matches this call."""
    return _PLANE is not None and _PLANE.active(site)
