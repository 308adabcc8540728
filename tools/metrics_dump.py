#!/usr/bin/env python
"""Pretty-print metrics from a running paddle_tpu server, or tail its
structured-event ring.

Usage:
  python tools/metrics_dump.py stats   http://127.0.0.1:8000
  python tools/metrics_dump.py metrics http://127.0.0.1:8000
  python tools/metrics_dump.py events  http://127.0.0.1:8000 [-n 50] [--follow]
  python tools/metrics_dump.py fleet   http://127.0.0.1:8000
  python tools/metrics_dump.py disagg  http://127.0.0.1:8000
  python tools/metrics_dump.py spec    http://127.0.0.1:8000
  python tools/metrics_dump.py qos     http://127.0.0.1:8000
  python tools/metrics_dump.py transport http://127.0.0.1:8000
  python tools/metrics_dump.py traces  http://127.0.0.1:8000 [--min-ms N] [--status S]
  python tools/metrics_dump.py trace   http://127.0.0.1:8000 <rid>
  python tools/metrics_dump.py snapshot stats.json

``stats`` renders ``GET /stats`` (the JSON snapshot) as an aligned
table; ``metrics`` dumps the raw Prometheus text from ``GET /metrics``;
``events`` prints the last N ring events as JSON lines and with
``--follow`` polls ``/events?since=<seq>`` for new ones; ``fleet``
renders a FleetServer's aggregated ``GET /fleet`` snapshot (replica
lifecycle states, per-replica load, routing/failover counters);
``disagg`` renders the disaggregated prefill/decode slice of
``GET /stats`` (handoff traffic, in-flight depth, routing decisions,
fallbacks, handoff ms/request); ``spec`` renders the fused
speculative-decoding slice (rounds/drafted/accepted counters, live
gamma, accept-length histogram, derived acceptance ratio); ``qos``
renders the SLO-guardrail slice as a dashboard — per-class queue
depths, shed/degrade/quota-reject counts, and the fleet's scale
trajectory (``scale_up/down``, retired slots, the autoscaler's
desired-replica gauge), from ``GET /stats`` with ``GET /fleet``
folded in when the front is a FleetServer;
``transport`` renders a socket
fleet's wire health — per-replica connection mode/address, lease
age, reconnect/retry/heartbeat-miss counters and wire volume from
``GET /fleet``, plus the ``paddle_tpu_transport_*`` registry slice
(RTT histogram included) from ``GET /stats``; ``traces`` lists the serving front's
retained trace index (``GET /traces`` — tail-sampled: slow/abnormal
traces always kept) and ``trace`` renders one request's span tree
(``GET /trace/<rid>``) with its phase-clock latency breakdown;
``snapshot`` pretty-prints a ``GET /stats`` document previously saved
to a file.

Stdlib only — usable on any host that can reach the server.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.error
import urllib.request


def _get(url: str, timeout: float = 10.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read()


def _render_snapshot(snap: dict) -> str:
    """Aligned table: counters/gauges one line; histograms show
    count/sum/mean plus the occupied buckets."""
    lines = []
    width = max((len(n) for n in snap), default=0)
    for name in sorted(snap):
        m = snap[name]
        kind = m.get("type", "?")
        if kind == "histogram":
            count, total = m.get("count", 0), m.get("sum", 0.0)
            mean = total / count if count else 0.0
            lines.append(f"{name:<{width}}  histogram  count={count} "
                         f"sum={total:.6g} mean={mean:.6g}")
            prev = 0
            for le, c in (m.get("buckets") or {}).items():
                if c != prev:
                    lines.append(f"{'':<{width}}    le={le}: {c}")
                prev = c
            for kind, ex in sorted((m.get("exemplars")
                                    or {}).items()):
                # the trace id behind the observation: drill into
                # the span tree with `trace <url> <id>`
                lines.append(
                    f"{'':<{width}}    exemplar {kind}="
                    f"{ex.get('value', 0):.6g} "
                    f"trace={ex.get('trace_id')}")
        else:
            v = m.get("value")
            vs = "NaN" if v is None else f"{v:.6g}"
            lines.append(f"{name:<{width}}  {kind:<9}  {vs}")
    return "\n".join(lines)


def cmd_stats(args) -> int:
    body = json.loads(_get(args.url.rstrip("/") + "/stats"))
    snap = body.get("metrics", body)     # /stats wraps; a file may not
    print(_render_snapshot(snap))
    return 0


def cmd_metrics(args) -> int:
    sys.stdout.write(
        _get(args.url.rstrip("/") + "/metrics").decode())
    return 0


def cmd_events(args) -> int:
    base = args.url.rstrip("/") + "/events"
    since = 0
    while True:
        q = f"?since={since}" if since else f"?n={args.n}"
        body = json.loads(_get(base + q))
        gap = body.get("gap", 0)
        if gap:
            # the ring wrapped between polls: these events are GONE
            # — a silent skip used to read as a quiet stream
            print(f"[gap: {gap} events lost]")
        for ev in body.get("events", []):
            print(json.dumps(ev))
            since = max(since, ev.get("seq", since))
        sys.stdout.flush()
        if not args.follow:
            return 0
        time.sleep(args.interval)


def _render_fleet(doc: dict) -> str:
    """The aggregated fleet snapshot: one header line (states +
    routing/degradation counters), then a per-replica table."""
    states = doc.get("states", {})
    lines = ["fleet: " + "  ".join(
        f"{s.lower()}={states.get(s, 0)}" for s in
        ("READY", "DEGRADED", "DRAINING", "DEAD", "STARTING"))]
    routed = doc.get("routed", {})
    lines.append("routed: " + "  ".join(
        f"{k}={routed.get(k, 0)}"
        for k in ("prefix", "least_loaded", "failover", "disagg")))
    roles = doc.get("roles")
    if roles and (roles.get("prefill") or roles.get("decode")):
        lines.append("roles: " + "  ".join(
            f"{k}={roles.get(k, 0)}"
            for k in ("prefill", "decode", "unified")))
    dis = doc.get("disagg")
    if dis:
        lines.append(
            "disagg: " + "  ".join(
                f"{k}={dis.get(k, 0)}"
                for k in ("handoffs_shipped", "handoff_pages",
                          "handoffs_inflight",
                          "colocated_fallbacks"))
            + "  decisions=" + "/".join(
                str(dis.get("decisions", {}).get(k, 0))
                for k in ("disagg", "colocated")))
    lines.append(
        f"failovers={doc.get('failovers', 0)}  "
        f"rejected={doc.get('rejected', 0)}  "
        f"deaths={doc.get('deaths', 0)}  "
        f"replaces={doc.get('replaces', 0)}  "
        f"pending_failovers={doc.get('pending_failovers', 0)}  "
        f"requests_live={doc.get('requests_live', 0)}")
    cols = ("idx", "state", "active", "queued", "queued_tokens",
            "occupancy", "decode_steps", "tokens_generated",
            "prefix_hit_pages", "restarts", "deaths", "replaces",
            "drains", "retry_after_s")
    rows = [[str(r.get(c, "")) for c in cols]
            for r in doc.get("replicas", [])]
    widths = [max(len(c), *(len(row[i]) for row in rows))
              if rows else len(c) for i, c in enumerate(cols)]
    lines.append("  ".join(c.ljust(w) for c, w in zip(cols, widths)))
    for row in rows:
        lines.append("  ".join(v.ljust(w)
                               for v, w in zip(row, widths)))
    for r in doc.get("replicas", []):
        if r.get("error"):
            lines.append(f"replica {r['idx']} error: {r['error']}")
    return "\n".join(lines)


def cmd_fleet(args) -> int:
    doc = json.loads(_get(args.url.rstrip("/") + "/fleet"))
    print(_render_fleet(doc))
    return 0


def _render_disagg(snap: dict) -> str:
    """The disaggregated prefill/decode slice of a registry snapshot:
    handoff traffic, in-flight depth, routing decisions, fallbacks,
    and the handoff-latency histogram."""
    dis = {n: m for n, m in snap.items()
           if n.startswith("paddle_tpu_disagg_")}
    if not dis:
        return ("no paddle_tpu_disagg_* metrics in this snapshot "
                "(not a disaggregated serving front?)")
    lines = [_render_snapshot(dis)]
    ship = dis.get("paddle_tpu_disagg_handoff_seconds") or {}
    pages = (dis.get("paddle_tpu_disagg_handoff_pages_total")
             or {}).get("value") or 0
    if ship.get("count"):
        lines.append(
            f"handoff ms/request = "
            f"{1000.0 * ship['sum'] / ship['count']:.3f}  "
            f"pages/handoff = {pages / ship['count']:.1f}")
    return "\n".join(lines)


def cmd_disagg(args) -> int:
    body = json.loads(_get(args.url.rstrip("/") + "/stats"))
    print(_render_disagg(body.get("metrics", body)))
    return 0


def _render_spec(snap: dict) -> str:
    """The fused speculative-decoding slice of a registry snapshot:
    round/draft/accept counters, the live gamma, and the per-round
    accept-length histogram with the derived acceptance ratio."""
    spec = {n: m for n, m in snap.items()
            if n.startswith("paddle_tpu_engine_spec_")}
    if not spec:
        return ("no paddle_tpu_engine_spec_* metrics in this "
                "snapshot (engine built without spec=SpecConfig?)")
    lines = [_render_snapshot(spec)]
    drafted = (spec.get(
        "paddle_tpu_engine_spec_drafted_tokens_total")
        or {}).get("value") or 0
    accepted = (spec.get(
        "paddle_tpu_engine_spec_accepted_tokens_total")
        or {}).get("value") or 0
    rounds = (spec.get("paddle_tpu_engine_spec_rounds_total")
              or {}).get("value") or 0
    if drafted:
        lines.append(
            f"acceptance = {accepted / drafted:.4f}  "
            f"accepted tokens/round = "
            f"{accepted / max(rounds, 1):.2f}  "
            f"(committed/round adds the +1 correction token)")
    return "\n".join(lines)


def cmd_spec(args) -> int:
    body = json.loads(_get(args.url.rstrip("/") + "/stats"))
    print(_render_spec(body.get("metrics", body)))
    return 0


def _render_qos(snap: dict, fleet_doc: dict = None) -> str:
    """The SLO-guardrail slice of a registry snapshot: per-class
    queue depths, shed/degrade/quota counters, and the fleet scale
    trajectory (docs/FAULT_TOLERANCE.md "Overload & degradation")."""
    def val(name):
        m = snap.get(name) or {}
        v = m.get("value")
        return 0 if v is None else v

    lines = []
    q = {c: val(f"paddle_tpu_engine_queued_{c}_count")
         for c in ("high", "normal", "low")}
    lines.append("queued by class: " + "  ".join(
        f"{c}={int(q[c])}" for c in ("high", "normal", "low")))
    lines.append(
        f"shed: rejected={int(val('paddle_tpu_engine_requests_rejected_total'))}  "
        f"degraded={int(val('paddle_tpu_engine_requests_degraded_total'))}  "
        f"quota_rejected={int(val('paddle_tpu_engine_quota_rejected_total'))}")
    fleet_qr = val("paddle_tpu_fleet_quota_rejected_total")
    ups = val("paddle_tpu_fleet_scale_up_total")
    downs = val("paddle_tpu_fleet_scale_down_total")
    retired = val("paddle_tpu_fleet_replicas_retired_count")
    desired = val(
        "paddle_tpu_fleet_autoscaler_desired_replicas_count")
    if any((fleet_qr, ups, downs, retired, desired)) or \
            "paddle_tpu_fleet_replicas_count" in snap:
        lines.append(
            f"fleet: quota_rejected={int(fleet_qr)}  "
            f"scale_ups={int(ups)}  scale_downs={int(downs)}  "
            f"retired={int(retired)}  desired={int(desired)}  "
            f"rejected={int(val('paddle_tpu_fleet_rejected_total'))}")
    if fleet_doc:
        states = fleet_doc.get("states", {})
        lines.append("replicas: " + "  ".join(
            f"{s.lower()}={states.get(s, 0)}" for s in
            ("READY", "DEGRADED", "DRAINING", "STARTING", "DEAD",
             "RETIRED")))
    qos = {n: m for n, m in snap.items() if n in (
        "paddle_tpu_engine_requests_degraded_total",
        "paddle_tpu_engine_quota_rejected_total",
        "paddle_tpu_engine_queued_high_count",
        "paddle_tpu_engine_queued_normal_count",
        "paddle_tpu_engine_queued_low_count",
        "paddle_tpu_fleet_quota_rejected_total",
        "paddle_tpu_fleet_scale_up_total",
        "paddle_tpu_fleet_scale_down_total",
        "paddle_tpu_fleet_replicas_retired_count",
        "paddle_tpu_fleet_autoscaler_desired_replicas_count")}
    if qos:
        lines.append(_render_snapshot(qos))
    return "\n".join(lines)


def cmd_qos(args) -> int:
    base = args.url.rstrip("/")
    body = json.loads(_get(base + "/stats"))
    fleet_doc = None
    try:
        fleet_doc = json.loads(_get(base + "/fleet"))
    except (urllib.error.URLError, ValueError):
        pass                     # single-engine fronts have no /fleet
    print(_render_qos(body.get("metrics", body), fleet_doc))
    return 0


def _render_trace(doc: dict) -> str:
    """One request's span tree, indented by parent, with the
    phase-clock latency breakdown the trace's close recorded."""
    lines = [f"trace {doc.get('trace_id')}  "
             f"status={doc.get('status')}  "
             f"duration_ms={doc.get('duration_ms')}"
             + ("  [in flight]" if doc.get("in_flight") else "")]
    if doc.get("error"):
        lines.append(f"error: {doc['error']}")
    clocks = (doc.get("attrs") or {}).get("clocks") or {}
    if clocks:
        lines.append("phase clocks (ms): " + "  ".join(
            f"{k}={1000.0 * v:.2f}"
            for k, v in sorted(clocks.items(),
                               key=lambda kv: -kv[1])))
    children = {}
    for span in doc.get("spans", []):
        children.setdefault(span.get("parent"), []).append(span)

    def walk(parent, depth):
        for span in children.get(parent, []):
            attrs = {k: v for k, v in (span.get("attrs")
                                       or {}).items()
                     if k not in ("phase",)}
            extra = ("  " + " ".join(f"{k}={v}" for k, v
                                     in sorted(attrs.items()))
                     if attrs else "")
            lines.append(
                f"{'  ' * depth}{span['name']:<18} "
                f"{1000.0 * (span.get('dur_s') or 0.0):9.3f} ms"
                + extra)
            walk(span["id"], depth + 1)

    walk(None, 0)
    return "\n".join(lines)


def cmd_trace(args) -> int:
    try:
        doc = json.loads(_get(
            args.url.rstrip("/") + f"/trace/{args.rid}"))
    except urllib.error.HTTPError as e:
        if e.code == 404:
            print(f"no trace for rid {args.rid} (dropped by tail "
                  f"sampling, or never begun)", file=sys.stderr)
            return 1
        raise
    print(_render_trace(doc))
    return 0


def cmd_traces(args) -> int:
    q = []
    if args.min_ms:
        q.append(f"min_ms={args.min_ms}")
    if args.status:
        q.append(f"status={args.status}")
    q.append(f"limit={args.limit}")
    body = json.loads(_get(args.url.rstrip("/") + "/traces?"
                           + "&".join(q)))
    rows = body.get("traces", [])
    if not rows:
        print("no traces retained")
        return 0
    cols = ("trace_id", "status", "duration_ms", "spans")
    srows = [[str(t.get(c, "")) for c in cols] for t in rows]
    widths = [max(len(c), *(len(r[i]) for r in srows))
              for i, c in enumerate(cols)]
    print("  ".join(c.ljust(w) for c, w in zip(cols, widths)))
    for r in srows:
        print("  ".join(v.ljust(w) for v, w in zip(r, widths)))
    return 0


def _render_transport(fleet_doc: dict, snap: dict = None) -> str:
    """A socket fleet's wire health: the aggregate counter line and
    a per-replica connection table from ``/fleet``, then the
    ``paddle_tpu_transport_*`` registry slice (RTT histogram) from
    ``/stats`` when the server exposes one."""
    agg = fleet_doc.get("transport")
    if agg is None:
        return ("no transport section in /fleet (in-process fleet? "
                "remote replicas are RemoteSpec entries)")
    lines = ["transport: " + "  ".join(
        f"{k}={agg.get(k, 0)}"
        for k in ("reconnects", "retries", "heartbeat_misses",
                  "frames", "bytes"))]
    cols = ("idx", "mode", "addr", "lease_s", "lease_age_s",
            "reconnects", "retries", "heartbeat_misses", "frames",
            "bytes_sent", "bytes_recv", "agent_pid")
    rows = []
    for r in fleet_doc.get("replicas", []):
        t = r.get("transport")
        if t is None:
            continue
        vals = dict(t, idx=r.get("idx"),
                    addr=":".join(str(x) for x in t.get("addr", []))
                    or "-")
        rows.append([str(vals.get(c, "-")) for c in cols])
    if rows:
        widths = [max(len(c), *(len(row[i]) for row in rows))
                  for i, c in enumerate(cols)]
        lines.append("  ".join(c.ljust(w)
                               for c, w in zip(cols, widths)))
        for row in rows:
            lines.append("  ".join(v.ljust(w)
                                   for v, w in zip(row, widths)))
    if snap:
        tr = {n: m for n, m in snap.items()
              if n.startswith("paddle_tpu_transport_")}
        if tr:
            lines.append(_render_snapshot(tr))
            rtt = tr.get("paddle_tpu_transport_rtt_seconds") or {}
            if rtt.get("count"):
                lines.append(
                    f"rtt ms/rpc = "
                    f"{1000.0 * rtt['sum'] / rtt['count']:.3f}")
    return "\n".join(lines)


def cmd_transport(args) -> int:
    base = args.url.rstrip("/")
    fleet_doc = json.loads(_get(base + "/fleet"))
    snap = None
    try:
        body = json.loads(_get(base + "/stats"))
        snap = body.get("metrics", body)
    except (urllib.error.URLError, ValueError):
        pass                     # router-only fronts have no /stats
    print(_render_transport(fleet_doc, snap))
    return 0


def cmd_snapshot(args) -> int:
    """A ``GET /stats`` document saved to a file, rendered as ``stats``
    renders the live one."""
    with open(args.path) as f:
        body = json.load(f)
    print(_render_snapshot(body.get("metrics", body)))
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = p.add_subparsers(dest="cmd", required=True)
    s = sub.add_parser("stats", help="pretty-print GET /stats")
    s.add_argument("url")
    s.set_defaults(fn=cmd_stats)
    s = sub.add_parser("metrics", help="dump GET /metrics")
    s.add_argument("url")
    s.set_defaults(fn=cmd_metrics)
    s = sub.add_parser("events", help="tail the event ring")
    s.add_argument("url")
    s.add_argument("-n", type=int, default=50,
                   help="initial events to show")
    s.add_argument("--follow", action="store_true",
                   help="poll for new events")
    s.add_argument("--interval", type=float, default=1.0)
    s.set_defaults(fn=cmd_events)
    s = sub.add_parser("fleet",
                       help="pretty-print GET /fleet (FleetServer)")
    s.add_argument("url")
    s.set_defaults(fn=cmd_fleet)
    s = sub.add_parser("disagg",
                       help="pretty-print the disaggregated "
                            "prefill/decode slice of GET /stats")
    s.add_argument("url")
    s.set_defaults(fn=cmd_disagg)
    s = sub.add_parser("spec",
                       help="pretty-print the fused speculative-"
                            "decoding slice of GET /stats")
    s.add_argument("url")
    s.set_defaults(fn=cmd_spec)
    s = sub.add_parser("qos",
                       help="pretty-print the SLO-guardrail slice "
                            "(per-class queues, shed/quota counts, "
                            "scale trajectory)")
    s.add_argument("url")
    s.set_defaults(fn=cmd_qos)
    s = sub.add_parser("transport",
                       help="pretty-print a socket fleet's wire "
                            "health (GET /fleet + /stats)")
    s.add_argument("url")
    s.set_defaults(fn=cmd_transport)
    s = sub.add_parser("traces",
                       help="list the retained trace index "
                            "(GET /traces)")
    s.add_argument("url")
    s.add_argument("--min-ms", type=float, default=0.0,
                   dest="min_ms")
    s.add_argument("--status", default=None)
    s.add_argument("--limit", type=int, default=50)
    s.set_defaults(fn=cmd_traces)
    s = sub.add_parser("trace",
                       help="render one request's span tree "
                            "(GET /trace/<rid>)")
    s.add_argument("url")
    s.add_argument("rid")
    s.set_defaults(fn=cmd_trace)
    s = sub.add_parser("snapshot",
                       help="pretty-print a snapshot file")
    s.add_argument("path")
    s.set_defaults(fn=cmd_snapshot)
    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
