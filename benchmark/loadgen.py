#!/usr/bin/env python3
"""The load generator: a child process that never imports JAX (one
process holds the chip).  It replays the phases of a schedule file on
the monotonic clock — a request is sent when it is DUE, whatever has or
has not finished — streams every request over ``POST /generate_stream``
and stamps due, sent, first token and every token.

    python3 benchmark/loadgen.py <url> <schedule.json> <out.jsonl>

It prints ``T0 <monotonic seconds>`` (the start of the first phase) as
soon as it knows it, and writes one JSON line a request when it ends.
A phase with ``cut_at_end`` closes whatever is still streaming at its
end (status ``cut``); after the last phase, in-flight requests drain
for at most ``drain_cap_s`` and are then cut too.
"""

from __future__ import annotations

import http.client
import json
import socket
import sys
import threading
import time
import urllib.parse


# the server's listen queue is short (socketserver's default of 5): a
# backlog's hundreds of requests connect a few at a time, not at once
CONNECT_GATE = threading.Semaphore(4)


class Sender(threading.Thread):
    def __init__(self, host, port, phase, index, req, due, cut_event):
        super().__init__(daemon=True)
        self.host, self.port = host, port
        self.req, self.cut_event = req, cut_event
        self.rec = {"phase": phase, "index": index, "due": due,
                    "sent": None, "token_times": [], "tokens": [],
                    "rid": None, "status": "unsent",
                    "prompt_len": len(req["prompt"]),
                    "max_new_tokens": req["max_new_tokens"]}
        self.conn = None

    def run(self):
        rec = self.rec
        delay = rec["due"] - time.monotonic()
        if delay > 0 and self.cut_event.wait(delay):
            rec["status"] = "cut"
            return
        body = json.dumps({"prompt": self.req["prompt"],
                           "max_new_tokens": self.req["max_new_tokens"]})
        try:
            self.conn = http.client.HTTPConnection(self.host, self.port,
                                                   timeout=600)
            with CONNECT_GATE:
                rec["sent"] = time.monotonic()
                self.conn.request("POST", "/generate_stream", body,
                                  {"Content-Type": "application/json",
                                   "Connection": "close"})
            resp = self.conn.getresponse()
            if resp.status != 200:
                rec["status"] = f"http {resp.status}"
                return
            rec["status"] = "streaming"
            while True:
                line = resp.readline()
                now = time.monotonic()
                if not line:
                    break
                if not line.strip():
                    continue
                msg = json.loads(line)
                if msg.get("done"):
                    rec["status"] = "error: " + msg["error"] \
                        if msg.get("error") else "ok"
                    resp.read()         # the terminal chunk
                    return
                rec["rid"] = msg["rid"]
                rec["tokens"].append(msg["token"])
                rec["token_times"].append(now)
            rec["status"] = "cut" if self.cut_event.is_set() \
                else "error: stream ended early"
        except (OSError, http.client.HTTPException, ValueError) as e:
            rec["status"] = "cut" if self.cut_event.is_set() \
                else f"error: {type(e).__name__}: {e}"
        finally:
            if self.conn is not None:
                self.conn.close()

    def cut(self):
        sock = getattr(self.conn, "sock", None)
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass


def main(argv) -> int:
    url, schedule_path, out_path = argv[1:4]
    with open(schedule_path) as f:
        sched = json.load(f)
    u = urllib.parse.urlsplit(url)
    t0 = time.monotonic() + sched["start_delay_s"]
    print(f"T0 {t0!r}", flush=True)
    start, groups = t0, []
    for ph in sched["phases"]:
        cut_event = threading.Event()
        senders = [Sender(u.hostname, u.port, ph["name"], i, r,
                          start + r["due"], cut_event)
                   for i, r in enumerate(ph["requests"])]
        groups.append((ph, start, cut_event, senders))
        start += ph["seconds"]
    for _, _, _, senders in groups:
        for s in senders:
            s.start()
    for k, (ph, ph_start, cut_event, senders) in enumerate(groups):
        end = ph_start + ph["seconds"]
        last = k == len(groups) - 1
        time.sleep(max(end - time.monotonic(), 0))
        if not ph["cut_at_end"]:
            if not last:
                continue
            deadline = end + sched["drain_cap_s"]
            for s in senders:
                s.join(max(deadline - time.monotonic(), 0))
        cut_event.set()
        for s in senders:
            s.cut()
        for s in senders:
            s.join(30)
    with open(out_path, "w") as f:
        for _, _, _, senders in groups:
            for s in senders:
                s.join(30)
                f.write(json.dumps(s.rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
