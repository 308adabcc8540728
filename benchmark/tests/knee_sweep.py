#!/usr/bin/env python3
"""The knee of an open-loop cell, found ONCE by a sweep on the chip:

    python3 benchmark/tests/knee_sweep.py <workload> <seconds> <seed> <rate> <rate> ...

One process; for each rate the cell's own run at that rate (the traffic
file's ``rate_rps`` replaced in memory), then, from the client's
stamps: the queue (requests due and not yet answered with a first
token) around the window's middle and over its last seconds, how many
completed inside the drain, and the tails.  The knee is the highest
rate at which the queue is no longer at the end than at the middle and
every request due completes.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def queue_mean(recs, lo, hi, step=0.1):
    n, total, t = 0, 0, lo
    while t < hi:
        total += sum(1 for r in recs if r["due"] <= t and
                     (not r["token_times"] or r["token_times"][0] > t))
        n += 1
        t += step
    return total / max(n, 1)


def main(argv) -> int:
    from benchmark import harness, serve_cell
    from benchmark.stats import median, percentile
    cell = harness.find_cell(argv[1])
    seconds, seed = float(argv[2]), int(argv[3])
    rows = []
    for k, rate in enumerate(float(r) for r in argv[4:]):
        cell.traffic["rate_rps"] = rate
        args = types.SimpleNamespace(workload=cell.name, seed=seed + k,
                                     seconds=seconds, trace=0)
        serve_cell.run(args, cell)
        gc.collect()
        with open(os.path.join(harness.OUT, cell.name,
                               "requests.jsonl")) as f:
            recs = [json.loads(l) for l in f]
        win = [r for r in recs if r["phase"] == "window"]
        t0 = min(r["due"] for r in win)
        mid, end = t0 + seconds / 2, t0 + seconds
        ttft = [(r["token_times"][0] - r["due"]) * 1e3 for r in win
                if r["token_times"]]
        gaps = [(b - a) * 1e3 for r in win for a, b in
                zip(r["token_times"], r["token_times"][1:])]
        rows.append({
            "rate": rate, "due": len(win),
            "completed": sum(r["status"] == "ok" for r in win),
            "queue_mid": queue_mean(recs, mid - 2, mid + 2),
            "queue_end": queue_mean(recs, end - 4, end),
            "ttft_p50": median(ttft), "ttft_p95": percentile(ttft, 95),
            "gap_p50": median(gaps), "gap_p95": percentile(gaps, 95)})
        print("SWEEP", json.dumps(rows[-1]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
