"""A serving cell: ``GenerationServer`` over the paged-KV continuous
batching engine, driven over real HTTP by the load generator (a child
process), on the mix a traffic file of ``kind: open_loop`` or
``backlog`` states.

Set-up: weights from the seed, pools, server, then one short request
for every page count the mix's prompts can have (each compiles its
admission shapes), then the load generator's warm-up phase, which runs
straight into the measured phase.  After the window has closed, the
run has drained, ``memory_peak_bytes`` has been read and the pools and
the server are freed, a seeded sample of the finished requests is
compared with the plain reference, which is handed the weights the
benchmark made.
"""

from __future__ import annotations

import json
import os
from collections import Counter
import subprocess
import sys
import time

import numpy as np

from . import harness, reference, traffic
from .harness import log
from .stats import median, percentile


def engine_counters(srv) -> dict:
    eng = srv.engine
    return {"decode_steps": eng.decode_steps,
            "tokens_generated": eng.tokens_generated,
            "prefill_calls": eng.prefill_calls,
            "prefill_token_slots": eng.prefill_token_slots,
            "prefill_padded_tokens": eng.prefill_padded_tokens}


def sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        time.sleep(d)


def send_wave(srv, url: str, wave: list) -> None:
    """Queue the wave's requests together and have them admitted as
    ONE wave: the engine thread is held idle (``has_work`` shadowed, as
    ``chip_smoke.py`` does) until ``/health`` reports them all queued."""
    import threading
    import urllib.request
    from paddle_tpu.inference.serving import generate_http
    got = [None] * len(wave)

    def call(i, r):
        try:
            got[i] = len(generate_http(
                url, r["prompt"], max_new_tokens=r["max_new_tokens"],
                timeout=1200.0))
        except Exception as e:              # reported below, by the parent
            body = e.read()[:300] if hasattr(e, "read") else b""
            got[i] = f"{type(e).__name__}: {e} {body!r}"
    threads = [threading.Thread(target=call, args=(i, r), daemon=True)
               for i, r in enumerate(wave)]
    if len(wave) > 1:
        srv.engine.has_work = lambda: False       # shadows the method
    try:
        for t in threads:
            t.start()
        while len(wave) > 1:
            with urllib.request.urlopen(url + "/health", timeout=60) as r:
                if json.loads(r.read())["queued"] >= len(wave):
                    break
            time.sleep(0.005)
    finally:
        if len(wave) > 1:
            del srv.engine.has_work               # the engine ticks again
    for t in threads:
        t.join(1200.0)
    want = [r["max_new_tokens"] for r in wave]
    if got != want:
        raise RuntimeError(f"a shape-sweep wave came back as {got}, "
                           f"asked {want}")


def queue_waits(tracer, recs) -> list:
    """Seconds each request spent queued, by the program's own phase
    clocks (``observability/tracing.phase_clocks``), for the requests
    whose trace the store kept."""
    out = []
    for r in recs:
        doc = tracer.get(str(r["rid"])) if r["rid"] is not None else None
        clocks = ((doc or {}).get("attrs") or {}).get("clocks")
        if clocks is not None:
            out.append(float(clocks.get("queued", 0.0)))
    return out


def resident_tokens_mean(recs, lo: float, hi: float, step: float = 0.05):
    """Time average over [lo, hi] of the tokens resident in the cache:
    each request holds its prompt from its first token on, plus the
    tokens streamed so far, until its last token."""
    if hi <= lo:
        return None
    ts = np.arange(lo, hi, step)
    total = np.zeros_like(ts)
    for r in recs:
        tt = r["token_times"]
        if not tt:
            continue
        live = (ts >= tt[0]) & (ts <= tt[-1])
        total += live * (r["prompt_len"] + np.searchsorted(tt, ts))
    return float(total.mean())


def measure(recs, t_w0: float, seconds: float, kind: str) -> dict:
    """End-to-end numbers of the measured phase, from the client's
    stamps alone."""
    out = {}
    done = [r for r in recs if r["status"] == "ok"
            and len(r["tokens"]) == r["max_new_tokens"]]
    out["completed"] = len(done)
    if kind == "backlog":
        n = sum(1 for r in recs for t in r["token_times"]
                if t_w0 <= t <= t_w0 + seconds)
        out["serve_tok_s"] = n / seconds
        started = [r for r in recs if r["token_times"]]
        out["attempted"] = len(done) + sum(
            1 for r in recs if r["status"].startswith(("error", "http")))
        out["started"] = len(started)
    else:
        ttft = [(r["token_times"][0] - r["due"]) * 1e3
                for r in recs if r["token_times"]]
        gaps = [(b - a) * 1e3 for r in recs
                for a, b in zip(r["token_times"], r["token_times"][1:])]
        out["ttft_ms"], out["gap_ms"] = ttft, gaps
        out["attempted"] = len(recs)
    out["failed"] = out["attempted"] - len(done)
    return out


def check_sample(recs, seed: int, n: int) -> list:
    """The finished requests to compare: the longest, and a seeded
    draw of the others."""
    done = [r for r in recs if r["status"] == "ok"]
    if not done:
        return []
    done.sort(key=lambda r: (r["prompt_len"] + len(r["tokens"]),
                             r["index"]))
    longest, rest = done[-1], done[:-1]
    rng = traffic.rng_for(seed, 13)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def run(args, cell, token_override=None, control=False) -> int:
    """One run.  ``token_override`` (tests only) alters tokens where
    the server produces them; ``control`` (the control's chip script
    only) reads the lower precision's gap beside the program's."""
    devices = harness.require_chips(cell.chips)
    from paddle_tpu.inference.serving import GenerationServer
    from paddle_tpu.models.llama_pretrain import build_mesh
    from paddle_tpu.models.paged_decode import PagedKVCache
    from paddle_tpu.observability import TraceStore, Tracer

    where = harness.enable_compile_cache()
    clock = harness.CompileClock()
    mix, fam = cell.traffic, cell.family
    sv = mix["server"]
    kind = mix["kind"]
    log(f"{cell.name}: device {devices[0].device_kind} x {len(devices)}; "
        f"compile cache {where}; {sv}")
    cfg = fam.build_cfg(cell.conf, train=False)
    tp = len(devices) > 1
    mesh = build_mesh(mp=len(devices), devices=devices)
    params = fam.make_params(cfg, args.seed, mesh)
    cache = PagedKVCache(cfg, num_pages=sv["num_pages"],
                         pages_max=sv["pages_max"], batch=sv["slots"],
                         page=sv["page"], mesh=mesh if tp else None)
    pool_bytes = cache.kpool.nbytes + cache.vpool.nbytes
    log(f"page pools {pool_bytes / 2**30:.2f} GiB over {len(devices)} "
        f"chip(s)")
    # every request's phase clocks are kept (the default store samples
    # one fast request in ten)
    tracer = Tracer(TraceStore(capacity=8192, keep_slower_than_ms=0.0,
                               sample_every=1))
    srv = GenerationServer(cfg, params, cache, mesh=mesh if tp else None,
                           tracer=tracer)
    if token_override is not None:
        token_override(srv)
    port = srv.start()
    url = f"http://127.0.0.1:{port}"
    out = harness.run_dir(cell)
    child = None
    try:
        snap = clock.snap()
        sweep = traffic.shape_sweep(mix, cfg.vocab_size, args.seed)
        for wave in sweep:
            send_wave(srv, url, wave)
        log(f"shape sweep of {len(sweep)} waves done: "
            f"{clock.since(snap)}")

        phases = traffic.serving_phases(mix, args.seed, args.seconds,
                                        cfg.vocab_size)
        sched = os.path.join(out, "schedule.json")
        with open(sched, "w") as f:
            json.dump({"start_delay_s": 1.0, "phases": phases,
                       "drain_cap_s": mix["drain_cap_s"]}, f)
        results = os.path.join(out, "requests.jsonl")
        child = subprocess.Popen(
            [sys.executable, os.path.join(harness.HERE, "loadgen.py"),
             url, sched, results], stdout=subprocess.PIPE, text=True)
        t0 = float(child.stdout.readline().split()[1])
        t_w0 = t0 + phases[0]["seconds"]
        t_w1 = t_w0 + args.seconds
        sleep_until(t_w0)
        setup_s = t_w0 - harness.T_PROCESS_START
        c0, snap = engine_counters(srv), clock.snap()
        tslice = harness.TraceSlice(os.path.join(out, "trace"))
        t_tr0 = t_tr1 = None
        if args.trace:
            t_tr0 = t_w0 + mix["trace_after_s"]
            sleep_until(t_tr0)
            tslice.start()
            t_tr1 = min(t_tr0 + mix["trace_seconds"], t_w1)
            sleep_until(t_tr1)
            tslice.stop()
        sleep_until(t_w1)
        c1, in_window = engine_counters(srv), clock.since(snap)
        trace = tslice.result()
        rc = child.wait(timeout=args.seconds + mix["drain_cap_s"] + 120)
        if rc != 0:
            raise RuntimeError(f"the load generator exited with {rc}")
        with open(results) as f:
            recs = [json.loads(line) for line in f]
        window = [r for r in recs if r["phase"] == "window"]
        qwaits = queue_waits(tracer, window)
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        srv.stop()
        for t in srv._threads:          # no engine thread at exit
            t.join(30)

    m = measure(window, t_w0, args.seconds, kind)
    counters = {k: c1[k] - c0[k] for k in c0}
    counters.update(compiles_in_window=in_window["compiles"],
                    device_kind=devices[0].device_kind,
                    chips=len(devices))
    late = [(r["sent"] - r["due"]) * 1e3 for r in window
            if r["sent"] is not None]
    log(f"window: {len(window)} requests due, {m['completed']} completed, "
        f"{m['failed']} failed of {m['attempted']} attempted; statuses "
        f"{Counter(r['status'][:200] for r in window).most_common(4)}; engine "
        f"counters {counters}; compiles in window {in_window}")
    log(f"load generator late: median {median(late):.3f} ms "
        f"(n={len(late)}); setup_s {setup_s:.2f}")
    e2e = {"setup_s": {"value": setup_s, "unit": "s"}}
    if kind == "backlog":
        e2e["serve_tok_s"] = {"value": m["serve_tok_s"],
                              "unit": "tokens/s"}
        log(f"requests started {m['started']}, cut at the window's end "
            f"{len(window) - m['attempted']}")
    elif not m["gap_ms"]:
        raise RuntimeError("no request of the window was answered")
    else:
        log(f"ttft: median {median(m['ttft_ms']):.2f} ms "
            f"(n={len(m['ttft_ms'])}); gap: median "
            f"{median(m['gap_ms']):.3f} ms (n={len(m['gap_ms'])})")
        e2e["gap_p95_ms"] = {"value": percentile(m["gap_ms"], 95),
                             "unit": "ms"}

    # the program's peak, then its pools and server freed, then the
    # reference: a process's peak never falls again
    memory_peak = harness.memory_peak_bytes(devices)
    log(f"peak HBM {memory_peak / 2**30:.2f} GiB (the program's: read "
        f"before the reference runs)")
    del srv, cache

    # correct: the served tokens of a seeded sample against the plain
    # reference, once the window has closed
    checks = harness.Checks()
    sample = check_sample(window, args.seed, mix["check_requests"])
    dev = devices[0] if tp else None
    blk = cell.block_reference
    t_ref = time.monotonic()
    gaps, low = [], []
    for r in sample:
        prompt = phases[1]["requests"][r["index"]]["prompt"]
        gaps.append(float(reference.serve_gaps(
            blk, params, cell.conf, prompt, r["tokens"], dev=dev).max()))
        if control:
            low.append(float(reference.serve_gaps(
                blk, params, cell.conf, prompt, r["tokens"], control=True,
                dev=dev).max()))
    n_tok = sum(len(r["tokens"]) for r in sample)
    log(f"reference: {len(sample)} requests, {n_tok} served tokens, "
        f"{time.monotonic() - t_ref:.1f}s (not set-up)")
    checks.add("served_logit_gap_max", max(gaps) if gaps else float("nan"),
               mix["limits"]["served_logit_gap_max"])
    if control:
        log(f"control int8: served_logit_gap_max {max(low):.6g}")
    checks.add("requests_failed", m["failed"], 0)

    if args.trace:
        log(f"end to end (traced run, not for comparison): {e2e}")
        spans = {"late_ms": late, "queue_wait_s": qwaits,
                 "ttft_ms": m.get("ttft_ms"),
                 "resident_tokens": resident_tokens_mean(recs, t_tr0,
                                                         t_tr1),
                 "prompt_lens_started": [
                     r["prompt_len"] for r in recs if r["token_times"]
                     and t_tr0 <= r["token_times"][0] <= t_tr1],
                 "trace_s": t_tr1 - t_tr0}
        metrics = harness.read_layer_metrics(cell, trace, counters, spans)
    else:
        metrics = e2e
    harness.result_line(cell, devices, bool(args.trace), checks.ok,
                        m["attempted"], m["failed"], metrics, memory_peak,
                        trace)
    return 0
