"""The one general traffic generator.  A mix is a data file,
``benchmark/traffic/<name>.json``; this module turns it and a seed into
the phases the load generator replays (serving) or the job the step
loop runs (training).

Every seed gets the SAME multiset of prompt lengths, output lengths and
arrival gaps (the stratified quantiles of the mix's distributions), in
another order and with other token ids: a seed then changes which
request meets which, never how much work a run holds.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator keyed by the run's seed (any size) and a stream."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                  int(seed) >> 32, *stream])


def quantile_lengths(dist: dict, n: int) -> list:
    """``n`` lengths at the stratified quantiles (i + 0.5) / n of
    ``dist``, clipped to its [min, max].  Deterministic: no seed."""
    u = [(i + 0.5) / n for i in range(n)]
    kind = dist["dist"]
    if kind == "lognormal":
        mu, sigma = math.log(dist["median"]), dist["sigma"]
        raw = [math.exp(mu + sigma * NormalDist().inv_cdf(x)) for x in u]
    elif kind == "uniform":
        raw = [dist["min"] + (dist["max"] - dist["min"]) * x for x in u]
    elif kind == "fixed":
        raw = [dist["value"]] * n
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo = dist.get("min", 1)
    hi = dist.get("max", max(raw))
    return [int(min(max(round(x), lo), hi)) for x in raw]


def poisson_gaps(n: int, seconds: float) -> list:
    """``n`` exponential gaps at stratified quantiles, scaled so that
    the last arrival falls inside ``seconds``."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = seconds / sum(raw) * n / (n + 0.5)
    return [g * scale for g in raw]


def _requests(mix: dict, n: int, seconds: float, vocab: int,
              rng: np.random.Generator, backlog: bool) -> list:
    prompts = quantile_lengths(mix["prompt_len"], n)
    outs = quantile_lengths(mix["output_len"], n)
    rng.shuffle(prompts)
    rng.shuffle(outs)
    if backlog:
        due = [0.0] * n
    else:
        gaps = poisson_gaps(n, seconds)
        rng.shuffle(gaps)
        due = list(np.cumsum(gaps))
    return [{"due": float(d), "max_new_tokens": int(o),
             "prompt": rng.integers(1, vocab, size=p).tolist()}
            for d, p, o in zip(due, prompts, outs)]


def serving_phases(mix: dict, seed: int, seconds: float,
                   vocab: int) -> list:
    """[warm-up phase, measured phase].  ``open_loop``: arrivals at
    ``rate_rps``; ``backlog``: ``backlog_per_s * seconds`` requests all
    due at the phase's start and cut at its end."""
    backlog = mix["kind"] == "backlog"
    phases = []
    for stream, (name, secs) in enumerate(
            (("warmup", float(mix["warmup_s"])), ("window", seconds))):
        per_s = mix["backlog_per_s"] if backlog else mix["rate_rps"]
        n = max(int(round(per_s * secs)), 1)
        phases.append({
            "name": name, "seconds": secs, "cut_at_end": backlog,
            "requests": _requests(mix, n, secs, vocab,
                                  rng_for(seed, 7, stream), backlog)})
    return phases


def shape_sweep(mix: dict, vocab: int, seed: int) -> list:
    """Waves sent one after another before any phase, so that the
    programs the phases need are compiled (or loaded) first: one short
    request for every page count a prompt of the mix can have (its own
    slice, scatter and packed bucket), then waves of 2, 3, 4 ... of the
    longest prompt, up to the server's ``wave_tokens_max``: they fill
    the larger packed buckets, and each wave size has eager programs of
    its own (the first-token tail is shaped by the requests in a wave).
    A wave is a list of requests that are queued together and admitted
    as one."""
    sv = mix["server"]
    page = sv["page"]
    rng = rng_for(seed, 11)

    def req(tokens):
        return {"prompt": rng.integers(1, vocab, size=tokens).tolist(),
                "max_new_tokens": 2}
    lo = -(-mix["prompt_len"].get("min", 1) // page)
    hi = -(-mix["prompt_len"]["max"] // page)
    waves = [[req(k * page)] for k in range(lo, hi + 1)]
    g = 2
    while g <= sv["slots"] and g * hi * page <= sv["wave_tokens_max"]:
        waves.append([req(hi * page) for _ in range(g)])
        g += 1
    return waves
