"""Benchmark driver: flagship training throughput on one TPU chip.

Prints THREE JSON lines (one metric each):
  1. LLaMA 1.345B pretrain tokens/s/chip — fed through the REAL input
     pipeline (paddle_tpu.io.DataLoader, 2 spawned workers, shared
     memory) instead of device-resident buffers, so the number includes
     host batch production + H2D transfer (round-3 verdict item 6).
  2. ResNet50 ``incubate.jit_train_step`` images/s (BASELINE config 2)
     with bf16 AMP O1.
  3. BERT-base SQuAD-style fine-tune samples/s (BASELINE config 3):
     12 layers, hidden 768, REAL dropout 0.1, AdamW, AMP O1, b32 s384.

``vs_baseline`` for line 1 is model-FLOPs-utilisation against the 45%
MFU a well-tuned A100 LLaMA pretrain achieves; for line 2 it is img/s
against the ~1,700 img/s A100 mixed-precision ResNet50 bar; for line 3
it is samples/s against the ~180 samples/s top of the A100
mixed-precision BERT-base fine-tune band (BASELINE.md; the reference
publishes no absolute numbers in-tree).

Robustness: backend init is retried with exponential backoff and a
hard per-attempt timeout, and every failure path emits a structured
JSON line instead of a raw traceback.  Exit code is 0 iff EVERY metric
line carries a measurement: a line that raises still prints its error
line (the others run), and makes the exit code non-zero.

What makes the 1.345B fit one 16GB v5e chip (see PERF.md):
  * Adafactor (factored second moment) — optimizer state drops from
    2x params fp32 (10.8 GB) to row/col vectors (~13 MB);
  * chunked cross-entropy ON (no fp32 [B,S,V] logits round-trip);
  * full-block rematerialisation (activations = one [L,B,S,H] carry).
"""

from __future__ import annotations

import json
import os
import sys
import time


class SyntheticTokens:
    """Module-level (picklable -> spawned workers) synthetic token
    dataset; per-index seeding keeps batches deterministic."""

    def __init__(self, n, seq, vocab):
        self.n, self.seq, self.vocab = n, seq, vocab

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        import numpy as np
        rng = np.random.RandomState(i)
        return rng.randint(0, self.vocab,
                           (self.seq + 1,)).astype(np.int64)


def _clear_backends() -> None:
    """Drop any cached (failed) backend state so a retry actually
    re-initialises the PjRt client instead of replaying the error."""
    from jax.extend import backend as _eb
    _eb.clear_backends()


def _bench_metrics(registry=None):
    """Register the bench's counters (process-wide default registry
    unless a fresh one is passed — the observability lint test does)."""
    from paddle_tpu.observability import default_registry
    r = registry if registry is not None else default_registry()
    return {
        "attempts": r.counter(
            "paddle_tpu_bench_backend_init_attempts_total",
            "Backend-init attempts (success or not)"),
        "failures": r.counter(
            "paddle_tpu_bench_backend_init_failures_total",
            "Backend-init attempts that raised"),
        "timeouts": r.counter(
            "paddle_tpu_bench_backend_init_timeouts_total",
            "Backend-init attempts aborted by the per-attempt "
            "hard timeout"),
    }


def _probe_devices():
    import jax
    devs = jax.devices()
    if not devs:
        raise RuntimeError("jax.devices() returned an empty list")
    return devs


def _init_devices(max_tries: int = 4, base_delay: float = 15.0,
                  attempt_timeout: float = None, attempt_fn=None):
    """jax.devices() with retry/backoff AND a hard per-attempt timeout.

    Backend init can fail transiently ("UNAVAILABLE: TPU backend
    setup/compile error" — run r04 of ROADMAP.md's table lost its whole
    capture to that) or wedge (r05: ONE attempt sat inside backend init
    until the driver's timeout).  Each attempt runs in a daemon thread
    bounded by
    ``attempt_timeout`` seconds (PADDLE_TPU_BENCH_INIT_TIMEOUT_S,
    default 120): a wedged attempt is abandoned, logged as a
    structured ``backend_init_attempt`` heartbeat (stderr JSON + the
    observability event ring + registry counters), and the loop moves
    on — one stuck attempt can never consume the driver's budget.

    Returns (devices, None) on success or (None, error_string) after
    exhausting retries.
    """
    import threading

    from paddle_tpu.observability import default_ring
    max_tries = int(os.environ.get("PADDLE_TPU_BENCH_INIT_TRIES",
                                   max_tries))
    base_delay = float(os.environ.get("PADDLE_TPU_BENCH_INIT_BACKOFF",
                                      base_delay))
    if attempt_timeout is None:
        attempt_timeout = float(os.environ.get(
            "PADDLE_TPU_BENCH_INIT_TIMEOUT_S", 120.0))
    fn = attempt_fn or _probe_devices
    mets = _bench_metrics()
    ring = default_ring()
    last_err = None
    for attempt in range(max_tries):
        box = {}

        def run():
            try:
                box["devs"] = fn()
            except Exception as e:  # backend init failure
                box["err"] = f"{type(e).__name__}: {str(e)[:300]}"

        t0 = time.monotonic()
        worker = threading.Thread(target=run, daemon=True,
                                  name=f"backend-init-{attempt}")
        worker.start()
        worker.join(attempt_timeout)
        mets["attempts"].inc()
        timed_out = worker.is_alive()
        if timed_out:
            # abandon the wedged daemon thread — joining again would
            # hand it the rest of the budget
            last_err = (f"attempt timed out after "
                        f"{attempt_timeout:.0f}s (hard per-attempt "
                        f"limit)")
            mets["timeouts"].inc()
        elif "devs" in box:
            ev = {"event": "backend_init_attempt",
                  "attempt": attempt + 1, "of": max_tries, "ok": True,
                  "elapsed_s": round(time.monotonic() - t0, 3)}
            ring.emit("backend_init_attempt",
                      **{k: v for k, v in ev.items() if k != "event"})
            print(json.dumps(ev), file=sys.stderr, flush=True)
            return box["devs"], None
        else:
            last_err = box.get("err", "unknown failure")
            mets["failures"].inc()
        ev = {"event": "backend_init_attempt", "attempt": attempt + 1,
              "of": max_tries, "ok": False,
              "elapsed_s": round(time.monotonic() - t0, 3),
              "error": last_err}
        ring.emit("backend_init_attempt",
                  **{k: v for k, v in ev.items() if k != "event"})
        print(json.dumps(ev), file=sys.stderr, flush=True)
        if attempt < max_tries - 1:
            if not timed_out:
                # a wedged attempt still holds backend state in its
                # abandoned thread; clearing under it could deadlock
                _clear_backends()
            time.sleep(base_delay * (2 ** attempt))
    return None, last_err


def _error_line(metric: str, unit: str, err: str) -> dict:
    return {"metric": metric, "value": 0, "unit": unit,
            "vs_baseline": 0, "extra": {"error": err[:300]}}


def _llama_line() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models.llama_pretrain import (
        LlamaPretrainConfig, build_mesh, init_params,
        init_adafactor_state, make_train_step)

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"

    if on_tpu:
        # 1.345B params: hidden 2048, ffn 5504, 24 layers, 16 heads of
        # head_dim 128 (the MXU-native head size, see PERF.md).  Measured
        # (v5e 16GB, 2026-07): b=8 full-remat adafactor; b=10 compiles
        # but drops to 44%; b>=12 / flash-saved / AdamW-bf16-moments
        # exceed HBM.  loss_chunks=4 measured best of {2, 4, 8}.
        cfg = LlamaPretrainConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5504,
            num_hidden_layers=24, num_attention_heads=16,
            num_key_value_heads=16, max_seq_len=2048,
            use_pallas_attention=True, sequence_parallel=False,
            remat=True, remat_policy="full", dtype=jnp.bfloat16,
            loss_chunks=4)
        batch, seq = 8, 2048
        steps = 10
        metric = "llama_1.3b_pretrain_tokens_per_sec_per_chip"
    else:
        cfg = LlamaPretrainConfig(
            vocab_size=512, hidden_size=128, intermediate_size=384,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=8, max_seq_len=256,
            use_pallas_attention=False, sequence_parallel=False,
            remat=True, dtype=jnp.float32)
        batch, seq = 4, 256
        steps = 3
        metric = "llama_tiny_cpu_smoke_tokens_per_sec"

    # REAL input pipeline: token batches are produced by spawned
    # DataLoader workers and cross host->device each step.  The shm
    # transport + 2 workers must sustain the chip (PERF.md quantifies
    # the gap vs device-resident buffers).
    from paddle_tpu.io import DataLoader

    loader = DataLoader(SyntheticTokens((steps + 4) * batch, seq,
                                        cfg.vocab_size),
                        batch_size=batch, num_workers=2,
                        use_shared_memory=True)

    mesh = build_mesh(dp=1, pp=1, sharding=1, sep=1, mp=1,
                      devices=jax.devices()[:1])
    with mesh:
        params = init_params(cfg, jax.random.PRNGKey(0), mesh, pp=1)
        opt_state = init_adafactor_state(params)
        step = make_train_step(cfg, mesh, pp=1, microbatches=1, lr=1e-2,
                               optimizer="adafactor")

        it = iter(loader)

        def next_tokens():
            b = next(it)
            arr = b.numpy() if hasattr(b, "numpy") else np.asarray(b)
            return jnp.asarray(arr)

        # warmup/compile.  The fence is a host transfer
        # (float(loss)): nothing after it can start before the step
        # has really finished.
        params, opt_state, loss = step(params, opt_state, next_tokens())
        float(loss)
        params, opt_state, loss = step(params, opt_state, next_tokens())
        float(loss)

        t0 = time.perf_counter()
        for i in range(steps):
            params, opt_state, loss = step(params, opt_state,
                                           next_tokens())
        loss_val = float(loss)  # fence: steps chain via donated params
        dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * steps / dt
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    # bf16 peak from the package's one table (keyed by device_kind; an
    # unknown device raises there)
    from paddle_tpu.device.peaks import chip_peaks
    mfu = tokens_per_sec * 6.0 * n_params / chip_peaks().flops
    return {
        "metric": metric,
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {"platform": platform, "params": n_params,
                  "mfu": round(mfu, 4), "loss": loss_val,
                  "step_ms": round(dt / steps * 1000, 1),
                  "optimizer": "adafactor",
                  "data": "DataLoader(2 spawned workers, shm)"},
    }


def _resnet_line() -> dict:
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.incubate import jit_train_step
    from paddle_tpu.vision import models as vmodels

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        model = vmodels.resnet50(num_classes=1000)
        batch, hw, classes, steps = 256, 224, 1000, 5
        metric = "resnet50_train_images_per_sec"
        baseline = 1700.0      # A100 mixed-precision img/s band
    else:
        model = vmodels.resnet18(num_classes=10)
        batch, hw, classes, steps = 8, 64, 10, 2
        metric = "resnet_tiny_cpu_smoke_images_per_sec"
        baseline = 1.0
    model.train()
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
    step = jit_train_step(model, paddle.nn.CrossEntropyLoss(), opt,
                          amp_level="O1")
    rng = np.random.RandomState(0)
    xs = [paddle.to_tensor(rng.randn(batch, 3, hw, hw)
                           .astype(np.float32)) for _ in range(2)]
    ys = [paddle.to_tensor(rng.randint(0, classes, (batch,))
                           .astype(np.int64)) for _ in range(2)]
    float(step(xs[0], ys[0]))          # compile + fence
    float(step(xs[1], ys[1]))
    t0 = time.perf_counter()
    loss = None
    for i in range(steps):
        loss = step(xs[i % 2], ys[i % 2])
    loss_val = float(loss)             # fence
    dt = time.perf_counter() - t0
    img_s = batch * steps / dt
    return {
        "metric": metric,
        "value": round(img_s, 2),
        "unit": "images/s",
        "vs_baseline": round(img_s / baseline, 4),
        "extra": {"platform": platform, "batch": batch,
                  "amp": "O1-bf16", "loss": loss_val,
                  "step_ms": round(dt / steps * 1000, 1)},
    }


def _bert_line() -> dict:
    """BASELINE config 3: BERT-base SQuAD-style QA fine-tune through
    ``incubate.jit_train_step`` — AdamW, AMP O1 bf16, REAL dropout 0.1
    (per-step PRNG threaded into the trace).  Loss-trajectory parity vs
    the eager loop is pinned by tests/test_jit_train_step.py::
    test_jit_train_step_bert_qa_finetune_compiled; this line makes the
    throughput driver-capturable (round-4 verdict weak item 7)."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.incubate import jit_train_step
    from paddle_tpu.models.bert import BertConfig, BertForQuestionAnswering

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        cfg = BertConfig(dropout_prob=0.1)     # dataclass defaults ARE base
        batch, seq, steps = 32, 384, 5
        metric = "bert_base_squad_finetune_samples_per_sec"
        baseline = 180.0   # top of the A100 mixed-precision band
    else:
        cfg = BertConfig(vocab_size=128, hidden_size=32,
                         num_hidden_layers=2, num_attention_heads=2,
                         intermediate_size=64,
                         max_position_embeddings=64, dropout_prob=0.1)
        batch, seq, steps = 4, 16, 2
        metric = "bert_tiny_cpu_smoke_samples_per_sec"
        baseline = 1.0

    paddle.seed(55)
    net = BertForQuestionAnswering(cfg)
    net.train()
    opt = paddle.optimizer.AdamW(learning_rate=5e-5,
                                 parameters=net.parameters())
    ce = paddle.nn.CrossEntropyLoss()

    def qa_loss(out, ys):
        s_logits, e_logits = out
        s_y, e_y = ys
        return (ce(s_logits, s_y) + ce(e_logits, e_y)) * 0.5

    step = jit_train_step(net, qa_loss, opt, amp_level="O1")
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64))
    tt = paddle.to_tensor(np.zeros((batch, seq), np.int64))
    mask = paddle.to_tensor(np.ones((batch, seq), np.float32))
    start = paddle.to_tensor(rng.randint(0, seq, (batch,)).astype(np.int64))
    end = paddle.to_tensor(rng.randint(0, seq, (batch,)).astype(np.int64))

    float(step((ids, tt, mask), (start, end)))   # compile + fence
    float(step((ids, tt, mask), (start, end)))
    t0 = time.perf_counter()
    loss = None
    for _ in range(steps):
        loss = step((ids, tt, mask), (start, end))
    loss_val = float(loss)                        # fence
    dt = time.perf_counter() - t0
    sps = batch * steps / dt
    return {
        "metric": metric,
        "value": round(sps, 2),
        "unit": "samples/s",
        "vs_baseline": round(sps / baseline, 4),
        "extra": {"platform": platform, "batch": batch, "seq": seq,
                  "amp": "O1-bf16", "dropout": cfg.dropout_prob,
                  "optimizer": "adamw", "loss": loss_val,
                  "step_ms": round(dt / steps * 1000, 1)},
    }


_SERVING_ENGINE = None      # keeps weakref-backed gauges readable
_SERVING_SYNC_TPS = None    # sync tok/s, for the overlap A/B speedup


def _hb_sums():
    """(host_bookkeeping.sum, decode_step.sum) from the process-wide
    registry — deltas over a timed window give that window's
    host_overhead_frac."""
    from paddle_tpu.observability import default_registry
    snap = default_registry().snapshot()
    h = snap.get("paddle_tpu_engine_host_bookkeeping_seconds") or {}
    d = snap.get("paddle_tpu_engine_decode_step_seconds") or {}
    return h.get("sum", 0.0), d.get("sum", 0.0)


def _serving_run(overlap: bool, decode_horizon: int = 1) -> dict:
    """Continuous-batching serving decode throughput — requests
    streamed through the paged-KV engine with observability ON (the
    engine publishes to the process-wide registry, so the final
    ``metrics_snapshot`` line carries occupancy / cache / lifecycle
    counters alongside this number).  Called twice for the
    sync-vs-overlap A/B: ``overlap=False`` is the blocking
    dispatch-per-token loop, ``overlap=True`` the dispatch-ahead
    pipeline (same workload, fresh engine + cache).
    ``decode_horizon=H`` fuses H micro-steps per dispatch in either
    lane; the reported ``host_overhead_frac`` (host bookkeeping
    seconds / decode-step seconds over the timed window) is what the
    horizon amortizes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.models.llama_pretrain import (LlamaPretrainConfig,
                                                  init_params)
    from paddle_tpu.models.paged_decode import PagedKVCache
    from paddle_tpu.models.serving_engine import ContinuousBatchingEngine
    from paddle_tpu.observability import default_registry, default_ring

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        cfg = LlamaPretrainConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=8, max_seq_len=2048,
            use_pallas_attention=True, remat=False,
            dtype=jnp.bfloat16)
        batch, n_req, prompt_len, new, page = 8, 16, 128, 64, 64
        num_pages, pages_max = 64, 8
        metric = ("serving_engine_overlap_decode_tokens_per_sec"
                  if overlap else "serving_engine_decode_tokens_per_sec")
    else:
        cfg = LlamaPretrainConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_seq_len=256, dtype=jnp.float32,
            param_dtype=jnp.float32, remat=False, loss_chunks=1,
            use_pallas_attention=False)
        batch, n_req, prompt_len, new, page = 2, 4, 12, 8, 16
        num_pages, pages_max = 64, 8
        metric = ("serving_tiny_cpu_smoke_overlap_tokens_per_sec"
                  if overlap else "serving_tiny_cpu_smoke_tokens_per_sec")

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "pp", "sharding", "sep", "mp"))
    params = init_params(cfg, jax.random.PRNGKey(0), mesh)
    cache = PagedKVCache(cfg, num_pages=num_pages,
                         pages_max=pages_max, batch=batch, page=page)
    eng = ContinuousBatchingEngine(
        cfg, params, cache, metrics_registry=default_registry(),
        metrics_ring=default_ring(), overlap=overlap,
        decode_horizon=decode_horizon)
    # pin the engine so the final metrics_snapshot line reads LIVE
    # gauge values (the scrape callbacks hold weakrefs and would read
    # 0 once the engine is collected)
    global _SERVING_ENGINE, _SERVING_SYNC_TPS
    _SERVING_ENGINE = eng
    rng = np.random.RandomState(0)

    # warm/compile end to end with the SAME admission shape as the
    # timed window (n_req same-bucket arrivals = one batched-prefill
    # program of width next_pow2(n_req)) — otherwise the first mode
    # measured pays that compile inside its window and the
    # sync-vs-overlap A/B is meaningless
    for _ in range(n_req):
        eng.submit(rng.randint(1, cfg.vocab_size, (prompt_len,)),
                   max_new_tokens=4)
    eng.run_to_completion()

    # report deltas over the TIMED window only (the lifetime counters
    # in the snapshot line include the warmup request)
    steps0, prefills0 = eng.decode_steps, eng.prefill_calls
    syncs0, flushes0 = eng.host_syncs, eng.pipeline_flushes
    preempt0 = eng.preemptions
    hb0, dec0 = _hb_sums()
    t0 = time.perf_counter()
    for _ in range(n_req):
        eng.submit(rng.randint(1, cfg.vocab_size, (prompt_len,)),
                   max_new_tokens=new)
    done = eng.run_to_completion()
    dt = time.perf_counter() - t0
    hb1, dec1 = _hb_sums()
    steps = eng.decode_steps - steps0
    tokens = sum(len(r.generated) for r in done)
    tps = tokens / dt
    extra = {"platform": platform, "requests": n_req,
             "batch_slots": batch, "tokens": tokens,
             "decode_steps": steps,
             "prefill_dispatches": eng.prefill_calls - prefills0,
             "preemptions": eng.preemptions - preempt0,
             "overlap": "on" if overlap else "off",
             "decode_horizon": decode_horizon,
             "host_syncs": eng.host_syncs - syncs0,
             "pipeline_flushes": eng.pipeline_flushes - flushes0,
             "host_overhead_frac": round(
                 (hb1 - hb0) / max(dec1 - dec0, 1e-12), 4),
             "step_ms": round(dt / max(steps, 1) * 1000, 2)}
    if overlap:
        if _SERVING_SYNC_TPS:
            extra["speedup_vs_sync"] = round(tps / _SERVING_SYNC_TPS, 4)
    else:
        _SERVING_SYNC_TPS = tps
    return {
        "metric": metric,
        "value": round(tps, 2),
        "unit": "tokens/s",
        "vs_baseline": 0,
        "extra": extra,
    }


def _admission_line() -> dict:
    """Packed-vs-batched ADMISSION A/B on a mixed-length arrival
    trace: the same prompts admit through the batched per-bucket lane
    (``packed=False`` — one dense [K_pow2, Lp] dispatch per length
    bucket per wave) and the packed varlen lane (one segmented-flash
    dispatch per wave, padding only the sub-bucket remainder).  Per
    side: ``prefill_calls`` for the admission wave,
    ``padded_token_frac`` (dispatched prefill slots carrying no real
    context), ``admission_ms`` (wall of the step() that admits the
    whole wave), and steady-state decode tok/s to pin the
    no-regression criterion.  ``value`` is the batched/packed
    admission-wall ratio (>1 = packed faster)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.models.llama_pretrain import (LlamaPretrainConfig,
                                                  init_params)
    from paddle_tpu.models.paged_decode import PagedKVCache
    from paddle_tpu.models.serving_engine import ContinuousBatchingEngine

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        cfg = LlamaPretrainConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=8, max_seq_len=2048,
            use_pallas_attention=True, remat=False,
            dtype=jnp.bfloat16)
        batch, new, page = 8, 32, 64
        num_pages, pages_max = 96, 16
        # mixed-length arrival trace: a long-tail spread across four
        # length buckets — the batched lane pays one dispatch each
        trace = [640, 64, 96, 500, 128, 72, 320, 200]
        metric = "serving_admission_packed_vs_batched"
    else:
        cfg = LlamaPretrainConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_seq_len=256, dtype=jnp.float32,
            param_dtype=jnp.float32, remat=False, loss_chunks=1,
            use_pallas_attention=False)
        batch, new, page = 4, 8, 16
        num_pages, pages_max = 64, 8
        trace = [100, 5, 9, 12]
        metric = "serving_admission_tiny_cpu_smoke_packed_vs_batched"

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "pp", "sharding", "sep", "mp"))
    params = init_params(cfg, jax.random.PRNGKey(0), mesh)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, (L,)) for L in trace]

    def run(packed):
        cache = PagedKVCache(cfg, num_pages=num_pages,
                             pages_max=pages_max, batch=batch,
                             page=page)
        eng = ContinuousBatchingEngine(cfg, params, cache,
                                       metrics_registry=False,
                                       packed=packed)
        # warm every compile the timed wave will hit (same shape mix)
        for p in prompts:
            eng.submit(p, max_new_tokens=2)
        eng.run_to_completion()
        calls0 = eng.prefill_calls
        slots0, padded0 = eng.prefill_token_slots, \
            eng.prefill_padded_tokens
        for p in prompts:
            eng.submit(p, max_new_tokens=new)
        t0 = time.perf_counter()
        eng.step()                    # the admission wave (+1 decode)
        admission_ms = (time.perf_counter() - t0) * 1000
        waves = 1
        while eng._queue:             # batch smaller than the trace:
            eng.step()                # later waves admit as slots free
            waves += 1
        t1 = time.perf_counter()
        done = eng.run_to_completion()
        decode_s = time.perf_counter() - t1
        slots = eng.prefill_token_slots - slots0
        return {
            "prefill_calls": eng.prefill_calls - calls0,
            "admission_waves": waves,
            "padded_token_frac": round(
                (eng.prefill_padded_tokens - padded0) / max(slots, 1),
                4),
            "admission_ms": round(admission_ms, 2),
            "decode_tok_per_s": round(
                sum(len(r.generated) for r in done)
                / max(decode_s + admission_ms / 1000, 1e-9), 1),
        }

    batched = run(False)
    packed = run(True)
    speed = batched["admission_ms"] / max(packed["admission_ms"], 1e-9)
    return {
        "metric": metric,
        "value": round(speed, 4),
        "unit": "x",
        "vs_baseline": 0,
        "extra": {"platform": platform, "trace_lens": trace,
                  "batch_slots": batch, "batched": batched,
                  "packed": packed},
    }


def _preemption_line() -> dict:
    """Two-tier KV cache A/B under PREEMPTION PRESSURE: the same
    request trace runs through a pool deliberately too small to hold
    every active context (forcing evict + requeue churn) with the
    host-RAM page tier off and on.  Per side: preemption count, how
    each resume happened (recompute re-prefill vs host-tier page
    restore), mean resume-admission wall, prefill tokens the offload
    path avoided, bytes swapped, and end-to-end decode tok/s.
    ``value`` is the recompute/swap resume-latency ratio (>1 = the
    restore path resumes faster).  Engines publish to the process-wide
    registry so the final ``metrics_snapshot`` line carries the swap
    counters."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.models.llama_pretrain import (LlamaPretrainConfig,
                                                  init_params)
    from paddle_tpu.models.paged_decode import PagedKVCache
    from paddle_tpu.models.serving_engine import ContinuousBatchingEngine
    from paddle_tpu.observability import default_registry, default_ring

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        cfg = LlamaPretrainConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=8, max_seq_len=2048,
            use_pallas_attention=True, remat=False,
            dtype=jnp.bfloat16)
        batch, page = 4, 64
        prompt_len, new = 256, 192
        # 4 requests of up to 7 pages each through 17 usable pages:
        # two run, admitting a third preempts
        num_pages, pages_max, host_pages = 18, 8, 64
        metric = "serving_preemption_offload_resume_ab"
    else:
        cfg = LlamaPretrainConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_seq_len=256, dtype=jnp.float32,
            param_dtype=jnp.float32, remat=False, loss_chunks=1,
            use_pallas_attention=False)
        batch, page = 2, 16
        prompt_len, new = 16, 20
        # 4 usable pages; 2 requests peak at 3 pages each -> preempt
        num_pages, pages_max, host_pages = 5, 4, 16
        metric = "serving_preemption_tiny_cpu_smoke_offload_resume_ab"

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "pp", "sharding", "sep", "mp"))
    params = init_params(cfg, jax.random.PRNGKey(0), mesh)
    rng = np.random.RandomState(0)
    n_req = batch + 2
    prompts = [rng.randint(1, cfg.vocab_size, (prompt_len,))
               for _ in range(n_req)]

    def run(offload):
        cache = PagedKVCache(cfg, num_pages=num_pages,
                             pages_max=pages_max, batch=batch,
                             page=page,
                             host_pages=host_pages if offload else 0)
        eng = ContinuousBatchingEngine(
            cfg, params, cache, metrics_registry=default_registry(),
            metrics_ring=default_ring())
        # warm every compile this trace hits — including the
        # preempt/swap/resume shapes, so the A/B measures steady
        # state, not jit (a short-budget warmup would never preempt)
        for p in prompts[:batch + 1]:
            eng.submit(p, max_new_tokens=new)
        eng.run_to_completion()
        # snapshot the lifetime counters so the reported numbers are
        # timed-window DELTAS — the warmup's first resume pays the
        # prefill compile and would otherwise dominate resume_ms_mean
        base = dict(preempt=eng.preemptions,
                    rec=eng.resumes_recompute,
                    swp=eng.resumes_swapped,
                    wall=eng.resume_wall_s, ev=eng.resume_events,
                    avoided=eng.prefill_tokens_avoided,
                    out=cache.swap_out_pages, inn=cache.swap_in_pages,
                    byt=cache.swap_bytes,
                    slots=eng.prefill_token_slots)
        t0 = time.perf_counter()
        for p in prompts:
            eng.submit(p, max_new_tokens=new)
        done = eng.run_to_completion()
        dt = time.perf_counter() - t0
        tokens = sum(len(r.generated) for r in done)
        events = eng.resume_events - base["ev"]
        return {
            "preemptions": eng.preemptions - base["preempt"],
            "resumes_recompute": eng.resumes_recompute - base["rec"],
            "resumes_swapped": eng.resumes_swapped - base["swp"],
            "resume_ms_mean": round(
                (eng.resume_wall_s - base["wall"])
                / max(events, 1) * 1000, 3),
            "prefill_tokens_avoided":
                eng.prefill_tokens_avoided - base["avoided"],
            "swap_out_pages": cache.swap_out_pages - base["out"],
            "swap_in_pages": cache.swap_in_pages - base["inn"],
            "swap_bytes": cache.swap_bytes - base["byt"],
            "decode_tok_per_s": round(tokens / dt, 1),
            "prefill_token_slots":
                eng.prefill_token_slots - base["slots"],
        }

    off = run(False)
    on = run(True)
    speed = (off["resume_ms_mean"]
             / max(on["resume_ms_mean"], 1e-9)) \
        if on["resumes_swapped"] else 0.0
    return {
        "metric": metric,
        "value": round(speed, 4),
        "unit": "x",
        "vs_baseline": 0,
        "extra": {"platform": platform, "requests": n_req,
                  "batch_slots": batch, "prompt_len": prompt_len,
                  "max_new_tokens": new, "host_pages": host_pages,
                  "offload_off": off, "offload_on": on},
    }


def _fault_recovery_line() -> dict:
    """Serving under INJECTED FAULTS (testing/faults.py): the same
    request trace runs fault-free and with a step-dispatch exception
    injected every K decode dispatches — each fault quarantines the
    active wave (error done-messages, engine stays up) — plus one
    consecutive burst that escapes quarantine (engines run
    ``max_consecutive_faults=1`` so the burst costs one extra wave,
    not four) into an EngineSupervisor restart (queued requests
    transplant).  Reports
    the recovered-request rate, per-request p99 latency added by the
    fault load, quarantine and restart counts.  ``value`` is the
    recovered fraction of the faulted window."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.models.llama_pretrain import (LlamaPretrainConfig,
                                                  init_params)
    from paddle_tpu.models.paged_decode import PagedKVCache
    from paddle_tpu.models.serving_engine import (
        ContinuousBatchingEngine, EngineSupervisor)
    from paddle_tpu.observability import default_registry, default_ring
    from paddle_tpu.testing import faults

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        cfg = LlamaPretrainConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=8, max_seq_len=2048,
            use_pallas_attention=True, remat=False,
            dtype=jnp.bfloat16)
        batch, n_req, prompt_len, new, page = 8, 24, 128, 48, 64
        num_pages, pages_max = 64, 8
        fault_every, burst_at = 40, 25
        metric = "serving_fault_recovery"
    else:
        cfg = LlamaPretrainConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_seq_len=256, dtype=jnp.float32,
            param_dtype=jnp.float32, remat=False, loss_chunks=1,
            use_pallas_attention=False)
        batch, n_req, prompt_len, new, page = 2, 12, 12, 8, 16
        num_pages, pages_max = 64, 8
        fault_every, burst_at = 17, 8
        metric = "serving_fault_recovery_tiny_cpu_smoke"

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "pp", "sharding", "sep", "mp"))
    params = init_params(cfg, jax.random.PRNGKey(0), mesh)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, (prompt_len,))
               for _ in range(n_req)]

    def factory():
        cache = PagedKVCache(cfg, num_pages=num_pages,
                             pages_max=pages_max, batch=batch,
                             page=page)
        return ContinuousBatchingEngine(
            cfg, params, cache, metrics_registry=default_registry(),
            metrics_ring=default_ring(), max_consecutive_faults=1)

    def run(faulted):
        sup = EngineSupervisor(factory, max_restarts=4, backoff_s=0.0)
        # warm every compile the timed window hits, fault-free
        for p in prompts[:batch]:
            sup.submit(p, max_new_tokens=4)
        sup.run_to_completion()
        restarts0 = sup.restarts
        fp = faults.install() if faulted else None
        try:
            if faulted:
                fp.inject("step_dispatch",
                          RuntimeError("bench injected fault"),
                          every=fault_every)
                for j in range(2):     # consecutive burst: escapes
                    #   quarantine (max 1 in a row here) -> supervisor
                    fp.inject("step_dispatch",
                              RuntimeError("bench injected burst"),
                              nth=burst_at + j)
            t0 = time.perf_counter()
            for p in prompts:
                sup.submit(p, max_new_tokens=new)
            done = sup.run_to_completion()
            dt = time.perf_counter() - t0
            quarantines = fp.fired.get("step_dispatch", 0) \
                if faulted else 0
        finally:
            if faulted:
                faults.uninstall()
        ok = [r for r in done if r.status == "ok"]
        lats = sorted((r.t_finish - r.t_submit) * 1000 for r in ok)
        p99 = (lats[min(len(lats) - 1, int(0.99 * len(lats)))]
               if lats else 0.0)
        tokens = sum(len(r.generated) for r in ok)
        return {"requests": len(done), "recovered": len(ok),
                "faulted_requests":
                    sum(1 for r in done if r.status == "error"),
                "recovered_rate": round(len(ok) / max(len(done), 1),
                                        4),
                "p99_ms": round(p99, 2),
                "decode_tok_per_s": round(tokens / dt, 1),
                "injected_faults": quarantines,
                "restarts": sup.restarts - restarts0}

    clean = run(False)
    faulty = run(True)
    return {
        "metric": metric,
        "value": faulty["recovered_rate"],
        "unit": "ratio",
        "vs_baseline": 0,
        "extra": {"platform": platform, "requests": n_req,
                  "batch_slots": batch,
                  "fault_every_k_dispatches": fault_every,
                  "added_p99_ms": round(
                      faulty["p99_ms"] - clean["p99_ms"], 2),
                  "fault_free": clean, "faulted": faulty},
    }


def _fleet_line() -> dict:
    """FLEET serving A/B (PR-8 tentpole): the same offered load runs
    through 1 engine replica and an N-replica ``FleetRouter`` —
    aggregate decode tok/s, p50/p99 TTFT, and the prefix-hit pages
    with vs without prefix-aware routing (the affinity stage is what
    keeps a fleet's two-tier caches warm); plus the same load with
    ``replica_death`` injected every K replica-steps, reporting
    recovered/total (failover + auto-replace keep accepted requests
    alive).  ``value`` is the N-replica/1-replica aggregate
    throughput ratio.  ``extra.soak`` is a short LOAD-SOAK window
    (mixed lengths + cancels + deadlines + step faults + a replica
    death + slow stalls): bounded RSS growth, first-half vs
    second-half p99, zero silent drops, every replica's
    ``PagedKVCache.audit()`` clean — the seed of the sustained-soak
    bench ROADMAP item 5 calls for."""
    import resource

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.fleet import FleetRouter
    from paddle_tpu.models.llama_pretrain import (LlamaPretrainConfig,
                                                  init_params)
    from paddle_tpu.models.paged_decode import PagedKVCache
    from paddle_tpu.models.serving_engine import ContinuousBatchingEngine
    from paddle_tpu.observability import default_registry, default_ring
    from paddle_tpu.testing import faults

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        cfg = LlamaPretrainConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=8, max_seq_len=2048,
            use_pallas_attention=True, remat=False,
            dtype=jnp.bfloat16)
        batch, page, new = 8, 64, 48
        num_pages, pages_max = 96, 8
        n_replicas, n_groups, per_group = 3, 4, 6
        prefix_len, tail_lens = 128, (16, 48, 96, 200)
        death_every = 60
        soak_waves, soak_per_wave, soak_new = 8, 6, 32
        metric = "serving_fleet_ab"
    else:
        cfg = LlamaPretrainConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_seq_len=256, dtype=jnp.float32,
            param_dtype=jnp.float32, remat=False, loss_chunks=1,
            use_pallas_attention=False)
        batch, page, new = 2, 16, 8
        num_pages, pages_max = 64, 8
        n_replicas, n_groups, per_group = 3, 3, 4
        prefix_len, tail_lens = 16, (2, 6, 11, 18)
        death_every = 10
        soak_waves, soak_per_wave, soak_new = 6, 4, 10
        metric = "serving_fleet_tiny_cpu_smoke_ab"

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "pp", "sharding", "sep", "mp"))
    params = init_params(cfg, jax.random.PRNGKey(0), mesh)
    rng = np.random.RandomState(0)
    # G prefix groups: shared page-aligned prefix + per-request tail —
    # the workload prefix-affinity routing exists for
    def make_prompts(r):
        gs = [r.randint(1, cfg.vocab_size, (prefix_len,))
              for _ in range(n_groups)]
        out = []
        for i in range(n_groups * per_group):
            tail = r.randint(1, cfg.vocab_size,
                             (tail_lens[i % len(tail_lens)],))
            out.append(np.concatenate([gs[i % n_groups], tail]))
        return out

    prompts = make_prompts(rng)
    # warmup twin: the SAME length mix (same compiles) but different
    # tokens, so warming never pre-seeds the timed window's prefixes
    warm_prompts = make_prompts(np.random.RandomState(1))

    def factory():
        cache = PagedKVCache(cfg, num_pages=num_pages,
                             pages_max=pages_max, batch=batch,
                             page=page)
        return ContinuousBatchingEngine(
            cfg, params, cache, metrics_registry=default_registry(),
            metrics_ring=default_ring(), enable_prefix_caching=True)

    def run(n, prefix_routing=True, death_k=None):
        router = FleetRouter([factory] * n,
                             prefix_routing=prefix_routing)
        # warm every compile the timed window hits (the FULL length
        # mix — per-arm queue depth changes which packed-bucket
        # shapes admission waves take) without seeding its prefixes
        for p in warm_prompts:
            router.submit(p, max_new_tokens=2)
        router.run_to_completion()
        # per-replica baseline keyed on replace count: a replica
        # rebuilt after a death starts a FRESH cache (prefix_hits=0),
        # so its warmup baseline must not be subtracted
        hits0 = {h.idx: (h.replaces, h.engine.cache.prefix_hits)
                 for h in router._replicas}
        fp = faults.install() if death_k else None
        try:
            if death_k:
                fp.inject("replica_death",
                          RuntimeError("bench replica death"),
                          every=death_k)
            t0 = time.perf_counter()
            for p in prompts:
                router.submit(p, max_new_tokens=new)
            done = router.run_to_completion()
            dt = time.perf_counter() - t0
        finally:
            if death_k:
                faults.uninstall()
        for h in router._replicas:
            h.engine.cache.audit()
        ok = [r for r in done if r.status == "ok"]
        ttfts = sorted((r.t_first_token - r.t_submit) * 1000
                       for r in ok if r.t_first_token)
        pct = lambda q: round(  # noqa: E731
            ttfts[min(len(ttfts) - 1, int(q * len(ttfts)))], 2) \
            if ttfts else 0.0
        hits = sum(
            h.engine.cache.prefix_hits
            - (hits0[h.idx][1]
               if h.replaces == hits0[h.idx][0] else 0)
            for h in router._replicas)
        offered = sum(len(p) // page for p in prompts)
        return {
            "replicas": n, "requests": len(done),
            "recovered": len(ok),
            "tok_per_s": round(
                sum(len(r.generated) for r in ok) / dt, 1),
            "ttft_p50_ms": pct(0.50), "ttft_p99_ms": pct(0.99),
            "prefix_hit_pages": hits,
            "prefix_hit_rate": round(hits / max(offered, 1), 4),
            "routed": dict(router.routed),
            "failovers": router.failovers,
            "deaths": router.deaths, "replaces": router.replaces,
        }

    def soak():
        """Short mixed soak: cancels + deadlines + step faults + one
        replica death + slow stalls under continuous offered load."""
        router = FleetRouter([factory] * n_replicas)
        for p in warm_prompts:                      # warm compiles
            router.submit(p, max_new_tokens=2)
        router.run_to_completion()
        rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        submitted, cancelled = 0, 0
        done = []
        t0 = time.perf_counter()
        fp = faults.install()
        try:
            fp.inject("step_dispatch",
                      RuntimeError("soak step fault"), every=37)
            fp.inject("replica_death",
                      RuntimeError("soak replica death"), nth=29)
            fp.inject("replica_slow", p=0.05, seed=11)
            for w in range(soak_waves):
                rids = []
                for j in range(soak_per_wave):
                    p = prompts[(w * soak_per_wave + j)
                                % len(prompts)]
                    kw = {}
                    if j % 4 == 3:
                        kw["deadline_s"] = 30.0
                    rids.append(router.submit(
                        p, max_new_tokens=soak_new, **kw))
                    submitted += 1
                if w % 2 == 1:
                    router.cancel(rids[0])
                    cancelled += 1
                for _ in range(4):
                    router.step()
                done.extend(router.finished())
            done.extend(router.run_to_completion())
        finally:
            faults.uninstall()
        wall = time.perf_counter() - t0
        for h in router._replicas:
            h.engine.cache.audit()
        rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        ok = [r for r in done if r.status == "ok"]
        lats = [(r.t_finish - r.t_submit) * 1000 for r in ok]
        half = len(lats) // 2

        def p99(xs):
            xs = sorted(xs)
            return round(xs[min(len(xs) - 1, int(0.99 * len(xs)))],
                         2) if xs else 0.0

        return {
            "submitted": submitted, "finished": len(done),
            "silent_drops": submitted - len(done),
            "ok": len(ok), "cancelled_req": cancelled,
            "statuses": {s: sum(1 for r in done if r.status == s)
                         for s in {r.status for r in done}},
            "wall_s": round(wall, 2),
            "tok_per_s": round(
                sum(len(r.generated) for r in ok) / wall, 1),
            "p99_first_half_ms": p99(lats[:half]),
            "p99_second_half_ms": p99(lats[half:]),
            "rss_growth_mb": round((rss1 - rss0) / 1024.0, 1),
            "deaths": router.deaths, "replaces": router.replaces,
            "audit_ok": True,
        }

    single = run(1)
    fleet = run(n_replicas, prefix_routing=True)
    no_affinity = run(n_replicas, prefix_routing=False)
    deaths = run(n_replicas, prefix_routing=True,
                 death_k=death_every)
    soaked = soak()
    return {
        "metric": metric,
        "value": round(fleet["tok_per_s"]
                       / max(single["tok_per_s"], 1e-9), 4),
        "unit": "x",
        "vs_baseline": 0,
        "extra": {"platform": platform, "replicas": n_replicas,
                  "batch_slots": batch,
                  "requests": len(prompts),
                  "prefix_groups": n_groups,
                  "death_every_k_replica_steps": death_every,
                  "single": single, "fleet": fleet,
                  "fleet_no_prefix_routing": no_affinity,
                  "fleet_replica_deaths": deaths,
                  "recovered_under_deaths":
                      f"{deaths['recovered']}/{deaths['requests']}",
                  "soak": soaked},
    }


def _serving_qos_line() -> dict:
    """SLO-GUARDRAIL serving A/B (ISSUE 20 tentpole): the same RAMPED
    mixed-class load (high/normal/low interleaved, offered waves
    growing past a single replica's queue capacity) runs through a
    fixed 1-replica fleet and the same fleet under a
    ``FleetAutoscaler`` — per-class TTFT p99, shed/degrade/reject
    counts, and the replica-count trajectory the controller walked.
    A third arm re-runs the autoscaled ramp with ``replica_death``
    injected MID-RAMP: the settle guard must hand the dead replica to
    the router's auto-replace (exactly one replacement, no controller
    oscillation).  ``value`` is the autoscaled/fixed aggregate decode
    throughput ratio."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.fleet import FleetAutoscaler, FleetRouter
    from paddle_tpu.models.llama_pretrain import (LlamaPretrainConfig,
                                                  init_params)
    from paddle_tpu.models.paged_decode import PagedKVCache
    from paddle_tpu.models.serving_engine import (
        ContinuousBatchingEngine, QueueFullError)
    from paddle_tpu.testing import faults

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        cfg = LlamaPretrainConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=8, max_seq_len=2048,
            use_pallas_attention=True, remat=False,
            dtype=jnp.bfloat16)
        batch, page, new = 8, 64, 32
        num_pages, pages_max = 96, 8
        queue_cap, max_replicas = 8, 3
        wave_sizes = (4, 6, 8, 10, 10, 8)
        steps_per_wave, prompt_lens = 3, (48, 96, 160, 220)
        high_qt, low_qt = 512.0, 64.0
        metric = "serving_qos_ab"
    else:
        cfg = LlamaPretrainConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_seq_len=256, dtype=jnp.float32,
            param_dtype=jnp.float32, remat=False, loss_chunks=1,
            use_pallas_attention=False)
        batch, page, new = 2, 16, 8
        num_pages, pages_max = 64, 8
        queue_cap, max_replicas = 4, 3
        wave_sizes = (2, 3, 4, 5, 5, 4)
        steps_per_wave, prompt_lens = 2, (6, 11, 15, 19)
        high_qt, low_qt = 24.0, 4.0
        metric = "serving_qos_tiny_cpu_smoke_ab"

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "pp", "sharding", "sep", "mp"))
    params = init_params(cfg, jax.random.PRNGKey(0), mesh)
    rng = np.random.RandomState(0)
    # ramped offered load: wave w submits wave_sizes[w] requests, the
    # class mix fixed (1 high : 2 normal : 2 low) so the shed/degrade
    # split is attributable, lengths cycled so compiles are shared
    classes = ("high", "normal", "normal", "low", "low")
    load = [[(rng.randint(1, cfg.vocab_size,
                          (prompt_lens[j % len(prompt_lens)],)),
              classes[j % len(classes)])
             for j in range(nw)] for nw in wave_sizes]
    warm = [rng.randint(1, cfg.vocab_size, (L,)) for L in prompt_lens]

    def factory():
        cache = PagedKVCache(cfg, num_pages=num_pages,
                             pages_max=pages_max, batch=batch,
                             page=page)
        return ContinuousBatchingEngine(
            cfg, params, cache, max_queue_len=queue_cap,
            metrics_registry=False)

    def live_count(router):
        return sum(1 for h in router._replicas
                   if h.state in ("READY", "DEGRADED")
                   and not h.retiring)

    def run(autoscale, kill_wave=None):
        router = FleetRouter([factory], metrics_registry=False)
        for p in warm:                              # warm compiles
            router.submit(p, max_new_tokens=2)
        router.run_to_completion()
        asc = FleetAutoscaler(
            router, factory, min_replicas=1,
            max_replicas=max_replicas, high_queued_tokens=high_qt,
            low_queued_tokens=low_qt, up_consecutive=1,
            down_consecutive=2, cooldown_s=0.0) if autoscale else None
        cls_of, rejected, degraded = {}, {}, 0
        trajectory, done = [], []
        fp = faults.install() if kill_wave is not None else None
        t0 = time.perf_counter()
        try:
            for w, wave in enumerate(load):
                for p, c in wave:
                    try:
                        rid = router.submit(p, max_new_tokens=new,
                                            priority=c)
                        cls_of[rid] = c
                    except QueueFullError:
                        rejected[c] = rejected.get(c, 0) + 1
                if w == kill_wave:
                    # nth matches the site's CUMULATIVE consult
                    # counter — arm relative to it so the very next
                    # replica step is the one that dies
                    fp.inject("replica_death",
                              RuntimeError("bench mid-ramp kill"),
                              nth=fp.counts.get("replica_death",
                                                0) + 1)
                for _ in range(steps_per_wave):
                    router.step()
                if asc:
                    asc.tick()
                trajectory.append(live_count(router))
                done.extend(router.finished())
            done.extend(router.run_to_completion())
            if asc:                    # drained: walk back to min
                for _ in range(4):
                    asc.tick()
                    router.step()
                    trajectory.append(live_count(router))
        finally:
            if fp is not None:
                faults.uninstall()
        wall = time.perf_counter() - t0
        for h in router._replicas:
            if h.state not in ("DEAD",):
                h.engine.cache.audit()
        ok = [r for r in done if r.status == "ok"]
        degraded = sum(1 for r in done if r.degraded)
        by_cls = {c: [(r.t_first_token - r.t_submit) * 1000
                      for r in ok if cls_of.get(r.rid) == c
                      and r.t_first_token]
                  for c in ("high", "normal", "low")}
        out = {
            "requests_offered": sum(wave_sizes),
            "ok": len(ok),
            "rejected_by_class": rejected,
            "degraded": degraded,
            "tok_per_s": round(
                sum(len(r.generated) for r in ok) / wall, 1),
            "ttft_p99_ms_by_class": {
                c: _ab_pct(v, 0.99) for c, v in by_cls.items()},
            "replica_trajectory": trajectory,
            "deaths": router.deaths, "replaces": router.replaces,
        }
        if asc:
            out.update(scale_ups=asc.scale_ups,
                       scale_downs=asc.scale_downs,
                       skipped_settling=asc.skipped_settling)
        return out

    fixed = run(autoscale=False)
    scaled = run(autoscale=True)
    killed = run(autoscale=True, kill_wave=len(wave_sizes) // 2)
    return {
        "metric": metric,
        "value": round(scaled["tok_per_s"]
                       / max(fixed["tok_per_s"], 1e-9), 4),
        "unit": "x",
        "vs_baseline": 0,
        "extra": {"platform": platform, "batch_slots": batch,
                  "queue_cap_per_replica": queue_cap,
                  "max_replicas": max_replicas,
                  "wave_sizes": list(wave_sizes),
                  "class_mix": "1 high : 2 normal : 2 low",
                  "fixed_1_replica": fixed,
                  "autoscaled": scaled,
                  "autoscaled_midramp_kill": killed},
    }


def _remote_line() -> dict:
    """SOCKETS-TRANSPORT serving A/B (ISSUE 14 tentpole): the same
    offered load runs through an in-process ``FleetRouter`` and a
    SOCKET fleet — every replica a ``ReplicaAgent`` behind a real TCP
    connection (in-thread agents: genuine localhost wire, no process
    spawn) — reporting aggregate decode tok/s, TTFT p50/p99, the wire
    bill (frames / bytes / RTT), handoff ms/request for a
    disaggregated prefill→decode pair whose KV blobs cross the wire,
    and recovered/total for BOTH fleets under the same
    death-every-K schedule (``replica_death`` in-process,
    ``agent_kill`` on the socket arm).  ``value`` is the
    socket/in-process aggregate throughput ratio — the localhost-CPU
    price of the wire.  ``extra.soak`` is a short CONNECTION-CHAOS
    window (drops + stalled links + one agent kill under load):
    zero silent drops, transport retry/reconnect counters, audits
    clean — seeding the ROADMAP item-5 network soak."""
    import resource

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.fleet import FleetRouter, ReplicaAgent, RemoteSpec
    from paddle_tpu.models.disagg import DecodeEngine, PrefillEngine
    from paddle_tpu.models.llama_pretrain import (LlamaPretrainConfig,
                                                  init_params)
    from paddle_tpu.models.paged_decode import PagedKVCache
    from paddle_tpu.models.serving_engine import ContinuousBatchingEngine
    from paddle_tpu.observability import default_registry, default_ring
    from paddle_tpu.testing import faults

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        cfg = LlamaPretrainConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=8, max_seq_len=2048,
            use_pallas_attention=True, remat=False,
            dtype=jnp.bfloat16)
        batch, page, new = 8, 64, 48
        num_pages, pages_max = 96, 8
        n_replicas, n_requests = 2, 20
        lens = (16, 48, 96, 200)
        death_every = 60
        remote_death_every = 240
        soak_waves, soak_per_wave, soak_new = 6, 5, 24
        metric = "serving_remote_ab"
    else:
        cfg = LlamaPretrainConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_seq_len=256, dtype=jnp.float32,
            param_dtype=jnp.float32, remat=False, loss_chunks=1,
            use_pallas_attention=False)
        batch, page, new = 2, 16, 8
        num_pages, pages_max = 64, 8
        n_replicas, n_requests = 2, 12
        lens = (5, 10, 17, 26)
        death_every = 10
        remote_death_every = 40
        soak_waves, soak_per_wave, soak_new = 5, 4, 10
        metric = "serving_remote_tiny_cpu_smoke_ab"

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "pp", "sharding", "sep", "mp"))
    params = init_params(cfg, jax.random.PRNGKey(0), mesh)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size,
                           (lens[i % len(lens)],))
               for i in range(n_requests)]
    warm_prompts = [np.random.RandomState(1).randint(
        1, cfg.vocab_size, (L,)) for L in lens]

    def factory(engine_cls=ContinuousBatchingEngine, host_pages=None):
        ck = dict(num_pages=num_pages, pages_max=pages_max,
                  batch=batch, page=page)
        if host_pages is not None:
            ck["host_pages"] = host_pages
        cache = PagedKVCache(cfg, **ck)
        return engine_cls(cfg, params, cache,
                          metrics_registry=False)

    def spec(role="unified", engine_cls=None, host_pages=None,
             lease=2.0, timeout=5.0, retries=3, seed=0):
        mk = (lambda: factory(engine_cls or ContinuousBatchingEngine,
                              host_pages))
        return RemoteSpec(
            agent=lambda: ReplicaAgent(mk, role=role, lease_s=lease),
            role=role, lease_s=lease, rpc_timeout_s=timeout,
            max_retries=retries, backoff_s=0.01, jitter_seed=seed)

    def teardown(router):
        for h in router._replicas:
            if getattr(h, "_agent", None) is not None:
                h._agent.die()

    def run(remote, death_k=None, chaos=False):
        if remote:
            lease, timeout = (0.4, 0.3) if death_k else (2.0, 5.0)
            reps = [spec(lease=lease, timeout=timeout, seed=i)
                    for i in range(n_replicas)]
        else:
            reps = [factory] * n_replicas
        # the default registry EXPLICITLY: an all-remote fleet has
        # no in-process engine registry to inherit, and the
        # transport/disagg instruments must land where the
        # metrics_snapshot line reads
        router = FleetRouter(reps,
                             metrics_registry=default_registry(),
                             metrics_ring=default_ring())
        try:
            for p in warm_prompts:               # warm the compiles
                router.submit(p, max_new_tokens=2)
            router.run_to_completion(max_steps=1_000_000)
            fp = faults.install() if (death_k or chaos) else None
            try:
                if death_k and remote:
                    # the remote seam is consulted per SYNC tick
                    # (~2 ms poll) where the in-process one is
                    # consulted per ENGINE step, so the socket
                    # arm's schedule is two FIXED consult indices —
                    # deterministic, and bounded so
                    # kill-faster-than-replace churn can never
                    # livelock the run (each kill costs a full
                    # agent rebuild)
                    fp.inject("agent_kill",
                              RuntimeError("bench death"),
                              nth=death_k // 2)
                    fp.inject("agent_kill",
                              RuntimeError("bench death"),
                              nth=death_k * 3 // 2)
                elif death_k:
                    fp.inject("replica_death",
                              RuntimeError("bench death"),
                              every=death_k)
                if chaos:
                    fp.inject("conn_drop",
                              ConnectionResetError("bench drop"),
                              every=23)
                    fp.inject("net_delay", p=0.02, seed=3)
                t0 = time.perf_counter()
                for p in prompts:
                    router.submit(p, max_new_tokens=new)
                done = router.run_to_completion(max_steps=1_000_000)
                dt = time.perf_counter() - t0
            finally:
                if fp is not None:
                    faults.uninstall()
            for h in router._replicas:
                if h.state in ("READY", "DEGRADED"):
                    h.engine.cache.audit()
            ok = [r for r in done if r.status == "ok"]
            ttfts = sorted((r.t_first_token - r.t_submit) * 1000
                           for r in ok if r.t_first_token)
            out = {
                "requests": len(done), "recovered": len(ok),
                "silent_drops": len(prompts) - len(done),
                "tok_per_s": round(
                    sum(len(r.generated) for r in ok) / dt, 1),
                "ttft_p50_ms": _ab_pct(ttfts, 0.50),
                "ttft_p99_ms": _ab_pct(ttfts, 0.99),
                "failovers": router.failovers,
                "deaths": router.deaths,
                "replaces": router.replaces,
            }
            if remote:
                snap = router.fleet_snapshot()["transport"]
                rtt_ms = None
                if router.transport_metrics is not None:
                    h = router.transport_metrics.rtt_seconds
                    if h.count:
                        rtt_ms = round(1000.0 * h.sum / h.count, 3)
                out["transport"] = dict(snap, rtt_ms_mean=rtt_ms)
            return out
        finally:
            teardown(router)

    def wire_handoff():
        """1 prefill + 1 decode agent over sockets: every request's
        KV blobs cross the wire; handoff ms/request measured at the
        ship stage (the disagg histogram on the shared registry)."""
        router = FleetRouter(
            [spec(role="prefill", engine_cls=PrefillEngine,
                  host_pages=num_pages),
             spec(role="decode", engine_cls=DecodeEngine,
                  host_pages=num_pages, seed=1)],
            handoff_gbps=1e9,
            metrics_registry=default_registry(),
            metrics_ring=default_ring())
        try:
            for p in warm_prompts:
                router.submit(p, max_new_tokens=2)
            router.run_to_completion(max_steps=1_000_000)
            bytes0 = router.fleet_snapshot()["transport"]["bytes"]
            hist0 = (default_registry().snapshot().get(
                "paddle_tpu_disagg_handoff_seconds") or {})
            t0 = time.perf_counter()
            for p in prompts:
                router.submit(p, max_new_tokens=new)
            done = router.run_to_completion(max_steps=1_000_000)
            dt = time.perf_counter() - t0
            hist = (default_registry().snapshot().get(
                "paddle_tpu_disagg_handoff_seconds") or {})
            shipped = ((hist.get("count") or 0)
                       - (hist0.get("count") or 0))
            ship_s = ((hist.get("sum") or 0.0)
                      - (hist0.get("sum") or 0.0))
            ok = [r for r in done if r.status == "ok"]
            snap = router.fleet_snapshot()
            return {
                "requests": len(done), "ok": len(ok),
                "handoffs_shipped": router.handoffs_shipped,
                "handoff_ms_per_request": round(
                    1000.0 * ship_s / max(shipped, 1), 3),
                "wire_bytes": snap["transport"]["bytes"] - bytes0,
                "tok_per_s": round(
                    sum(len(r.generated) for r in ok) / dt, 1),
            }
        finally:
            teardown(router)

    def soak():
        """Connection chaos under continuous load: drops + stalled
        links + one agent kill; nothing silently dropped."""
        router = FleetRouter(
            [spec(lease=0.4, timeout=0.3, retries=2, seed=i)
             for i in range(n_replicas)],
            metrics_registry=default_registry(),
            metrics_ring=default_ring())
        try:
            for p in warm_prompts:
                router.submit(p, max_new_tokens=2)
            router.run_to_completion(max_steps=1_000_000)
            rss0 = resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss
            submitted, cancelled = 0, 0
            done = []
            t0 = time.perf_counter()
            fp = faults.install()
            try:
                fp.inject("conn_drop",
                          ConnectionResetError("soak drop"),
                          every=17)
                fp.inject("net_delay", p=0.03, seed=7)
                fp.inject("agent_kill", RuntimeError("soak kill"),
                          nth=9, times=1)
                for w in range(soak_waves):
                    rids = []
                    for j in range(soak_per_wave):
                        p = prompts[(w * soak_per_wave + j)
                                    % len(prompts)]
                        kw = {}
                        if j % 4 == 3:
                            kw["deadline_s"] = 30.0
                        rids.append(router.submit(
                            p, max_new_tokens=soak_new, **kw))
                        submitted += 1
                    if w % 2 == 1:
                        router.cancel(rids[0])
                        cancelled += 1
                    for _ in range(4):
                        router.step()
                    done.extend(router.finished())
                done.extend(
                    router.run_to_completion(max_steps=1_000_000))
            finally:
                faults.uninstall()
            wall = time.perf_counter() - t0
            for h in router._replicas:
                if h.state in ("READY", "DEGRADED"):
                    h.engine.cache.audit()
            rss1 = resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss
            ok = [r for r in done if r.status == "ok"]
            snap = router.fleet_snapshot()
            return {
                "submitted": submitted, "finished": len(done),
                "silent_drops": submitted - len(done),
                "ok": len(ok), "cancelled_req": cancelled,
                "statuses": {s: sum(1 for r in done
                                    if r.status == s)
                             for s in {r.status for r in done}},
                "wall_s": round(wall, 2),
                "tok_per_s": round(
                    sum(len(r.generated) for r in ok) / wall, 1),
                "rss_growth_mb": round((rss1 - rss0) / 1024.0, 1),
                "deaths": snap["deaths"],
                "replaces": snap["replaces"],
                "transport": snap["transport"],
                "audit_ok": True,
            }
        finally:
            teardown(router)

    inproc = run(remote=False)
    sockets = run(remote=True)
    inproc_deaths = run(remote=False, death_k=death_every)
    socket_deaths = run(remote=True, death_k=remote_death_every)
    handoff = wire_handoff()
    soaked = soak()
    return {
        "metric": metric,
        "value": round(sockets["tok_per_s"]
                       / max(inproc["tok_per_s"], 1e-9), 4),
        "unit": "x",
        "vs_baseline": 0,
        "extra": {
            "platform": platform, "replicas": n_replicas,
            "requests": n_requests,
            "death_every_k_replica_steps": death_every,
            "agent_kill_every_k_sync_ticks": remote_death_every,
            "in_process": inproc, "sockets": sockets,
            "in_process_deaths": inproc_deaths,
            "socket_deaths": socket_deaths,
            "recovered_in_process":
                f"{inproc_deaths['recovered']}"
                f"/{inproc_deaths['requests']}",
            "recovered_sockets":
                f"{socket_deaths['recovered']}"
                f"/{socket_deaths['requests']}",
            "wire_handoff": handoff,
            "soak": soaked},
    }


def _ab_pct(xs, q):
    """Percentile over a small sample (shared by the serving A/B
    lines so their reported quantiles are computed identically)."""
    xs = sorted(xs)
    return round(xs[min(len(xs) - 1, int(q * len(xs)))], 3) \
        if xs else 0.0


def _ab_lat_stats(done) -> dict:
    """TTFT/TPOT p50/p99 over the ok-finished requests — the shared
    latency block of the serving A/B lines."""
    ok = [r for r in done if r.status == "ok"]
    ttft = [(r.t_first_token - r.t_submit) * 1000
            for r in ok if r.t_first_token]
    tpot = [(r.t_finish - r.t_first_token) * 1000
            / (len(r.generated) - 1)
            for r in ok if r.t_first_token and len(r.generated) > 1]
    return {"requests_ok": len(ok),
            "ttft_p50_ms": _ab_pct(ttft, 0.5),
            "ttft_p99_ms": _ab_pct(ttft, 0.99),
            "tpot_p50_ms": _ab_pct(tpot, 0.5),
            "tpot_p99_ms": _ab_pct(tpot, 0.99)}


def _ab_drive(submit, step, admitted_this_tick, schedule, wave_gap,
              new, stagger=0):
    """Shared offered-load loop of the serving A/B lines
    (serving_disagg_ab, serving_mixed_ab — SAME harness, so their
    ratios stay comparable at the same offered load): submit waves on
    schedule, step once per tick, sample the decode-step wall split
    by whether this tick was admission-adjacent.  ``stagger`` adds
    ``stagger * j`` generated tokens to the j-th request of each wave
    so the resident batch drains gradually (slots free while
    neighbours still decode — the arrival pattern the mixed lane
    exists for; 0 keeps the lockstep schedule)."""
    adm, quiet = [], []
    pend = list(enumerate(schedule))
    tick = 0
    done = []
    while pend or step.__self__.has_work():
        if pend and tick >= pend[0][0] * wave_gap:
            for j, p in enumerate(pend.pop(0)[1]):
                submit(p, new + stagger * j)
        t0 = time.perf_counter()
        step()
        wall = (time.perf_counter() - t0) * 1000
        drv = step.__self__
        dec_ms = wall if not hasattr(drv, "last_decode_step_s") \
            else drv.last_decode_step_s * 1000
        hit = admitted_this_tick()    # advances its counters —
        #                               consult EVERY tick
        if dec_ms > 0:        # ticks with no decode work carry no
            #                   decode-step sample
            (adm if hit else quiet).append(dec_ms)
        done.extend(drv.finished())
        tick += 1
        if tick > 5000:
            raise RuntimeError("serving A/B bench did not drain")
    return adm, quiet, done


def _ab_run_disagg(cfg, params, mk_cache, host_pages, batch,
                   long_lens, short_lens, drive, warm_sched, sched,
                   detail=False, registry=None, ring=None):
    """The 1P+1D arm shared by serving_disagg_ab and
    serving_mixed_ab (ONE implementation, so the two lines' disagg
    numbers stay comparable as the harness evolves): build the pair,
    calibrate the cost-model link speed so the decision SPLITS this
    workload (geometric mean of the gbps thresholds at which the
    shortest long prompt and the longest short prompt flip — the
    decision stays a counter), warm, drive, report.  ``detail`` adds
    the routing/handoff counters serving_disagg_ab reports."""
    import numpy as np

    from paddle_tpu.models.disagg import (DecodeEngine,
                                          DisaggCoordinator,
                                          PrefillEngine,
                                          handoff_flip_gbps)

    pe = PrefillEngine(cfg, params, mk_cache(host_pages),
                       metrics_registry=registry
                       if registry is not None else False,
                       metrics_ring=ring,
                       max_inflight_handoffs=2 * batch)
    de = DecodeEngine(cfg, params, mk_cache(host_pages),
                      metrics_registry=registry
                      if registry is not None else False,
                      metrics_ring=ring)
    gbps = float(np.sqrt(
        handoff_flip_gbps(min(long_lens), de)
        * handoff_flip_gbps(max(short_lens), de)))
    co = DisaggCoordinator(pe, de, handoff_gbps=gbps)
    last = {"pf": pe.prefill_calls, "sw": de.resumes_swapped}

    def admitted():
        # an admission-adjacent tick: the prefill engine ran a wave
        # OR the decode engine restored shipped pages (the disagg
        # arm's admission cost lives in the restores)
        hit = (pe.prefill_calls > last["pf"]
               or de.resumes_swapped > last["sw"])
        last["pf"] = pe.prefill_calls
        last["sw"] = de.resumes_swapped
        return hit

    submit = lambda p, n: co.submit(p, max_new_tokens=n)  # noqa: E731
    drive(submit, co.step, admitted, warm_sched)    # compiles
    warm_routed = dict(co.routed)
    adm, quiet, done = drive(submit, co.step, admitted, sched)
    out = _ab_lat_stats(done)
    out.update({"decode_step_p99_during_admission_ms":
                _ab_pct(adm, 0.99),
                "decode_step_p50_during_admission_ms":
                _ab_pct(adm, 0.5),
                "decode_step_p99_quiet_ms": _ab_pct(quiet, 0.99),
                "admission_ticks": len(adm),
                "handoff_gbps_knob": round(gbps, 3)})
    if detail:
        out.update({
            "routed": {k: co.routed[k] - warm_routed[k]
                       for k in co.routed},
            "handoffs_shipped": co.handoffs_shipped,
            "handoff_pages": co.handoff_pages,
            "handoff_ms_per_request": round(
                1000.0 * co.handoff_wall_s
                / max(co.handoffs_shipped, 1), 4),
            "colocated_fallbacks": co.colocated_fallbacks,
            "decode_prefill_calls": de.prefill_calls,
            "prefill_tokens_avoided": de.prefill_tokens_avoided})
    pe.cache.audit()
    de.cache.audit()
    return out


def _disagg_line() -> dict:
    """DISAGGREGATED prefill/decode A/B (PR-9 tentpole): the same
    offered load — waves of long prompts (the stall-inducing
    workload) plus short ones (the cost model keeps them colocated) —
    runs through one UNIFIED engine and a 1P+1D
    ``DisaggCoordinator`` at the same submission schedule.  Reports
    TTFT/TPOT p50/p99, the decode-step p99 DURING ADMISSION WAVES
    (the stall this architecture deletes: on the unified engine an
    admission tick's step includes the packed prefill; on the disagg
    pair the decode engine's step never does), handoff ms/request,
    and the per-request cost-model routing counts.  ``value`` is the
    unified/disagg ratio of admission-tick decode-step p99 (>1 =
    disagg deleted stall)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.models.llama_pretrain import (LlamaPretrainConfig,
                                                  init_params)
    from paddle_tpu.models.paged_decode import PagedKVCache
    from paddle_tpu.models.serving_engine import ContinuousBatchingEngine
    from paddle_tpu.observability import default_registry, default_ring

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        cfg = LlamaPretrainConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=8, max_seq_len=2048,
            use_pallas_attention=True, remat=False,
            dtype=jnp.bfloat16)
        batch, page, new = 8, 64, 48
        num_pages, pages_max, host_pages = 128, 8, 96
        long_lens, short_lens = (192, 256, 320, 448), (16, 32)
        waves, per_wave, wave_gap = 4, 6, 6
        metric = "serving_disagg_ab"
    else:
        cfg = LlamaPretrainConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_seq_len=256, dtype=jnp.float32,
            param_dtype=jnp.float32, remat=False, loss_chunks=1,
            use_pallas_attention=False)
        batch, page, new = 4, 16, 12
        num_pages, pages_max, host_pages = 96, 8, 64
        long_lens, short_lens = (48, 64, 80, 100), (3, 6)
        waves, per_wave, wave_gap = 4, 4, 4
        metric = "serving_disagg_tiny_cpu_smoke_ab"

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "pp", "sharding", "sep", "mp"))
    params = init_params(cfg, jax.random.PRNGKey(0), mesh)
    rng = np.random.RandomState(0)
    # submission schedule: one wave every wave_gap ticks, mostly long
    # prompts + a short tail rider per wave
    def make_sched(r):
        out = []
        for w in range(waves):
            ps = [r.randint(1, cfg.vocab_size,
                            (long_lens[(w * per_wave + j)
                                       % len(long_lens)],))
                  for j in range(per_wave - 1)]
            ps.append(r.randint(1, cfg.vocab_size,
                               (short_lens[w % len(short_lens)],)))
            out.append(ps)
        return out

    sched = make_sched(rng)
    # warmup twin: the SAME length mix and wave structure (same
    # packed-bucket / restore-scatter compile shapes) with different
    # tokens, driven through the same schedule so the timed window
    # never pays a first-shape compile
    warm_sched = make_sched(np.random.RandomState(1))

    def mk_cache(hp=0):
        return PagedKVCache(cfg, num_pages=num_pages,
                            pages_max=pages_max, batch=batch,
                            page=page, host_pages=hp)

    pct, lat_stats = _ab_pct, _ab_lat_stats

    def drive(submit, step, admitted_this_tick, schedule):
        return _ab_drive(submit, step, admitted_this_tick, schedule,
                         wave_gap, new)

    def run_unified():
        eng = ContinuousBatchingEngine(
            cfg, params, mk_cache(), metrics_registry=False)
        last = {"pf": eng.prefill_calls}

        def admitted():
            hit = eng.prefill_calls > last["pf"]
            last["pf"] = eng.prefill_calls
            return hit

        submit = lambda p, n: eng.submit(p, max_new_tokens=n)  # noqa: E731
        drive(submit, eng.step, admitted, warm_sched)   # compiles
        adm, quiet, done = drive(submit, eng.step, admitted, sched)
        out = lat_stats(done)
        out.update({"decode_step_p99_during_admission_ms":
                    pct(adm, 0.99),
                    "decode_step_p50_during_admission_ms":
                    pct(adm, 0.5),
                    "decode_step_p99_quiet_ms": pct(quiet, 0.99),
                    "admission_ticks": len(adm)})
        eng.cache.audit()
        return out

    unified = run_unified()
    disagg = _ab_run_disagg(cfg, params, mk_cache, host_pages, batch,
                            long_lens, short_lens, drive, warm_sched,
                            sched, detail=True,
                            registry=default_registry(),
                            ring=default_ring())
    u99 = unified["decode_step_p99_during_admission_ms"]
    d99 = disagg["decode_step_p99_during_admission_ms"]
    return {
        "metric": metric,
        "value": round(u99 / max(d99, 1e-9), 4),
        "unit": "x",
        "vs_baseline": 0,
        "extra": {
            "platform": platform, "batch_slots": batch,
            "requests": sum(len(w) for w in sched),
            "waves": waves, "wave_gap_ticks": wave_gap,
            "unified": unified, "disagg_1p1d": disagg,
            "disagg_deletes_admission_stall": bool(u99 > d99),
            "note": "CPU smoke time-slices both engines on one host: "
                    "TTFT/TPOT wall numbers interleave the two "
                    "devices' work and cannot show the concurrency "
                    "win — the decode-step latency during admission "
                    "waves is the honest per-device measurable "
                    "(on-chip capture: ROADMAP item 5)",
        },
    }


def _serving_mixed_line() -> dict:
    """MIXED prefill+decode A/B (PR-11 tentpole, Sarathi-style
    token-budget piggybacking): the same offered load — waves of long
    prompts arriving while a resident batch decodes — runs through
    (a) a UNIFIED engine with sequential packed admission (every wave
    is a stall: the admission tick's step carries the whole packed
    prefill), (b) the same engine with ``mixed=True`` (prefill tokens
    ride inside the decode dispatches, ``mixed_token_budget`` per
    tick — no second engine, no stall), and (c) the 1P+1D
    ``DisaggCoordinator`` (the architecture that deletes the stall by
    paying for a second engine).  Reports decode-step p99 DURING the
    admission phase (the stall this lane deletes), TTFT/TPOT p50/p99
    and the mixed lane's budget utilization.  ``value`` is the
    unified/mixed ratio of admission-phase decode-step p99 (>1 =
    mixed deleted stall without a second engine)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.models.llama_pretrain import (LlamaPretrainConfig,
                                                  init_params)
    from paddle_tpu.models.paged_decode import PagedKVCache
    from paddle_tpu.models.serving_engine import ContinuousBatchingEngine
    from paddle_tpu.observability import default_registry, default_ring

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        cfg = LlamaPretrainConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=8, max_seq_len=2048,
            use_pallas_attention=True, remat=False,
            dtype=jnp.bfloat16)
        batch, page, new = 8, 64, 48
        num_pages, pages_max, host_pages = 160, 8, 96
        long_lens, short_lens = (192, 256, 320, 448), (16, 32)
        waves, per_wave, wave_gap = 4, 6, 6
        budget = 2 * page
        metric = "serving_mixed_ab"
    else:
        cfg = LlamaPretrainConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_seq_len=256, dtype=jnp.float32,
            param_dtype=jnp.float32, remat=False, loss_chunks=1,
            use_pallas_attention=False)
        batch, page, new = 4, 16, 12
        num_pages, pages_max, host_pages = 96, 8, 64
        long_lens, short_lens = (48, 64, 80, 100), (3, 6)
        waves, per_wave, wave_gap = 4, 4, 4
        budget = page
        metric = "serving_mixed_tiny_cpu_smoke_ab"

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "pp", "sharding", "sep", "mp"))
    params = init_params(cfg, jax.random.PRNGKey(0), mesh)

    def make_sched(r):
        out = []
        for w in range(waves):
            ps = [r.randint(1, cfg.vocab_size,
                            (long_lens[(w * per_wave + j)
                                       % len(long_lens)],))
                  for j in range(per_wave - 1)]
            ps.append(r.randint(1, cfg.vocab_size,
                                (short_lens[w % len(short_lens)],)))
            out.append(ps)
        return out

    sched = make_sched(np.random.RandomState(0))
    # warmup twin: same length mix / wave structure, different tokens
    # — the timed window never pays a first-shape compile
    warm_sched = make_sched(np.random.RandomState(1))

    def mk_cache(hp=0):
        return PagedKVCache(cfg, num_pages=num_pages,
                            pages_max=pages_max, batch=batch,
                            page=page, host_pages=hp)

    pct, lat_stats = _ab_pct, _ab_lat_stats

    def drive(submit, step, admitted_this_tick, schedule):
        # stagger=3: generation lengths vary per request so the
        # resident batch drains gradually (the arrival-into-a-busy-
        # batch pattern the mixed lane exists for)
        return _ab_drive(submit, step, admitted_this_tick, schedule,
                         wave_gap, new, stagger=3)

    def run_engine(mixed):
        # BOTH arms carry identical instrumentation (the shared
        # default registry), so the u99/m99 headline compares equal
        # per-tick observation cost
        eng = ContinuousBatchingEngine(
            cfg, params, mk_cache(),
            metrics_registry=default_registry(),
            metrics_ring=default_ring(),
            mixed=mixed, mixed_token_budget=budget if mixed else 0)
        last = {"pf": eng.prefill_calls, "mx": eng.mixed_prefill_tokens}

        def admitted():
            # admission-phase tick: a sequential wave ran, or the
            # mixed dispatch piggybacked fresh prefill tokens
            hit = (eng.prefill_calls > last["pf"]
                   or eng.mixed_prefill_tokens > last["mx"])
            last["pf"] = eng.prefill_calls
            last["mx"] = eng.mixed_prefill_tokens
            return hit

        submit = lambda p, n: eng.submit(p, max_new_tokens=n)  # noqa: E731
        drive(submit, eng.step, admitted, warm_sched)   # compiles
        t_mark = (eng.mixed_ticks, eng.mixed_prefill_tokens,
                  eng.mixed_degraded)
        adm, quiet, done = drive(submit, eng.step, admitted, sched)
        out = lat_stats(done)
        out.update({"decode_step_p99_during_admission_ms":
                    pct(adm, 0.99),
                    "decode_step_p50_during_admission_ms":
                    pct(adm, 0.5),
                    "decode_step_p99_quiet_ms": pct(quiet, 0.99),
                    "admission_ticks": len(adm)})
        if mixed:
            ticks = eng.mixed_ticks - t_mark[0]
            piggy = eng.mixed_prefill_tokens - t_mark[1]
            out.update({
                "mixed_ticks": ticks,
                "piggybacked_prefill_tokens": piggy,
                "mixed_token_budget": eng.mixed_token_budget,
                "budget_utilization": round(
                    piggy / max(ticks * eng.mixed_token_budget, 1),
                    4),
                "mixed_degraded_waves":
                    eng.mixed_degraded - t_mark[2],
                "prefill_calls": eng.prefill_calls})
        eng.cache.audit()
        return out

    unified = run_engine(mixed=False)
    mixed = run_engine(mixed=True)
    disagg = _ab_run_disagg(cfg, params, mk_cache, host_pages, batch,
                            long_lens, short_lens, drive, warm_sched,
                            sched)
    u99 = unified["decode_step_p99_during_admission_ms"]
    m99 = mixed["decode_step_p99_during_admission_ms"]
    d99 = disagg["decode_step_p99_during_admission_ms"]
    return {
        "metric": metric,
        "value": round(u99 / max(m99, 1e-9), 4),
        "unit": "x",
        "vs_baseline": 0,
        "extra": {
            "platform": platform, "batch_slots": batch,
            "requests": sum(len(w) for w in sched),
            "waves": waves, "wave_gap_ticks": wave_gap,
            "unified_sequential": unified,
            "mixed": mixed,
            "disagg_1p1d": disagg,
            "mixed_deletes_admission_stall": bool(u99 > m99),
            "mixed_vs_disagg_stall_ratio": round(
                d99 / max(m99, 1e-9), 4),
            "note": "mixed deletes the colocated admission stall "
                    "WITHOUT a second engine: compare value (>1) "
                    "against serving_disagg_ab's unified/disagg "
                    "ratio at the same offered load.  CPU smoke "
                    "walls include queued host work; the admission-"
                    "phase decode-step p99 is the honest per-device "
                    "measurable (on-chip capture: ROADMAP item 5)",
        },
    }


def _serving_tp_line() -> dict:
    """TENSOR-PARALLEL serving A/B on an mp mesh (PR-7 tentpole): the
    same mixed-length trace admits through the batched-under-TP and
    packed-under-TP lanes (dispatch counts pin the ONE-dispatch-per-
    wave contract on a mesh), then decodes with ``tp_allreduce`` fp32
    vs int8 (+ overlap) — reporting admission dispatches, decode
    tok/s, and analytic collective bytes-moved per decode step per
    lane.  ``value`` is the int8 bytes per step over a 4-BYTE fp32
    wire (the EQuARX win and the acceptance pin; <= ~0.31 at smoke
    scale, ~0.27 at bench hidden sizes); ``extra`` also carries the
    ratio against the default lane's ACTUAL wire dtype, which on a
    bf16 TPU config is 2 bytes (ratio ~0.56).

    Needs >= 2 devices: on CPU run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models.llama_pretrain import (LlamaPretrainConfig,
                                                  build_mesh,
                                                  init_params)
    from paddle_tpu.models.paged_decode import (
        PagedKVCache, tp_collective_bytes_per_step)
    from paddle_tpu.models.serving_engine import ContinuousBatchingEngine

    platform = jax.devices()[0].platform
    ndev = len(jax.devices())
    mp = 4 if ndev >= 4 else (2 if ndev >= 2 else 0)
    if not mp:
        return _error_line(
            "serving_tp_ab", "ratio",
            f"needs >= 2 devices for a TP mesh, have {ndev}; on CPU "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=4")
    on_tpu = platform == "tpu"
    if on_tpu:
        cfg = LlamaPretrainConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=8, max_seq_len=2048,
            use_pallas_attention=True, remat=False,
            dtype=jnp.bfloat16)
        batch, new, page = 8, 32, 64
        num_pages, pages_max = 96, 16
        trace = [640, 64, 96, 500, 128, 72, 320, 200]
        metric = "serving_tp_ab"
    else:
        cfg = LlamaPretrainConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, max_seq_len=256, dtype=jnp.float32,
            param_dtype=jnp.float32, remat=False, loss_chunks=1,
            use_pallas_attention=False)
        batch, new, page = 4, 8, 16
        num_pages, pages_max = 64, 8
        trace = [100, 5, 9, 12]
        metric = "serving_tp_tiny_cpu_smoke_ab"

    mesh = build_mesh(dp=1, pp=1, sharding=1, sep=1, mp=mp,
                      devices=jax.devices()[:mp])
    params = init_params(cfg, jax.random.PRNGKey(0), mesh)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, (L,)) for L in trace]

    def run(packed, mode, overlap):
        cache = PagedKVCache(cfg, num_pages=num_pages,
                             pages_max=pages_max, batch=batch,
                             page=page, mesh=mesh)
        eng = ContinuousBatchingEngine(
            cfg, params, cache, mesh=mesh, metrics_registry=False,
            packed=packed, tp_allreduce=mode, overlap=overlap)
        # warm every compile the timed wave will hit
        for p in prompts:
            eng.submit(p, max_new_tokens=2)
        eng.run_to_completion()
        calls0 = eng.prefill_calls
        for p in prompts:
            eng.submit(p, max_new_tokens=new)
        t0 = time.perf_counter()
        eng.step()                    # the admission wave (+1 decode)
        admission_ms = (time.perf_counter() - t0) * 1000
        while eng._queue:
            eng.step()
        t1 = time.perf_counter()
        done = eng.run_to_completion()
        decode_s = time.perf_counter() - t1
        return {
            "prefill_calls": eng.prefill_calls - calls0,
            "admission_ms": round(admission_ms, 2),
            "decode_tok_per_s": round(
                sum(len(r.generated) for r in done)
                / max(decode_s + admission_ms / 1000, 1e-9), 1),
            "bytes_per_step": eng._tp_bytes_step,
            "allreduce_mbytes_total": round(
                eng.tp_allreduce_bytes / 1e6, 4),
        }

    batched = run(False, "fp32", False)
    packed = run(True, "fp32", False)
    q8_overlap = run(True, "int8", True)
    fp_bytes = tp_collective_bytes_per_step(cfg, mp, "fp32", batch)
    q8_bytes = tp_collective_bytes_per_step(cfg, mp, "int8", batch)
    # the acceptance pin is against a 4-byte fp32 wire; the default
    # lane's actual wire is the compute dtype (2 bytes under bf16)
    fp32_4byte = fp_bytes * 4 // np.dtype(cfg.dtype).itemsize
    return {
        "metric": metric,
        "value": round(q8_bytes / max(fp32_4byte, 1), 4),
        "unit": "ratio",
        "vs_baseline": 0,
        "extra": {"platform": platform, "mp": mp,
                  "trace_lens": trace, "batch_slots": batch,
                  "batched_fp32": batched, "packed_fp32": packed,
                  "packed_int8_overlap": q8_overlap,
                  "bytes_per_step_default_lane": fp_bytes,
                  "bytes_per_step_int8": q8_bytes,
                  "ratio_vs_default_lane": round(
                      q8_bytes / max(fp_bytes, 1), 4)},
    }


def _trace_overhead_line() -> dict:
    """TRACING-COST A/B (ISSUE-13 tentpole acceptance): the same
    offered load runs through two identical engines — tracing OFF vs
    tracing ON (per-request TraceContexts, phase-clock accrual,
    retirement-time span materialization, tail-sampled store) — and
    reports the decode tok/s delta, the decode-step p99 delta, and
    the store's retained-bytes footprint.  ``value`` is the on/off
    decode-tok/s ratio (acceptance bar: >= 0.97, i.e. <= 3% cost;
    min-of-3 interleaved repeats so CI timer noise hits both arms).
    The ON arm publishes to the process-wide default tracer, so the
    final ``metrics_snapshot`` line carries its retained trace
    ids."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.models.llama_pretrain import (LlamaPretrainConfig,
                                                  init_params)
    from paddle_tpu.models.paged_decode import PagedKVCache
    from paddle_tpu.models.serving_engine import ContinuousBatchingEngine
    from paddle_tpu.observability import default_registry, default_tracer

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        cfg = LlamaPretrainConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=8, max_seq_len=2048,
            use_pallas_attention=True, remat=False,
            dtype=jnp.bfloat16)
        batch, n_req, prompt_len, new, page = 8, 16, 128, 48, 64
        num_pages, pages_max = 64, 8
        metric = "serving_trace_overhead"
    else:
        cfg = LlamaPretrainConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_seq_len=256, dtype=jnp.float32,
            param_dtype=jnp.float32, remat=False, loss_chunks=1,
            use_pallas_attention=False)
        batch, n_req, prompt_len, new, page = 4, 8, 12, 16, 16
        num_pages, pages_max = 64, 8
        metric = "serving_trace_tiny_cpu_smoke_overhead"

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "pp", "sharding", "sep", "mp"))
    params = init_params(cfg, jax.random.PRNGKey(0), mesh)
    tracer = default_tracer()
    tracer.store.bind_metrics(default_registry())
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, (prompt_len,))
               for _ in range(n_req)]

    def build(traced):
        cache = PagedKVCache(cfg, num_pages=num_pages,
                             pages_max=pages_max, batch=batch,
                             page=page)
        return ContinuousBatchingEngine(
            cfg, params, cache, metrics_registry=False,
            tracer=tracer if traced else None)

    def run(eng):
        for p in prompts:
            eng.submit(p, max_new_tokens=new)
        t0 = time.perf_counter()
        walls = []
        tokens = 0
        while eng.has_work():
            s0 = time.perf_counter()
            eng.step()
            walls.append((time.perf_counter() - s0) * 1000)
            tokens += sum(len(r.generated) for r in eng.finished())
        return tokens / (time.perf_counter() - t0), walls

    eng_off, eng_on = build(False), build(True)
    run(eng_off), run(eng_on)                  # warm both compiles
    offs, ons, p99o, p99n = [], [], [], []
    for _ in range(3):
        tps, walls = run(eng_off)
        offs.append(tps)
        p99o.append(_ab_pct(walls, 0.99))
        tps, walls = run(eng_on)
        ons.append(tps)
        p99n.append(_ab_pct(walls, 0.99))
    t_off, t_on = max(offs), max(ons)          # min-wall == max-tok/s
    store = tracer.store.stats()
    return {
        "metric": metric,
        "value": round(t_on / max(t_off, 1e-9), 4),
        "unit": "ratio",
        "vs_baseline": 0,
        "extra": {
            "platform": platform, "requests_per_round": n_req,
            "rounds": 3, "batch_slots": batch,
            "decode_tok_per_s_off": round(t_off, 1),
            "decode_tok_per_s_on": round(t_on, 1),
            "tok_per_s_cost_pct": round(
                100.0 * (1.0 - t_on / max(t_off, 1e-9)), 2),
            "decode_step_p99_off_ms": min(p99o),
            "decode_step_p99_on_ms": min(p99n),
            "trace_store": store,
            "trace_ids_sample": [
                t["trace_id"] for t in tracer.index(limit=5)],
            "note": "phase clocks accrue only at scheduler mutation "
                    "points; decode steps are never spans — the "
                    "per-token hot path is untouched by design "
                    "(docs/OBSERVABILITY.md, Tracing)",
        },
    }


def _serving_line() -> dict:
    return _serving_run(overlap=False)


def _serving_overlap_line() -> dict:
    return _serving_run(overlap=True)


_HORIZON_ENGINE = None  # LAST arm pinned so weakref gauges stay
#                         readable (counters live in the registry and
#                         survive the earlier arms' collection — only
#                         the last-constructed engine feeds callback
#                         gauges, so pinning all three would just hold
#                         their KV pools device-resident under every
#                         later bench line)


def _horizon_line() -> dict:
    """Multi-token decode horizon A/B: the SAME offered load served
    at ``decode_horizon`` 1 vs 4 vs 8 (fresh engine + cache per arm,
    budget-bound requests so every row runs full blocks).  Per arm:
    decode tok/s, host_overhead_frac (host bookkeeping / decode-step
    seconds — the cost the horizon amortizes H x), dispatches/token
    (expect ~1/H; the acceptance bar is <= 1.1/H), TTFT p50.  The
    trim caveat — aggressive stop-sequence traffic burns up to H-1
    trimmed tokens per stop — is PERF.md's; this workload has no
    stops, so ``horizon_trimmed_tokens`` stays 0."""
    import statistics
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.models.llama_pretrain import (LlamaPretrainConfig,
                                                  init_params)
    from paddle_tpu.models.paged_decode import PagedKVCache
    from paddle_tpu.models.serving_engine import ContinuousBatchingEngine
    from paddle_tpu.observability import default_registry, default_ring

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        cfg = LlamaPretrainConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=8, max_seq_len=2048,
            use_pallas_attention=True, remat=False,
            dtype=jnp.bfloat16)
        batch, n_req, prompt_len, new, page = 8, 16, 128, 33, 64
        num_pages, pages_max = 96, 8
    else:
        cfg = LlamaPretrainConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_seq_len=256, dtype=jnp.float32,
            param_dtype=jnp.float32, remat=False, loss_chunks=1,
            use_pallas_attention=False)
        # wider batch than the overlap A/B's smoke: per-tick host
        # bookkeeping must be REAL work (8 live rows) for the
        # amortization to be measurable over the dispatch wait
        batch, n_req, prompt_len, new, page = 8, 16, 12, 17, 16
        num_pages, pages_max = 128, 8

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "pp", "sharding", "sep", "mp"))
    params = init_params(cfg, jax.random.PRNGKey(0), mesh)
    arms = {}
    for H in (1, 4, 8):
        cache = PagedKVCache(cfg, num_pages=num_pages,
                             pages_max=pages_max, batch=batch,
                             page=page)
        eng = ContinuousBatchingEngine(
            cfg, params, cache, metrics_registry=default_registry(),
            metrics_ring=default_ring(), decode_horizon=H)
        global _HORIZON_ENGINE
        _HORIZON_ENGINE = eng
        rng = np.random.RandomState(0)
        # warm/compile with the timed window's admission + block shape
        for _ in range(batch):
            eng.submit(rng.randint(1, cfg.vocab_size, (prompt_len,)),
                       max_new_tokens=new)
        eng.run_to_completion()
        steps0, syncs0 = eng.decode_steps, eng.host_syncs
        hb0, dec0 = _hb_sums()
        t0 = time.perf_counter()
        for _ in range(n_req):
            eng.submit(rng.randint(1, cfg.vocab_size, (prompt_len,)),
                       max_new_tokens=new)
        done = eng.run_to_completion()
        dt = time.perf_counter() - t0
        hb1, dec1 = _hb_sums()
        steps = eng.decode_steps - steps0
        # dispatches/token over DECODE tokens (admission first tokens
        # ride the prefill tail, not a decode dispatch)
        dec_tokens = sum(len(r.generated) - 1 for r in done)
        ttfts = sorted(r.t_first_token - r.t_submit for r in done)
        arms[H] = {
            "decode_tok_per_s": round(
                sum(len(r.generated) for r in done) / dt, 1),
            "host_overhead_frac": round(
                (hb1 - hb0) / max(dec1 - dec0, 1e-12), 4),
            "dispatches_per_token": round(
                steps / max(dec_tokens, 1), 4),
            "ttft_p50_ms": round(
                statistics.median(ttfts) * 1000, 2),
            "decode_dispatches": steps,
            "host_syncs": eng.host_syncs - syncs0,
            "trimmed_tokens": eng.horizon_trimmed_tokens,
        }
    frac1 = arms[1]["host_overhead_frac"]
    frac8 = arms[8]["host_overhead_frac"]
    return {
        "metric": "serving_horizon_ab",
        # the headline: how much of the per-token host overhead the
        # H=8 horizon deleted (frac_H1 / frac_H8, higher is better)
        "value": round(frac1 / max(frac8, 1e-9), 3),
        "unit": "x",
        "vs_baseline": 0,
        "extra": {
            "platform": platform, "requests": n_req,
            "batch_slots": batch, "max_new_tokens": new,
            "arms": {f"H={k}": v for k, v in arms.items()},
            "note": "budget-bound load, no stop sequences (trim "
                    "waste 0 here; the stop-heavy caveat is "
                    "PERF.md's).  dispatches/token ~ 1/H is the "
                    "acceptance pin; host_overhead_frac is the cost "
                    "ROADMAP item 5 names.",
        },
    }


_SPEC_ENGINE = None  # LAST arm pinned, same rationale as
#                      _HORIZON_ENGINE above


def _spec_ab_line() -> dict:
    """Fused speculative decoding A/B: the SAME offered load served
    plain (H=1), with a decode horizon (H=4), and through the fused
    spec lane — draft-model form and model-free prompt-lookup form
    (sync and overlap).  Fresh engine + cache per arm.

    Workload: REPETITIVE-CONTINUATION prompts — each prompt is a
    random stem extended with the model's own greedy continuation up
    to the point where that continuation enters an exact cycle, so
    the timed decode really emits self-repeating text.  That is
    prompt-lookup's design case (extractive / copy-heavy traffic);
    random-continuation traffic drives lookup acceptance toward zero
    and is reported as such in PERF.md, not here.  The draft-model
    arm uses draft == target: its acceptance is 1.0 BY CONSTRUCTION
    (the ceiling), so the arm isolates the fused round's overhead —
    a real small draft lands between it and the H=1 floor in
    proportion to its agreement rate.

    Per arm: decode tok/s, TTFT/TPOT p50+p99, dispatches/token,
    acceptance rate (accepted/drafted, honest — phantom pipeline
    rounds excluded by the engine's device-chain accounting), and a
    token-exactness check vs the H=1 arm's outputs."""
    import statistics
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.models.decode import make_generate
    from paddle_tpu.models.llama_pretrain import (LlamaPretrainConfig,
                                                  init_params)
    from paddle_tpu.models.paged_decode import PagedKVCache
    from paddle_tpu.models.serving_engine import (
        ContinuousBatchingEngine, SpecConfig)
    from paddle_tpu.observability import default_registry, default_ring

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        cfg = LlamaPretrainConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=8, max_seq_len=2048,
            use_pallas_attention=True, remat=False,
            dtype=jnp.bfloat16)
        batch, n_req, new, page = 8, 16, 100, 64
        num_pages, pages_max = 96, 8
    else:
        cfg = LlamaPretrainConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_seq_len=512, dtype=jnp.float32,
            param_dtype=jnp.float32, remat=False, loss_chunks=1,
            use_pallas_attention=False)
        batch, n_req, new, page = 8, 16, 100, 16
        num_pages, pages_max = 136, 16

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "pp", "sharding", "sep", "mp"))
    # seed 2: this init's greedy attractors are reached within ~60
    # tokens at smoke scale, keeping the cycle scan below cheap
    params = init_params(cfg, jax.random.PRNGKey(2), mesh)

    # --- build the repetitive-continuation workload: scan a random
    # prompt bank for stems whose greedy continuation enters an exact
    # cycle early, and extend each stem to the cycle entry point
    rng = np.random.RandomState(7)
    bank = [rng.randint(1, cfg.vocab_size, (12,)) for _ in range(30)]
    gen = make_generate(cfg, prompt_len=12, max_new_tokens=150)
    prompts, periods = [], []
    for stem in bank:
        out = list(np.asarray(gen(params, jnp.asarray(stem[None]),
                                  jax.random.PRNGKey(0)))[0])
        per = next((T for T in range(1, 25)
                    if out[-3 * T:-2 * T] == out[-2 * T:-T]
                    == out[-T:]), None)
        if per is None:
            continue
        s = len(out) - per
        while s > 0 and out[s - 1] == out[s - 1 + per]:
            s -= 1
        if s > 70:
            continue                   # cycle too late: skip the stem
        prompts.append(np.concatenate(
            [stem, np.asarray(out[:s + 2 * per], np.int64)]))
        periods.append(per)
        if len(prompts) >= 5:
            break
    degenerate = len(prompts) < 2
    if degenerate:
        # this init has no early attractors (possible at real scale):
        # fall back to plain random prompts — lookup acceptance will
        # be near zero and the ratios below report that honestly
        prompts = bank[:5]

    def build(label):
        cache = PagedKVCache(cfg, num_pages=num_pages,
                             pages_max=pages_max, batch=batch,
                             page=page)
        kw = {"metrics_registry": default_registry(),
              "metrics_ring": default_ring()}
        if label == "H=4":
            kw["decode_horizon"] = 4
        elif label == "spec-draft-ceiling":
            dcache = PagedKVCache(cfg, num_pages=num_pages,
                                  pages_max=pages_max, batch=batch,
                                  page=page)
            kw["spec"] = SpecConfig(gamma=4, source="draft",
                                    draft_cfg=cfg, draft_params=params,
                                    draft_cache=dcache)
        elif label.startswith("spec-lookup"):
            kw["spec"] = SpecConfig(gamma=7, source="prompt_lookup")
            kw["overlap"] = label.endswith("overlap")
        return ContinuousBatchingEngine(cfg, params, cache, **kw)

    def pctl(xs, q):
        xs = sorted(xs)
        return xs[round(q * (len(xs) - 1))]

    arms = {}
    outputs = {}
    for label in ("H=1", "H=4", "spec-draft-ceiling", "spec-lookup",
                  "spec-lookup-overlap"):
        eng = build(label)
        global _SPEC_ENGINE
        _SPEC_ENGINE = eng
        spec_on = label.startswith("spec")

        def wave():
            for i in range(n_req):
                eng.submit(prompts[i % len(prompts)],
                           max_new_tokens=new,
                           spec=True if spec_on else None)
            return eng.run_to_completion()
        # two full-shape warm waves: the 16-request wave exercises
        # admit-during-decode paths an 8-request wave never compiles
        wave()
        wave()
        steps0, syncs0 = eng.decode_steps, eng.host_syncs
        dr0 = getattr(eng, "spec_drafted", 0)
        ac0 = getattr(eng, "spec_accepted", 0)
        t0 = time.perf_counter()
        done = wave()
        dt = time.perf_counter() - t0
        steps = eng.decode_steps - steps0
        dec_tokens = sum(len(r.generated) - 1 for r in done)
        ttfts = [r.t_first_token - r.t_submit for r in done]
        tpots = [(r.t_finish - r.t_first_token)
                 / max(len(r.generated) - 1, 1) for r in done]
        outputs[label] = {r.rid % len(prompts): list(r.generated)
                          for r in done}
        arm = {
            "decode_tok_per_s": round(
                sum(len(r.generated) for r in done) / dt, 1),
            "dispatches_per_token": round(
                steps / max(dec_tokens, 1), 4),
            "ttft_p50_ms": round(
                statistics.median(ttfts) * 1000, 2),
            "ttft_p99_ms": round(pctl(ttfts, 0.99) * 1000, 2),
            "tpot_p50_ms": round(
                statistics.median(tpots) * 1000, 3),
            "tpot_p99_ms": round(pctl(tpots, 0.99) * 1000, 3),
            "decode_dispatches": steps,
            "host_syncs": eng.host_syncs - syncs0,
        }
        if spec_on:
            drafted = eng.spec_drafted - dr0
            arm["acceptance_rate"] = round(
                (eng.spec_accepted - ac0) / max(drafted, 1), 4)
            arm["drafted_tokens"] = drafted
        arms[label] = arm

    # token-exactness across arms: every lane must emit the H=1
    # greedy sequence for the same prompt (requests are budget-bound
    # and deterministic, so per-prompt outputs are comparable)
    exact = all(outputs[lab] == outputs["H=1"] for lab in arms)
    ratio = (arms["spec-lookup"]["decode_tok_per_s"]
             / max(arms["H=1"]["decode_tok_per_s"], 1e-9))
    return {
        "metric": "serving_spec_ab",
        # headline: fused prompt-lookup spec vs plain H=1 decode
        # throughput on the lane's design-case workload
        "value": round(ratio, 3),
        "unit": "x",
        "vs_baseline": 0,
        "extra": {
            "platform": platform, "requests": n_req,
            "batch_slots": batch, "max_new_tokens": new,
            "token_exact_vs_plain": exact,
            "workload": ("random-prompts (degenerate: no early "
                         "greedy cycles found)" if degenerate else
                         f"repetitive-continuation x{len(prompts)} "
                         f"(cycle periods {periods})"),
            "arms": arms,
            "note": "equal load per arm; draft-ceiling arm uses "
                    "draft == target (acceptance 1.0 by construction "
                    "— an overhead bound, not a draft-model result); "
                    "lookup acceptance < 1 is real n-gram misses.  "
                    "CPU-smoke caveats in PERF.md.",
        },
    }


def _snapshot_line() -> dict:
    """Final line: the process-wide registry snapshot + recent events,
    so BENCH_r*.json carries the engine/serving counters (occupancy,
    cache hit rate, init-attempt history) next to the throughput
    numbers.  ``host_overhead_frac`` = host bookkeeping seconds /
    decode-step seconds across all engines this process ran — the
    fraction of decode wall the dispatch-ahead pipeline can hide."""
    from paddle_tpu.observability import (default_registry,
                                          default_ring, default_tracer)
    snap = default_registry().snapshot()
    host = snap.get("paddle_tpu_engine_host_bookkeeping_seconds") or {}
    dec = snap.get("paddle_tpu_engine_decode_step_seconds") or {}
    frac = (host.get("sum", 0.0) / dec["sum"]) if dec.get("sum") else 0.0
    # padding waste across packed admission waves: wasted prefill
    # slots / dispatched packed-stream slots (registry-visible engines
    # admit packed by default; tools/metrics_dump.py prints this)
    padded = snap.get(
        "paddle_tpu_engine_prefill_padded_tokens_total") or {}
    packed = snap.get("paddle_tpu_engine_prefill_packed_tokens") or {}
    pfrac = ((padded.get("value") or 0.0) / packed["sum"]) \
        if packed.get("sum") else 0.0

    def _cval(name):
        m = snap.get(name) or {}
        return m.get("value") or 0.0

    return {"metric": "metrics_snapshot", "value": len(snap),
            "unit": "metrics", "vs_baseline": 0,
            "extra": {"snapshot": snap,
                      "host_overhead_frac": round(frac, 4),
                      "prefill_padded_token_frac": round(pfrac, 4),
                      # two-tier KV cache swap traffic (the preemption
                      # A/B's engines publish process-wide)
                      "swap_out_pages_total": _cval(
                          "paddle_tpu_kvcache_swap_out_pages_total"),
                      "swap_in_pages_total": _cval(
                          "paddle_tpu_kvcache_swap_in_pages_total"),
                      "swap_bytes_total": _cval(
                          "paddle_tpu_kvcache_swap_bytes_total"),
                      "prefill_tokens_avoided_total": _cval(
                          "paddle_tpu_engine_prefill_tokens_avoided"
                          "_total"),
                      # fault-tolerance counters (the fault-recovery
                      # bench line's engines publish process-wide)
                      "requests_faulted_total": _cval(
                          "paddle_tpu_engine_requests_faulted_total"),
                      "engine_restarts_total": _cval(
                          "paddle_tpu_engine_restarts_total"),
                      "requests_rejected_total": _cval(
                          "paddle_tpu_engine_requests_rejected_total"),
                      # fleet tier (the serving_fleet_ab line's
                      # routers publish process-wide)
                      "fleet_failovers_total": _cval(
                          "paddle_tpu_fleet_failovers_total"),
                      "fleet_rejected_total": _cval(
                          "paddle_tpu_fleet_rejected_total"),
                      "fleet_replica_deaths_total": _cval(
                          "paddle_tpu_fleet_replica_deaths_total"),
                      "fleet_replica_replaces_total": _cval(
                          "paddle_tpu_fleet_replica_replaces_total"),
                      # mixed prefill+decode lane (the
                      # serving_mixed_ab line's engine publishes
                      # process-wide)
                      "mixed_ticks_total": _cval(
                          "paddle_tpu_engine_mixed_ticks_total"),
                      "mixed_piggybacked_prefill_tokens_total": _cval(
                          "paddle_tpu_engine_mixed_piggybacked_"
                          "prefill_tokens_total"),
                      # multi-token decode horizon (the
                      # serving_horizon_ab line's engines publish
                      # process-wide): stop-seq trim waste + the
                      # aggregate dispatch amortization
                      "horizon_trimmed_tokens_total": _cval(
                          "paddle_tpu_engine_horizon_trimmed_tokens"
                          "_total"),
                      "dispatches_per_token": round(
                          _cval("paddle_tpu_engine_decode_steps"
                                "_total")
                          / max(_cval(
                              "paddle_tpu_engine_tokens_generated"
                              "_total"), 1.0), 4),
                      # disaggregated prefill/decode (the
                      # serving_disagg_ab line's coordinator
                      # publishes process-wide)
                      "disagg_handoff_pages_total": _cval(
                          "paddle_tpu_disagg_handoff_pages_total"),
                      "disagg_handoff_bytes_total": _cval(
                          "paddle_tpu_disagg_handoff_bytes_total"),
                      "disagg_colocated_fallback_total": _cval(
                          "paddle_tpu_disagg_colocated_fallback"
                          "_total"),
                      # sockets transport (the serving_remote_ab
                      # line's socket-fleet arms publish
                      # process-wide)
                      "transport_reconnects_total": _cval(
                          "paddle_tpu_transport_reconnects_total"),
                      "transport_retries_total": _cval(
                          "paddle_tpu_transport_retries_total"),
                      "transport_heartbeat_misses_total": _cval(
                          "paddle_tpu_transport_heartbeat_misses"
                          "_total"),
                      "transport_frames_total": _cval(
                          "paddle_tpu_transport_frames_total"),
                      "transport_bytes_total": _cval(
                          "paddle_tpu_transport_bytes_total"),
                      # tail-sampled trace store: retention counters
                      # + the retained trace ids (drill into any of
                      # them with tools/metrics_dump.py trace)
                      "trace_retained_total": _cval(
                          "paddle_tpu_trace_retained_total"),
                      "trace_sampled_out_total": _cval(
                          "paddle_tpu_trace_sampled_out_total"),
                      "trace_ids": [
                          t["trace_id"] for t in
                          default_tracer().index(limit=20)],
                      "events": default_ring().recent(50)}}


def main() -> None:
    lines = [
        ("llama_1.3b_pretrain_tokens_per_sec_per_chip", "tokens/s/chip",
         _llama_line),
        ("resnet50_train_images_per_sec", "images/s", _resnet_line),
        ("bert_base_squad_finetune_samples_per_sec", "samples/s",
         _bert_line),
        ("serving_engine_decode_tokens_per_sec", "tokens/s",
         _serving_line),
        ("serving_engine_overlap_decode_tokens_per_sec", "tokens/s",
         _serving_overlap_line),
        ("serving_horizon_ab", "x", _horizon_line),
        ("serving_spec_ab", "x", _spec_ab_line),
        ("serving_admission_packed_vs_batched", "x", _admission_line),
        ("serving_tp_ab", "ratio", _serving_tp_line),
        ("serving_preemption_offload_resume_ab", "x",
         _preemption_line),
        ("serving_fault_recovery", "ratio", _fault_recovery_line),
        ("serving_fleet_ab", "x", _fleet_line),
        ("serving_qos_ab", "x", _serving_qos_line),
        ("serving_disagg_ab", "x", _disagg_line),
        ("serving_mixed_ab", "x", _serving_mixed_line),
        ("serving_trace_overhead", "ratio", _trace_overhead_line),
        ("serving_remote_ab", "x", _remote_line),
    ]

    devs, err = _init_devices()
    if devs is None:
        # Structured failure: one parseable error line per metric, no
        # traceback.  rc=1 tells the driver nothing was measured; the
        # snapshot still carries the per-attempt init history.
        for metric, unit, _ in lines:
            print(json.dumps(_error_line(
                metric, unit, f"backend init failed after retries: {err}")))
        print(json.dumps(_snapshot_line()))
        sys.stdout.flush()
        sys.exit(1)

    from paddle_tpu.framework.compile_cache import enable_compile_cache
    enable_compile_cache()
    failed = 0
    for metric, unit, fn in lines:
        try:
            print(json.dumps(fn()))
        except Exception as e:   # boundary: the other lines still run,
            failed += 1          # but the exit code tells the truth
            print(json.dumps(_error_line(
                metric, unit, f"{type(e).__name__}: {str(e)[:250]}")))
        sys.stdout.flush()
    print(json.dumps(_snapshot_line()))
    sys.stdout.flush()
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
