"""Async dispatch-ahead serving engine (``overlap=True``): the decode
hot loop keeps its state device-resident (next token, lens, active
mask, remaining budget, per-slot done) and chains step k's on-device
outputs into step k+1's dispatch, draining results one step behind.

Contract under test:
* GREEDY TOKEN-EXACTNESS vs the synchronous engine across every nasty
  path — eos, multi-token stop sequences (host-only knowledge →
  pipeline flush), preemption mid-flight, chunked prefill, prefix
  caching, speculative rounds, TP shard_map serving;
* ZERO per-token blocking host syncs in steady-state decode, asserted
  through counting wrappers on the engine's dispatch/fetch seams (not
  grep);
* the pipeline flushes at scheduler mutation points and the page pool
  drains clean.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models.llama_pretrain import (LlamaPretrainConfig,
                                              init_params)
from paddle_tpu.models.decode import make_generate
from paddle_tpu.models.paged_decode import PagedKVCache
from paddle_tpu.models.serving_engine import ContinuousBatchingEngine


def _cfg():
    return LlamaPretrainConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_seq_len=256, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False, loss_chunks=1,
        use_pallas_attention=False)


def _params(cfg):
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "pp", "sharding", "sep", "mp"))
    return init_params(cfg, jax.random.PRNGKey(0), mesh)


def _solo_ref(cfg, params, prompt, new):
    g = make_generate(cfg, prompt_len=len(prompt), max_new_tokens=new)
    return list(np.asarray(g(params, jnp.asarray(prompt[None]),
                             jax.random.PRNGKey(0)))[0])


@pytest.mark.parametrize("kv_quant", [None, "int8"])
def test_overlap_token_exact_vs_sync_under_churn(kv_quant):
    """Mixed-length requests streamed through a 2-slot batch (forced
    queueing + slot reuse): per-request generations from the overlap
    engine equal the synchronous engine's token-for-token, and the
    pool drains clean."""
    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.RandomState(0)
    specs = [(rng.randint(1, 128, (int(rng.randint(3, 20)),)),
              int(rng.randint(2, 8))) for _ in range(5)]

    def run(overlap):
        cache = PagedKVCache(cfg, num_pages=64, pages_max=8, batch=2,
                             page=16, kv_quant=kv_quant)
        eng = ContinuousBatchingEngine(cfg, params, cache,
                                       overlap=overlap)
        for p, n in specs:
            eng.submit(p, max_new_tokens=n)
        done = eng.run_to_completion()
        assert cache.free_pages() == cache.num_pages - 1
        return {r.rid: list(r.generated) for r in done}, eng

    got_sync, _ = run(False)
    got_over, eng = run(True)
    assert got_over == got_sync
    assert eng.host_syncs > 0 and eng.decode_steps > 0


def test_overlap_streaming_matches_finished_generations():
    """drain_stream() under overlap still yields every (rid, token)
    pair exactly once, in per-request order (tokens surface one step
    later than sync; content is identical)."""
    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.RandomState(1)
    cache = PagedKVCache(cfg, num_pages=64, pages_max=8, batch=2,
                         page=16)
    eng = ContinuousBatchingEngine(cfg, params, cache, overlap=True)
    r1 = eng.submit(rng.randint(1, 128, (10,)), max_new_tokens=6)
    r2 = eng.submit(rng.randint(1, 128, (7,)), max_new_tokens=4)
    streamed = {r1: [], r2: []}
    while eng.has_work():
        eng.step()
        for rid, t in eng.drain_stream():
            streamed[rid].append(t)
    by_rid = {r.rid: r for r in eng.finished()}
    for rid, toks in streamed.items():
        assert toks == by_rid[rid].generated


def test_overlap_eos_stops_early():
    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.RandomState(2)
    prompt = rng.randint(1, 128, (6,))
    ref = _solo_ref(cfg, params, prompt, 8)
    # eos = the first generated token (past the first) that no earlier
    # one equals — with these weights ref[0] == ref[1], and an eos that
    # already appeared would stop the run before the position under test
    stop = next(i for i in range(1, len(ref)) if ref[i] not in ref[:i])
    cache = PagedKVCache(cfg, num_pages=32, pages_max=8, batch=1,
                         page=16)
    eng = ContinuousBatchingEngine(cfg, params, cache,
                                   eos_id=int(ref[stop]), overlap=True)
    eng.submit(prompt, max_new_tokens=10)
    done = eng.run_to_completion()
    assert done[0].generated == ref[:stop + 1]   # stopped at eos, not 10


def test_overlap_stop_sequence_retires_and_flushes():
    """A multi-token stop sequence is host-only knowledge: the drain
    retires the request mid-pipeline and schedules a flush; the
    surviving request is untouched and both match their solo runs."""
    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.RandomState(3)
    p1 = rng.randint(1, 128, (9,))
    ref1 = _solo_ref(cfg, params, p1, 12)
    stop = ref1[3:5]                         # completes at token 5
    p2 = rng.randint(1, 128, (7,))
    ref2 = _solo_ref(cfg, params, p2, 12)
    cache = PagedKVCache(cfg, num_pages=64, pages_max=8, batch=2,
                         page=16)
    eng = ContinuousBatchingEngine(cfg, params, cache, overlap=True)
    r1 = eng.submit(p1, max_new_tokens=12, stop_sequences=[stop])
    r2 = eng.submit(p2, max_new_tokens=12)
    done = {r.rid: list(r.generated) for r in eng.run_to_completion()}
    assert done[r1] == ref1[:5]
    assert done[r2] == ref2
    assert eng.pipeline_flushes >= 1, \
        "a host-only retirement must flush the pipeline"


def test_overlap_preemption_midflight_token_exact():
    """Pool exhaustion mid-decode with dispatches in flight: the
    pipeline drains before the victim is evicted, the victim resumes
    by recompute, and both requests match their solo greedy runs."""
    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.RandomState(4)
    cache = PagedKVCache(cfg, num_pages=5, pages_max=4, batch=2,
                         page=16)
    eng = ContinuousBatchingEngine(cfg, params, cache, overlap=True)
    prompts = [rng.randint(1, 128, (16,)) for _ in range(2)]
    for p in prompts:
        eng.submit(p, max_new_tokens=20)
    done = sorted(eng.run_to_completion(), key=lambda r: r.rid)
    assert len(done) == 2
    assert any(r.preempted > 0 for r in done), \
        "pool was sized to force preemption"
    for req, prompt in zip(done, prompts):
        assert list(req.generated) == _solo_ref(cfg, params, prompt,
                                                20)
    assert cache.free_pages() == cache.num_pages - 1


def test_overlap_chunked_prefill_and_prefix_caching():
    """Chunked admission and prefix-cached admission both compose
    with the pipeline (admission flushes it): long prompts and shared
    prefixes stay token-exact, and cached pages are still reused."""
    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.RandomState(5)
    p80 = rng.randint(1, 128, (80,))         # > chunk of 32
    cache = PagedKVCache(cfg, num_pages=32, pages_max=8, batch=2,
                         page=16)
    eng = ContinuousBatchingEngine(cfg, params, cache,
                                   prefill_chunk=32, overlap=True)
    eng.submit(p80, max_new_tokens=6)
    done = eng.run_to_completion()
    assert list(done[0].generated) == _solo_ref(cfg, params, p80, 6)

    prefix = rng.randint(1, 128, (48,))      # 3 full 16-pages
    tails = [rng.randint(1, 128, (5,)), rng.randint(1, 128, (9,))]
    cache = PagedKVCache(cfg, num_pages=64, pages_max=8, batch=2,
                         page=16)
    eng = ContinuousBatchingEngine(cfg, params, cache,
                                   enable_prefix_caching=True,
                                   overlap=True)
    for t in tails:
        eng.submit(np.concatenate([prefix, t]), max_new_tokens=5)
    done = sorted(eng.run_to_completion(), key=lambda r: r.rid)
    assert cache.prefix_hits == 3, cache.prefix_hits
    for req, t in zip(done, tails):
        p = np.concatenate([prefix, t])
        assert list(req.generated) == _solo_ref(cfg, params, p, 5)


def test_overlap_speculative_rounds_token_exact():
    """The speculative lanes (now the fused one-dispatch-per-round
    engine, reached through the SpeculativeEngine compat shim)
    reproduce each other's outputs token-exactly, and BOTH lanes pay
    one blocking fetch per round — overlap adds at most the final
    chained round's drain, never a per-token or per-draft cadence."""
    from paddle_tpu.models.speculative import SpeculativeEngine

    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.RandomState(6)
    prompts = [rng.randint(1, 128, (9,)), rng.randint(1, 128, (7,))]

    def run(overlap):
        cache = PagedKVCache(cfg, num_pages=64, pages_max=8, batch=2,
                             page=16)
        dcache = PagedKVCache(cfg, num_pages=64, pages_max=8, batch=2,
                              page=16)
        eng = SpeculativeEngine(cfg, params, cache, cfg, params,
                                dcache, gamma=3, overlap=overlap)
        for p in prompts:
            eng.submit(p, max_new_tokens=8)
        done = {r.rid: list(r.generated)
                for r in eng.run_to_completion()}
        return done, eng

    got_sync, eng_sync = run(False)
    got_over, eng_over = run(True)
    assert got_over == got_sync
    # gamma=3 with an identical-weights draft accepts everything: 8 new
    # tokens = 2 rounds.  The fused lane fetches ONCE per round in both
    # modes (the old sidecar engine paid gamma+2 syncs/round sync-side);
    # overlap's pipeline drains the last chained round as one extra
    # fetch.  Pin the exact counts so a regression to a per-draft or
    # per-token fetch cadence is loud.
    assert eng_sync.host_syncs == eng_sync.spec_rounds == 2, \
        (eng_sync.host_syncs, eng_sync.spec_rounds)
    assert eng_over.host_syncs <= eng_sync.host_syncs + 1, \
        (eng_over.host_syncs, eng_sync.host_syncs)
    for rid, p in enumerate(prompts):
        assert got_over[rid] == _solo_ref(cfg, params, p, 8)


def test_overlap_tp_sharded_serving_token_exact():
    """The dispatch-ahead pipeline over the TP shard_map step (mp=2):
    the async program wraps the sharded per-token step and the state
    advance rides replicated — outputs match the single-device
    synchronous engine exactly."""
    from paddle_tpu.models.llama_pretrain import build_mesh

    cfg = _cfg()
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 128, (int(rng.randint(4, 20)),))
               for _ in range(4)]

    def run(mesh, mp, overlap):
        params = init_params(cfg, jax.random.PRNGKey(0), mesh)
        cache = PagedKVCache(cfg, num_pages=64, pages_max=8, batch=2,
                             page=16, mesh=mesh if mp > 1 else None)
        eng = ContinuousBatchingEngine(
            cfg, params, cache, mesh=mesh if mp > 1 else None,
            overlap=overlap)
        for p in prompts:
            eng.submit(p, max_new_tokens=6)
        return {r.rid: list(r.generated)
                for r in eng.run_to_completion()}

    mesh_tp = build_mesh(dp=1, pp=1, sharding=1, sep=1, mp=2,
                         devices=jax.devices()[:2])
    got_tp = run(mesh_tp, mp=2, overlap=True)
    mesh_1 = build_mesh(devices=jax.devices()[:1])
    got_1 = run(mesh_1, mp=1, overlap=False)
    assert got_tp == got_1


def test_overlap_steady_state_no_per_token_blocking_sync():
    """REGRESSION GUARD for the tentpole claim: in steady-state decode
    (no admission, no stops, no preemption) the hot loop performs ZERO
    blocking host syncs on the step it just dispatched — every fetch
    lands only after a NEWER dispatch is already in flight, exactly
    one fetch per drained step, and the pipeline never flushes.
    Asserted by counting through the engine's dispatch/fetch seams."""
    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.RandomState(8)
    prompt = rng.randint(1, 128, (10,))
    new = 24
    cache = PagedKVCache(cfg, num_pages=64, pages_max=8, batch=1,
                         page=16)
    eng = ContinuousBatchingEngine(cfg, params, cache, overlap=True)

    events = []
    orig_dispatch = eng._dispatch_async
    orig_fetch = eng._fetch

    def counting_dispatch():
        events.append("dispatch")
        return orig_dispatch()

    def counting_fetch(*arrs):
        events.append("fetch")
        return orig_fetch(*arrs)

    eng._dispatch_async = counting_dispatch
    eng._fetch = counting_fetch

    eng.submit(prompt, max_new_tokens=new)
    done = eng.run_to_completion()
    assert list(done[0].generated) == _solo_ref(cfg, params, prompt,
                                                new)
    assert eng.pipeline_flushes == 0, \
        "steady-state decode must never flush the pipeline"

    dispatched = 0
    depth_at_fetch = []       # dispatches-ahead-of-host per fetch
    for ev in events:
        if ev == "dispatch":
            dispatched += 1
        else:
            depth_at_fetch.append(dispatched - len(depth_at_fetch))
    # fetching result k requires dispatch k+1 already issued: the host
    # only ever blocks on a step at least one behind the device.  The
    # exception is the final `lookahead` tail drain(s) once the batch
    # went idle — there is nothing left to overlap with.
    assert all(d >= 2 for d in depth_at_fetch[:-eng.lookahead]), \
        depth_at_fetch
    # the pipeline fully drains when the batch empties: one fetch per
    # dispatch, nothing stranded in flight
    assert len(depth_at_fetch) == dispatched
    assert not eng._inflight
    assert len(depth_at_fetch) >= new - 2


@pytest.mark.parametrize("lookahead", [1, 3])
def test_overlap_exactly_sized_request_at_row_capacity(lookahead):
    """A request sized to exactly fill its row (prompt + max_new ==
    pages_max * page): the pipeline's lens mirror over-advances past
    the table capacity for the dead-but-undrained row, which must NOT
    trip the capacity check — deeper lookahead widens that window."""
    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.RandomState(11)
    cache = PagedKVCache(cfg, num_pages=32, pages_max=4, batch=1,
                         page=16)                 # row cap 64 slots
    eng = ContinuousBatchingEngine(cfg, params, cache, overlap=True,
                                   lookahead=lookahead)
    prompt = rng.randint(1, 128, (16,))
    eng.submit(prompt, max_new_tokens=48)         # 16 + 48 == 64
    done = eng.run_to_completion()
    assert list(done[0].generated) == _solo_ref(cfg, params, prompt,
                                                48)
    assert cache.free_pages() == cache.num_pages - 1


def test_overlap_metrics_inflight_gauge_and_host_histogram():
    """The dispatch-ahead instruments: the in-flight gauge reads the
    live pipeline depth (0 once drained) and the host-bookkeeping
    histogram accumulates one sample per drained step."""
    from paddle_tpu.observability import MetricsRegistry

    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.RandomState(9)
    reg = MetricsRegistry()
    cache = PagedKVCache(cfg, num_pages=64, pages_max=8, batch=2,
                         page=16)
    eng = ContinuousBatchingEngine(cfg, params, cache,
                                   metrics_registry=reg, overlap=True)
    eng.submit(rng.randint(1, 128, (8,)), max_new_tokens=6)
    eng.step()
    eng.step()
    assert reg.get(
        "paddle_tpu_engine_inflight_dispatches_count").value \
        == len(eng._inflight) >= 1
    eng.run_to_completion()
    # the idle engine parks with an EMPTY pipeline (tail dispatches
    # drained) — a monitor alerting on pipeline depth reads 0
    assert reg.get(
        "paddle_tpu_engine_inflight_dispatches_count").value == 0
    host = reg.get("paddle_tpu_engine_host_bookkeeping_seconds")
    assert host.count >= 1 and host.sum >= 0.0
    assert reg.get(
        "paddle_tpu_engine_tokens_generated_total").value \
        == eng.tokens_generated
