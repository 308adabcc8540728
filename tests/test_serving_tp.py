"""Tensor-parallel everywhere: every serving lane on a sharded mesh.

Contract under test (the PR-7 tentpole, on a 4-way forced-host mesh):

* PACKED admission stays exactly ONE prefill dispatch per wave on a
  TP mesh (``_prefill_packed_tp`` through the shard_map seam) and is
  token-exact vs the batched-under-TP lane AND the single-device
  engine — across prefix caching, int8 KV pools, ``overlap=True``
  and preemption-with-offload;
* the host page tier composes with the sharded pool: per-shard
  staging round-trips BITWISE (fp and int8 + scale planes), swap
  resumes restore with zero prefill tokens, ``audit()`` stays clean;
* the dispatch-ahead pipeline over the sharded step keeps the
  zero-steady-state-blocking-sync contract (counted through the
  ``_fetch`` seam);
* ``SpeculativeEngine`` runs draft + verify on the same mesh,
  token-exact vs its single-device self and plain greedy;
* ``tp_allreduce="int8"`` (EQuARX-style quantized ring RS/AG) moves
  <= ~30% of the fp32 collective bytes per decode step and holds a
  pinned STATISTICAL bar vs the fp32 lane (teacher-forced logit
  error, like the int8-KV acceptance), not token-exactness.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models.llama_pretrain import (LlamaPretrainConfig,
                                              build_mesh, init_params)
from paddle_tpu.models.decode import make_generate
from paddle_tpu.models.paged_decode import (
    PagedKVCache, tp_collective_bytes_per_step, _q8_ring_plan)
from paddle_tpu.models.serving_engine import ContinuousBatchingEngine

pytestmark = pytest.mark.tp

MP = 4      # the acceptance mesh: 4-way (conftest forces 8 devices)


def _cfg(**kw):
    # nkv divides MP so heads shard 4-way
    base = dict(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, max_seq_len=256, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False, loss_chunks=1,
        use_pallas_attention=False)
    base.update(kw)
    return LlamaPretrainConfig(**base)


def _mesh(mp):
    return build_mesh(dp=1, pp=1, sharding=1, sep=1, mp=mp,
                      devices=jax.devices()[:mp])


def _setup(cfg, mp, cache_kw=None):
    mesh = _mesh(mp)
    m = mesh if mp > 1 else None
    params = init_params(cfg, jax.random.PRNGKey(0), mesh)
    ck = dict(num_pages=64, pages_max=8, batch=2, page=16)
    ck.update(cache_kw or {})
    cache = PagedKVCache(cfg, mesh=m, **ck)
    return m, params, cache


def _solo_ref(cfg, params, prompt, new):
    g = make_generate(cfg, prompt_len=len(prompt), max_new_tokens=new)
    return list(np.asarray(g(params, jnp.asarray(prompt[None]),
                             jax.random.PRNGKey(0)))[0])


def _prompts(seed=0, n=4, lo=4, hi=20):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 128, (int(rng.randint(lo, hi)),))
            for _ in range(n)]


def _run(cfg, mp, prompts, new=6, cache_kw=None, **ek):
    m, params, cache = _setup(cfg, mp, cache_kw)
    eng = ContinuousBatchingEngine(cfg, params, cache, mesh=m, **ek)
    for p in prompts:
        eng.submit(p, max_new_tokens=new)
    done = eng.run_to_completion()
    return {r.rid: list(r.generated) for r in done}, eng


# ---------------------------------------------------------------------------
# packed admission on the mesh
# ---------------------------------------------------------------------------
def test_tp_packed_one_dispatch_per_wave_token_exact():
    """The tentpole pin: a mixed-length admission wave on a 4-way mesh
    is exactly ONE prefill dispatch (the shard_map packed program),
    and output is token-exact vs batched-under-TP, the single-device
    packed engine, and solo dense generation."""
    cfg = _cfg()
    rng = np.random.RandomState(0)
    # one wave fills the batch; lengths straddle the 64-token prefill
    # bucket so the batched lane pays one dispatch PER BUCKET
    prompts = [rng.randint(1, 128, (10,)), rng.randint(1, 128, (80,))]

    got_tp, eng_tp = _run(cfg, MP, prompts, packed=True)
    assert eng_tp.prefill_calls == 1, \
        "a mixed-length wave on a mesh must be ONE packed dispatch"
    got_tpb, eng_tpb = _run(cfg, MP, prompts, packed=False)
    assert eng_tpb.prefill_calls >= 2   # one per length bucket
    got_1, _ = _run(cfg, 1, prompts, packed=True)
    assert got_tp == got_tpb == got_1
    # multi-wave: 2 slots x 4 prompts -> 2 waves = 2 packed dispatches
    more = prompts + [np.asarray(p[:-1]) for p in prompts]
    got_m, eng_m = _run(cfg, MP, more, packed=True)
    assert eng_m.prefill_calls == 2
    got_m1, _ = _run(cfg, 1, more, packed=True)
    assert got_m == got_m1


def test_tp_packed_prefix_cache_token_exact():
    """Prefix-cache admissions under TP packed: reused pages gather
    from the LOCAL pool shard (history lane of _prefill_packed_tp) and
    outputs stay token-exact; the index actually hits."""
    cfg = _cfg()
    rng = np.random.RandomState(2)
    common = rng.randint(1, 128, (32,))         # two full pages
    prompts = [np.concatenate([common, rng.randint(1, 128, (k,))])
               for k in (3, 5, 7, 9)]

    got_tp, eng_tp = _run(cfg, MP, prompts, enable_prefix_caching=True)
    got_1, eng_1 = _run(cfg, 1, prompts, enable_prefix_caching=True)
    got_plain, _ = _run(cfg, 1, prompts)
    assert got_tp == got_1 == got_plain
    assert eng_tp.cache.prefix_hits > 0
    eng_tp.cache.audit()


def test_tp_packed_int8_kv_token_exact():
    """int8 KV pools compose with the TP packed lane: per-LOCAL-head
    scale planes shard with the heads; the mp=4 int8 engine matches
    the single-device int8 packed engine token-exactly."""
    cfg = _cfg()
    prompts = _prompts(3, n=4)
    ck = dict(kv_quant="int8")
    got_tp, _ = _run(cfg, MP, prompts, cache_kw=ck)
    got_1, _ = _run(cfg, 1, prompts, cache_kw=ck)
    assert got_tp == got_1


# ---------------------------------------------------------------------------
# overlap pipeline on the mesh
# ---------------------------------------------------------------------------
def test_tp_overlap_zero_steady_state_syncs_token_exact():
    """The dispatch-ahead pipeline over the sharded step on a 4-way
    mesh: steady-state decode performs zero blocking host syncs on the
    step it just dispatched (every fetch lands only after a newer
    dispatch is in flight, one fetch per drained step, no flushes) —
    and output is token-exact vs the single-device synchronous
    engine."""
    cfg = _cfg()
    rng = np.random.RandomState(4)
    prompt = rng.randint(1, 128, (10,))
    new = 16
    m, params, cache = _setup(cfg, MP, dict(batch=1))
    eng = ContinuousBatchingEngine(cfg, params, cache, mesh=m,
                                   overlap=True)
    events = []
    orig_dispatch, orig_fetch = eng._dispatch_async, eng._fetch
    eng._dispatch_async = lambda: (events.append("d"),
                                   orig_dispatch())[1]
    eng._fetch = lambda *a: (events.append("f"), orig_fetch(*a))[1]
    eng.submit(prompt, max_new_tokens=new)
    done = eng.run_to_completion()

    _, params1, _ = _setup(cfg, 1)
    assert list(done[0].generated) == _solo_ref(cfg, params1, prompt,
                                                new)
    assert eng.pipeline_flushes == 0
    # steady state: the first fetch only after the second dispatch,
    # and every subsequent fetch trails a newer dispatch
    first_f = events.index("f")
    assert events[:first_f].count("d") >= 2
    assert events.count("f") == events.count("d")


# ---------------------------------------------------------------------------
# host page tier on the sharded pool
# ---------------------------------------------------------------------------
def test_tp_sharded_swap_roundtrip_bitwise():
    """Per-shard staging (kv_offload._split_shards): a swap-out /
    swap-in of a kv-head-sharded row round-trips BITWISE — pages and
    the int8 scale planes alike."""
    cfg = _cfg()
    for quant in (None, "int8"):
        mesh = _mesh(MP)
        cache = PagedKVCache(cfg, num_pages=16, pages_max=4, batch=2,
                             page=16, mesh=mesh, host_pages=8,
                             kv_quant=quant)
        rng = np.random.RandomState(5)
        Lyr, nkv, d = (cfg.num_hidden_layers,
                       cfg.num_key_value_heads, cfg.head_dim)
        cache.alloc_row(0, 40)
        ks = jnp.asarray(rng.randn(Lyr, 48, nkv, d).astype(np.float32))
        vs = jnp.asarray(rng.randn(Lyr, 48, nkv, d).astype(np.float32))
        cache.write_row_pages(0, ks, vs, 40)
        pids = cache.tables[0, :3].copy()
        before_k = np.asarray(cache.kpool[:, pids])
        before_v = np.asarray(cache.vpool[:, pids])
        scales = None
        if quant == "int8":
            scales = (np.asarray(cache.kscale[:, pids]),
                      np.asarray(cache.vscale[:, pids]))
        handle = cache.swap_out_row(0)
        assert cache.swap_in_row(0, handle) == 40
        pids2 = cache.tables[0, :3]
        assert np.array_equal(before_k, np.asarray(cache.kpool[:, pids2]))
        assert np.array_equal(before_v, np.asarray(cache.vpool[:, pids2]))
        if quant == "int8":
            assert np.array_equal(scales[0],
                                  np.asarray(cache.kscale[:, pids2]))
            assert np.array_equal(scales[1],
                                  np.asarray(cache.vscale[:, pids2]))
        cache.audit()


def test_tp_preemption_with_offload_token_exact():
    """Preemption under pool pressure on the mesh, host tier attached:
    victims SWAP OUT per shard, resumes restore with zero prefill
    tokens, outputs stay token-exact vs the single-device engine, and
    page accounting audits clean."""
    cfg = _cfg()
    rng = np.random.RandomState(6)
    prompts = [rng.randint(1, 128, (12,)) for _ in range(2)]
    new = 40             # each row grows to 4 pages; two rows need 8
    #                      but only 6 are usable -> preemption churn
    ck = dict(num_pages=7, pages_max=8, host_pages=16)

    def run(mp, **ek):
        m, params, cache = _setup(cfg, mp, dict(ck))
        eng = ContinuousBatchingEngine(cfg, params, cache, mesh=m,
                                       **ek)
        eng.offload_swap_gbps = 1e9      # cost model: swap always wins
        for p in prompts:
            eng.submit(p, max_new_tokens=new)
        done = eng.run_to_completion()
        return {r.rid: list(r.generated) for r in done}, eng

    got_tp, eng_tp = run(MP)
    got_1, eng_1 = run(1)
    assert got_tp == got_1
    assert eng_tp.preemptions > 0, "pool pressure must preempt"
    assert eng_tp.resumes_swapped > 0, \
        "the sharded host tier must serve swap resumes"
    assert eng_tp.prefill_tokens_avoided > 0
    eng_tp.cache.audit()


def test_tp_prefix_demote_promote_token_exact():
    """The two-tier prefix cache on a sharded pool: demoted prefix
    pages promote back from the host tier (per-shard gather/restore)
    and admissions stay token-exact."""
    cfg = _cfg()
    rng = np.random.RandomState(7)
    common = rng.randint(1, 128, (32,))
    prompts = [np.concatenate([common, rng.randint(1, 128, (k,))])
               for k in (3, 5, 7, 9, 11, 13)]
    ck = dict(num_pages=12, pages_max=8, host_pages=16)
    got_tp, eng_tp = _run(cfg, MP, prompts, cache_kw=ck,
                          enable_prefix_caching=True)
    got_1, _ = _run(cfg, 1, prompts, cache_kw=ck,
                    enable_prefix_caching=True)
    got_plain, _ = _run(cfg, 1, prompts)
    assert got_tp == got_1 == got_plain
    eng_tp.cache.audit()


# ---------------------------------------------------------------------------
# speculative serving on the mesh
# ---------------------------------------------------------------------------
def _spec_cfgs():
    cfg = _cfg()
    dcfg = _cfg(hidden_size=32, intermediate_size=64,
                num_hidden_layers=1)
    return cfg, dcfg


def _run_spec(mp, prompts, new=8, overlap=False):
    from paddle_tpu.models.speculative import SpeculativeEngine
    cfg, dcfg = _spec_cfgs()
    mesh = _mesh(mp)
    m = mesh if mp > 1 else None
    params = init_params(cfg, jax.random.PRNGKey(0), mesh)
    dparams = init_params(dcfg, jax.random.PRNGKey(1), mesh)
    cache = PagedKVCache(cfg, num_pages=64, pages_max=8, batch=2,
                         page=16, mesh=m)
    dcache = PagedKVCache(dcfg, num_pages=64, pages_max=8, batch=2,
                          page=16, mesh=m)
    eng = SpeculativeEngine(cfg, params, cache, dcfg, dparams, dcache,
                            gamma=3, mesh=m, overlap=overlap)
    for p in prompts:
        eng.submit(p, max_new_tokens=new)
    done = eng.run_to_completion()
    return {r.rid: list(r.generated) for r in done}, eng


@pytest.mark.slow
def test_tp_speculative_token_exact():
    """SpeculativeEngine on a 4-way mesh: draft + verify both run
    sharded and the committed output is token-exact vs the
    single-device speculative engine AND the plain greedy engine."""
    cfg, _ = _spec_cfgs()
    prompts = _prompts(8, n=4)
    got_tp, eng_tp = _run_spec(MP, prompts)
    got_1, _ = _run_spec(1, prompts)
    got_plain, _ = _run(cfg, 1, prompts, new=8)
    assert got_tp == got_1 == got_plain
    assert eng_tp.spec_rounds > 0
    assert eng_tp.tp_allreduce_bytes > 0   # draft+verify accounted


def test_tp_speculative_overlap_token_exact():
    """Dispatch-ahead drafting composes with the TP mesh."""
    prompts = _prompts(9, n=3)
    got_tp, _ = _run_spec(MP, prompts, overlap=True)
    got_1, _ = _run_spec(1, prompts, overlap=False)
    assert got_tp == got_1


def test_tp_speculative_mesh_mismatch_names_constraint():
    """The rejection message names the REAL constraint (draft pool on
    the same mesh) and a workaround — not 'compose later'."""
    from paddle_tpu.models.speculative import SpeculativeEngine
    cfg, dcfg = _spec_cfgs()
    mesh = _mesh(2)
    params = init_params(cfg, jax.random.PRNGKey(0), mesh)
    dparams = init_params(dcfg, jax.random.PRNGKey(1), mesh)
    cache = PagedKVCache(cfg, num_pages=64, pages_max=8, batch=2,
                         page=16, mesh=mesh)
    dcache = PagedKVCache(dcfg, num_pages=64, pages_max=8, batch=2,
                          page=16)          # NOT on the mesh
    with pytest.raises(ValueError, match="SAME mesh") as ei:
        SpeculativeEngine(cfg, params, cache, dcfg, dparams, dcache,
                          gamma=3, mesh=mesh)
    msg = str(ei.value)
    assert "mesh=" in msg and "Workaround" in msg
    assert "compose later" not in msg


# ---------------------------------------------------------------------------
# quantized all-reduce: bytes budget + statistical bar
# ---------------------------------------------------------------------------
def test_tp_allreduce_int8_bytes_budget():
    """The acceptance pin: tp_allreduce='int8' moves <= ~30% of the
    fp32 collective bytes per decode step (int8 payloads + f32
    per-block scales on every ring hop), at this config's block size
    and asymptotically less at real hidden sizes; the engine counter
    advances by exactly the analytic per-step figure."""
    cfg = _cfg()
    fp = tp_collective_bytes_per_step(cfg, MP, "fp32", batch=2)
    q8 = tp_collective_bytes_per_step(cfg, MP, "int8", batch=2)
    assert fp > 0 and q8 > 0
    assert q8 / fp <= 1.0 / 3.0 + 1e-9, (q8, fp)
    # asymptotic check at a production hidden size: strictly < 30%
    big = _cfg(hidden_size=1024, intermediate_size=2048,
               num_attention_heads=8, num_key_value_heads=8)
    assert (tp_collective_bytes_per_step(big, MP, "int8")
            / tp_collective_bytes_per_step(big, MP, "fp32")) < 0.30

    prompts = _prompts(10, n=2)
    got, eng = _run(cfg, MP, prompts, tp_allreduce="int8")
    assert eng.tp_allreduce_bytes == eng.decode_steps * q8
    got_fp, eng_fp = _run(cfg, MP, prompts)
    assert eng_fp.tp_allreduce_bytes == eng_fp.decode_steps * fp


def test_tp_allreduce_int8_statistical_bar():
    """The pinned STATISTICAL bar for the quantized collective (the
    analog of the int8-KV acceptance): the quantized ring all-reduce
    itself is bounded DIRECTLY — relative error of the reduced sum
    under 2% of the value scale for unit-normal partials — and
    end-to-end greedy generation agrees with the fp32 lane on >= 75%
    of tokens (a tiny random-init model's logits are tightly packed,
    so one argmax flip legitimately FORKS the rest of that sequence —
    the collective-level bound above is the principled part of the
    bar), with every sequence's first (exact-prefill-fed) token
    identical."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.models.paged_decode import _make_q8_allreduce
    # 1. the collective's own error bound: int8 wire with per-block
    #    scales keeps each hop's rounding <= 1/254 of the block max;
    #    mp-1 accumulation hops keep the total well under 2%
    mesh = _mesh(MP)
    nch, block = _q8_ring_plan(64, MP)
    ar = _make_q8_allreduce("mp", MP, 64 // nch, block)
    g = jax.jit(jax.shard_map(lambda x: ar(x[0]), mesh=mesh,
                              in_specs=(P("mp"),), out_specs=P(),
                              check_vma=False))
    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.randn(MP, 8, 64 // nch).astype(np.float32))
    got = np.asarray(g(x))
    want = np.asarray(x).sum(0)
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel < 0.02, f"quantized all-reduce rel error {rel:.4f}"

    # 2. end-to-end generation bar vs the fp32 lane
    cfg = _cfg()
    prompts = [rng.randint(1, 128, (int(rng.randint(8, 24)),))
               for _ in range(4)]
    got_fp, _ = _run(cfg, MP, prompts, new=8)
    got_q8, _ = _run(cfg, MP, prompts, new=8, tp_allreduce="int8")
    total = agree = 0
    for rid in got_fp:
        for a, b in zip(got_fp[rid], got_q8[rid]):
            total += 1
            agree += int(a == b)
    assert agree / total >= 0.75, f"agreement {agree}/{total}"
    for rid in got_fp:
        assert got_fp[rid][0] == got_q8[rid][0]


def test_tp_allreduce_int8_requires_mesh():
    """tp_allreduce='int8' on a single-device engine is a loud
    ValueError — there are no collectives to quantize."""
    cfg = _cfg()
    _, params, cache = _setup(cfg, 1)
    with pytest.raises(ValueError, match="mp>1"):
        ContinuousBatchingEngine(cfg, params, cache,
                                 tp_allreduce="int8")
    with pytest.raises(ValueError, match="fp32"):
        ContinuousBatchingEngine(cfg, params, cache,
                                 tp_allreduce="int4")


def test_tp_q8_ring_plan_blocks():
    """The wire plan: blocks divide the per-rank chunk and the bytes
    model follows (1 + 4/block)/4 of fp32."""
    nch, block = _q8_ring_plan(64, 4)
    assert (64 // (4 * nch)) % block == 0
    nch2, block2 = _q8_ring_plan(1024, 4)
    assert (1024 // (4 * nch2)) % block2 == 0 and block2 == 32
    with pytest.raises(ValueError, match="divide"):
        _q8_ring_plan(63, 4)


def test_tp_allreduce_int8_overlap_statistical():
    """The quantized collective composes with the dispatch-ahead
    pipeline: overlap+int8 matches sync+int8 token-exactly (same
    program, same numerics — overlap changes scheduling, not math)."""
    cfg = _cfg()
    prompts = _prompts(12, n=3)
    got_sync, _ = _run(cfg, MP, prompts, tp_allreduce="int8")
    got_over, eng = _run(cfg, MP, prompts, tp_allreduce="int8",
                         overlap=True)
    assert got_sync == got_over
    assert eng.tp_allreduce_bytes > 0
