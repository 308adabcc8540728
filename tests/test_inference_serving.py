"""Inference deployment surface (round-3 N1 partial): the HTTP serving
front + replica-per-device pool over an AOT-exported program.

Reference: fleet_executor DistModel (dist_model.h:57) device fan-out +
the serving products over AnalysisPredictor.
"""

import json
import urllib.request

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.inference import Config, convert_to_export
from paddle_tpu.inference.serving import (DevicePool, InferenceServer,
                                          predict_http)


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    paddle.seed(7)
    net = paddle.nn.Sequential(
        paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
        paddle.nn.Linear(16, 3))
    x = np.random.RandomState(0).randn(4, 8).astype("float32")
    y = net(paddle.to_tensor(x)).numpy()
    path = str(tmp_path_factory.mktemp("srv") / "model")
    convert_to_export(net, [((4, 8), "float32")], path)
    return path + ".stablehlo", x, y


def test_device_pool_spreads_replicas(artifact):
    prog, x, y = artifact
    devs = jax.local_devices()
    assert len(devs) >= 2, "suite runs with 8 virtual CPU devices"
    pool = DevicePool(Config(prog_file=prog), devices=devs[:4])
    assert len(pool.device_names) == 4
    # every replica serves the same math on its own device
    for i in range(4):
        outs = pool.run_on(i, [x])
        np.testing.assert_allclose(outs[0], y, rtol=1e-5, atol=1e-6)
    # round robin covers all replicas
    for _ in range(4):
        np.testing.assert_allclose(pool.run([x])[0], y, rtol=1e-5,
                                   atol=1e-6)


def test_http_server_round_trip(artifact):
    prog, x, y = artifact
    srv = InferenceServer(Config(prog_file=prog),
                          devices=jax.local_devices()[:2])
    port = srv.start()
    try:
        url = f"http://127.0.0.1:{port}"
        # health reports devices
        with urllib.request.urlopen(url + "/health", timeout=10) as r:
            meta = json.loads(r.read())
        assert meta["status"] == "ok" and len(meta["devices"]) == 2

        # two requests round-robin across the replicas
        for _ in range(2):
            outs = predict_http(url, [x])
            np.testing.assert_allclose(outs[0], y, rtol=1e-5,
                                       atol=1e-6)
        with urllib.request.urlopen(url + "/health", timeout=10) as r:
            assert json.loads(r.read())["requests"] == 2

        # malformed payload is a clean 400, not a dead server
        req = urllib.request.Request(
            url + "/predict", data=b"not an npz",
            headers={"Content-Type": "application/octet-stream"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 400
        # still alive
        np.testing.assert_allclose(predict_http(url, [x])[0], y,
                                   rtol=1e-5, atol=1e-6)
    finally:
        srv.stop()


def test_concurrent_requests_no_cross_leak(artifact):
    """Review regression: concurrent callers sharing ONE replica must
    each get their own outputs (Predictor.run's staged self._outputs
    would race; the pool uses the stateless _execute form)."""
    import threading
    prog, x, y = artifact
    pool = DevicePool(Config(prog_file=prog),
                      devices=jax.local_devices()[:1])
    errs = []

    def worker(i):
        xi = (x + i).astype(np.float32)
        want = pool.run_on(0, [xi])[0]          # sequential reference
        for _ in range(10):
            got = pool.run([xi])[0]
            if not np.allclose(got, want, atol=1e-5):
                errs.append(i)

    ts = [threading.Thread(target=worker, args=(i,), daemon=True)
          for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(120)
        assert not t.is_alive(), "a request never came back from the pool"
    assert not errs, f"cross-request leaks from threads {errs}"


def _gen_setup(mesh=None, batch=2):
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from paddle_tpu.models.llama_pretrain import (LlamaPretrainConfig,
                                                  init_params)
    from paddle_tpu.models.paged_decode import PagedKVCache
    cfg = LlamaPretrainConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_seq_len=256, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False, loss_chunks=1,
        use_pallas_attention=False)
    m = mesh or Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                     ("dp", "pp", "sharding", "sep", "mp"))
    params = init_params(cfg, jax.random.PRNGKey(0), m)
    cache = PagedKVCache(cfg, num_pages=64, pages_max=8, batch=batch,
                         page=16, mesh=mesh)
    return cfg, params, cache


def test_generation_server_concurrent_requests_parity():
    """The serving PRODUCT loop: two concurrent HTTP /generate requests
    batch through the continuous-batching engine and each response
    matches its solo greedy run; /generate_stream yields tokens
    incrementally and totals the same sequence."""
    import threading
    from paddle_tpu.inference.serving import (GenerationServer,
                                              generate_http,
                                              generate_http_stream)
    from paddle_tpu.models.decode import make_generate

    cfg, params, cache = _gen_setup()
    srv = GenerationServer(cfg, params, cache)
    port = srv.start()
    url = f"http://127.0.0.1:{port}"
    try:
        rng = np.random.RandomState(21)
        prompts = [rng.randint(1, 128, (int(rng.randint(5, 14)),))
                   for _ in range(2)]
        results = {}

        def call(i):
            results[i] = generate_http(url, prompts[i],
                                       max_new_tokens=6)

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert set(results) == {0, 1}
        import jax.numpy as jnp
        for i, p in enumerate(prompts):
            g = make_generate(cfg, prompt_len=len(p), max_new_tokens=6)
            ref = np.asarray(g(params, jnp.asarray(p[None]),
                               jax.random.PRNGKey(0)))[0]
            np.testing.assert_array_equal(np.asarray(results[i]), ref)

        # streaming endpoint: tokens arrive one line at a time and
        # concatenate to the same greedy sequence
        p = prompts[0]
        stream = list(generate_http_stream(url, p, max_new_tokens=6))
        g = make_generate(cfg, prompt_len=len(p), max_new_tokens=6)
        ref = np.asarray(g(params, jnp.asarray(p[None]),
                           jax.random.PRNGKey(0)))[0]
        np.testing.assert_array_equal(np.asarray(stream), ref)

        # oversized request -> 400, server keeps serving
        with pytest.raises(urllib.request.HTTPError):
            generate_http(url, rng.randint(1, 128, (300,)),
                          max_new_tokens=64)
        assert generate_http(url, p, max_new_tokens=3)
    finally:
        srv.stop()


def test_generation_server_tp_mesh_parity():
    """The same HTTP generation server over a TP mesh (mp=2, sharded
    params + kv-head-sharded pools): a model wider than one chip serves
    THROUGH THE PRODUCT FRONT with token-exact output (the
    fleet-executor DistModel serving analog, dist_model.h:57)."""
    from paddle_tpu.inference.serving import (GenerationServer,
                                              generate_http)
    from paddle_tpu.models.decode import make_generate
    from paddle_tpu.models.llama_pretrain import build_mesh

    mesh = build_mesh(dp=1, pp=1, sharding=1, sep=1, mp=2,
                      devices=jax.devices()[:2])
    cfg, params, cache = _gen_setup(mesh=mesh)
    srv = GenerationServer(cfg, params, cache, mesh=mesh)
    port = srv.start()
    try:
        rng = np.random.RandomState(22)
        p = rng.randint(1, 128, (9,))
        got = generate_http(f"http://127.0.0.1:{port}", p,
                            max_new_tokens=5)
        import jax.numpy as jnp
        g = make_generate(cfg, prompt_len=9, max_new_tokens=5)
        ref = np.asarray(g(params, jnp.asarray(p[None]),
                           jax.random.PRNGKey(0)))[0]
        np.testing.assert_array_equal(np.asarray(got), ref)
    finally:
        srv.stop()


def test_generation_server_engine_crash_fails_pending_loudly():
    """A crashed engine step must 500 the pending requests and 503 new
    submits — never leave HTTP clients blocked on silent queues."""
    from paddle_tpu.inference.serving import (GenerationServer,
                                              generate_http)

    cfg, params, cache = _gen_setup()
    srv = GenerationServer(cfg, params, cache)

    def boom():
        raise RuntimeError("induced engine failure")

    srv.engine.step = boom          # crash on first drive iteration
    port = srv.start()
    url = f"http://127.0.0.1:{port}"
    try:
        rng = np.random.RandomState(30)
        with pytest.raises(urllib.request.HTTPError) as ei:
            generate_http(url, rng.randint(1, 128, (6,)),
                          max_new_tokens=4, timeout=30)
        assert ei.value.code == 500
        with pytest.raises(urllib.request.HTTPError) as ei2:
            generate_http(url, rng.randint(1, 128, (6,)),
                          max_new_tokens=4, timeout=30)
        assert ei2.value.code == 503
    finally:
        srv.stop()


def test_generation_server_health_metrics():
    """/health reports live serving counters (tokens, steps, prefill
    dispatches, preemptions, pool occupancy)."""
    from paddle_tpu.inference.serving import (GenerationServer,
                                              generate_http)

    cfg, params, cache = _gen_setup()
    srv = GenerationServer(cfg, params, cache)
    port = srv.start()
    url = f"http://127.0.0.1:{port}"
    try:
        rng = np.random.RandomState(31)
        generate_http(url, rng.randint(1, 128, (8,)), max_new_tokens=4)
        with urllib.request.urlopen(url + "/health", timeout=10) as r:
            h = json.loads(r.read())
        assert h["status"] == "ok"
        assert h["requests_finished"] == 1
        assert h["tokens_generated"] >= 3     # admission token + steps
        assert h["prefill_calls"] == 1
        assert h["active"] == 0 and h["queued"] == 0
    finally:
        srv.stop()


def test_generation_server_over_speculative_engine():
    """The HTTP front serves a caller-built SpeculativeEngine
    unchanged — speculative continuous batching behind /generate,
    token-exact vs plain greedy."""
    from paddle_tpu.models.paged_decode import PagedKVCache
    from paddle_tpu.models.speculative import SpeculativeEngine
    from paddle_tpu.models.decode import make_generate
    from paddle_tpu.inference.serving import (GenerationServer,
                                              generate_http)
    import jax.numpy as jnp

    cfg, params, cache = _gen_setup()
    dcache = PagedKVCache(cfg, num_pages=64, pages_max=8, batch=2,
                          page=16)
    eng = SpeculativeEngine(cfg, params, cache, cfg, params, dcache,
                            gamma=3)
    srv = GenerationServer(engine=eng)
    port = srv.start()
    try:
        rng = np.random.RandomState(33)
        p = rng.randint(1, 128, (11,))
        got = generate_http(f"http://127.0.0.1:{port}", p,
                            max_new_tokens=6)
        g = make_generate(cfg, prompt_len=11, max_new_tokens=6)
        ref = np.asarray(g(params, jnp.asarray(p[None]),
                           jax.random.PRNGKey(0)))[0]
        np.testing.assert_array_equal(np.asarray(got), ref)
        assert eng.spec_rounds >= 1
    finally:
        srv.stop()


def test_generation_server_metrics_and_stats_endpoints():
    """Acceptance: GET /metrics on a live GenerationServer returns
    valid Prometheus text exposition whose values are consistent with
    the engine's internal counters; /stats returns the JSON snapshot;
    /events returns the structured ring tail; /health is a view over
    the same registry."""
    from paddle_tpu.inference.serving import (GenerationServer,
                                              generate_http)
    from paddle_tpu.observability import MetricsRegistry

    cfg, params, cache = _gen_setup()
    reg = MetricsRegistry()
    srv = GenerationServer(cfg, params, cache, metrics_registry=reg)
    assert srv.registry is reg            # server scrapes the engine's
    port = srv.start()
    url = f"http://127.0.0.1:{port}"
    try:
        rng = np.random.RandomState(41)
        for _ in range(2):
            generate_http(url, rng.randint(1, 128, (8,)),
                          max_new_tokens=5)

        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        # every sample line is NAME[{le="..."}] VALUE
        import re
        line_re = re.compile(
            r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{le="[^"]+"\})? \S+$')
        samples = {}
        for line in text.strip().splitlines():
            if line.startswith("#"):
                continue
            assert line_re.match(line), f"malformed: {line!r}"
            name, val = line.rsplit(" ", 1)
            samples[name] = float(val)

        eng = srv.engine
        assert samples[
            "paddle_tpu_engine_requests_finished_total"] == 2
        assert samples["paddle_tpu_engine_decode_steps_total"] \
            == eng.decode_steps
        assert samples["paddle_tpu_engine_tokens_generated_total"] \
            == eng.tokens_generated
        assert samples["paddle_tpu_engine_prefill_dispatches_total"] \
            == eng.prefill_calls
        assert samples["paddle_tpu_engine_preemptions_total"] \
            == eng.preemptions
        assert samples["paddle_tpu_kvcache_free_pages_count"] \
            == cache.free_pages()
        assert samples["paddle_tpu_engine_batch_occupancy_ratio"] == 0
        assert samples["paddle_tpu_request_ttft_seconds_count"] == 2
        assert samples["paddle_tpu_request_tpot_seconds_count"] == 2
        assert samples[
            "paddle_tpu_request_queue_wait_seconds_count"] == 2
        assert samples['paddle_tpu_request_ttft_seconds_bucket'
                       '{le="+Inf"}'] == 2
        assert samples["paddle_tpu_http_generate_requests_total"] == 2
        # what the process compiled, from the program's own log (PR 54):
        # this server's engine programs are among them
        from paddle_tpu.observability import compile_log
        assert {n for n in samples if n.startswith("paddle_tpu_compile_")} \
            == set(compile_log.INSTRUMENTS)
        assert samples["paddle_tpu_compile_programs_total"] >= 2
        assert samples["paddle_tpu_compile_programs_total"] \
            <= compile_log.totals()["programs"]

        # /stats: the JSON snapshot of the same registry
        with urllib.request.urlopen(url + "/stats", timeout=10) as r:
            stats = json.loads(r.read())
        m = stats["metrics"]
        assert m["paddle_tpu_engine_requests_finished_total"][
            "value"] == 2
        assert m["paddle_tpu_request_ttft_seconds"]["count"] == 2

        # /events: lifecycle events for both requests, seq-tagged
        with urllib.request.urlopen(url + "/events?n=50",
                                    timeout=10) as r:
            evs = json.loads(r.read())["events"]
        names = [e["name"] for e in evs]
        assert names.count("request_submitted") == 2
        assert names.count("request_finished") == 2
        seqs = [e["seq"] for e in evs]
        assert seqs == sorted(seqs)
        last = max(seqs)
        with urllib.request.urlopen(
                url + f"/events?since={last}", timeout=10) as r:
            assert json.loads(r.read())["events"] == []

        # /health reads the registry (same numbers, legacy keys)
        with urllib.request.urlopen(url + "/health", timeout=10) as r:
            h = json.loads(r.read())
        assert h["requests_finished"] == 2
        assert h["decode_steps"] == eng.decode_steps
        assert h["free_pages"] == cache.free_pages()
    finally:
        srv.stop()


def test_inference_server_metrics_endpoint(artifact):
    """InferenceServer exposes the same observability surface."""
    prog, x, y = artifact
    srv = InferenceServer(Config(prog_file=prog),
                          devices=jax.local_devices()[:1])
    port = srv.start()
    url = f"http://127.0.0.1:{port}"
    try:
        for _ in range(3):
            predict_http(url, [x])
        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            text = r.read().decode()
        assert "paddle_tpu_http_predict_requests_total 3" in text
        with urllib.request.urlopen(url + "/stats", timeout=10) as r:
            stats = json.loads(r.read())
        assert stats["metrics"][
            "paddle_tpu_http_predict_requests_total"]["value"] == 3
    finally:
        srv.stop()
