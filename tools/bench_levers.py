"""On-chip lever measurements (ROADMAP S5/D6 material).

Three experiments, one JSON line each (PERF.md-style keep-or-reject):
  1. ResNet50 re-measure — 3 runs, median (the round-4 1,598 img/s is
     unconfirmed vs round-3's 1,705; same config).
  2. FLAGS_pallas_rmsnorm_matmul A/B at the 1.3B bench config
     (device-resident buffers so the lever isn't hidden behind input
     transport).
  3. int8-KV paged decode at b=32 equal lengths vs the recorded
     1,769 dense / 1,260 paged-bf16 (PERF.md pending row).

Usage:  python tools/bench_levers.py [resnet|rmm|int8kv|all]
"""

from __future__ import annotations

import json
import sys
import time


def _fence(x):
    return float(x if not hasattr(x, "sum") else x.sum())


def measure_resnet(runs: int = 3):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.incubate import jit_train_step
    from paddle_tpu.vision import models as vmodels

    vals = []
    for r in range(runs):
        model = vmodels.resnet50(num_classes=1000)
        model.train()
        opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                        parameters=model.parameters())
        step = jit_train_step(model, paddle.nn.CrossEntropyLoss(), opt,
                              amp_level="O1")
        rng = np.random.RandomState(r)
        xs = [paddle.to_tensor(rng.randn(256, 3, 224, 224)
                               .astype(np.float32)) for _ in range(2)]
        ys = [paddle.to_tensor(rng.randint(0, 1000, (256,))
                               .astype(np.int64)) for _ in range(2)]
        float(step(xs[0], ys[0]))
        float(step(xs[1], ys[1]))
        t0 = time.perf_counter()
        loss = None
        for i in range(5):
            loss = step(xs[i % 2], ys[i % 2])
        float(loss)
        dt = time.perf_counter() - t0
        vals.append(256 * 5 / dt)
    med = sorted(vals)[len(vals) // 2]
    print(json.dumps({"experiment": "resnet50_remeasure",
                      "runs": [round(v, 1) for v in vals],
                      "median_img_s": round(med, 1),
                      "round3_ref": 1705.0, "round4_claim": 1598.0}))
    return med


def _llama_throughput(steps: int = 10):
    """1.3B device-resident throughput under the CURRENT flag state."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models.llama_pretrain import (
        LlamaPretrainConfig, build_mesh, init_params,
        init_adafactor_state, make_train_step)

    cfg = LlamaPretrainConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5504,
        num_hidden_layers=24, num_attention_heads=16,
        num_key_value_heads=16, max_seq_len=2048,
        use_pallas_attention=True, sequence_parallel=False,
        remat=True, remat_policy="full", dtype=jnp.bfloat16,
        loss_chunks=4)
    batch, seq = 8, 2048
    mesh = build_mesh(devices=jax.devices()[:1])
    with mesh:
        params = init_params(cfg, jax.random.PRNGKey(0), mesh)
        opt_state = init_adafactor_state(params)
        step = make_train_step(cfg, mesh, pp=1, microbatches=1,
                               lr=1e-2, optimizer="adafactor")
        toks = [jnp.asarray(np.random.RandomState(i).randint(
            0, 32000, (batch, seq + 1))) for i in range(4)]
        params, opt_state, loss = step(params, opt_state, toks[0])
        float(loss)
        params, opt_state, loss = step(params, opt_state, toks[1])
        float(loss)
        t0 = time.perf_counter()
        for i in range(steps):
            params, opt_state, loss = step(params, opt_state,
                                           toks[i % 4])
        float(loss)
        dt = time.perf_counter() - t0
    return batch * seq * steps / dt


def measure_rmm():
    from paddle_tpu.flags import set_flags
    base = _llama_throughput()
    set_flags({"FLAGS_pallas_rmsnorm_matmul": True})
    try:
        fused = _llama_throughput()
    finally:
        set_flags({"FLAGS_pallas_rmsnorm_matmul": False})
    print(json.dumps({
        "experiment": "rmsnorm_matmul_lever",
        "base_tok_s": round(base, 1), "fused_tok_s": round(fused, 1),
        "delta_pct": round((fused / base - 1) * 100, 2),
        "verdict": "KEEP" if fused > base * 1.005 else "REJECT"}))
    return base, fused


def measure_int8kv(batch: int = 32, ctx: int = 128, new: int = 128):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models.llama_pretrain import (
        LlamaPretrainConfig, build_mesh, init_params)
    from paddle_tpu.models.paged_decode import (PagedKVCache,
                                                generate_paged)

    cfg = LlamaPretrainConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5504,
        num_hidden_layers=24, num_attention_heads=16,
        num_key_value_heads=16, max_seq_len=4096,
        use_pallas_attention=True, remat=False, dtype=jnp.bfloat16,
        loss_chunks=1)
    mesh = build_mesh(devices=jax.devices()[:1])
    params = init_params(cfg, jax.random.PRNGKey(0), mesh)
    prompt = np.random.RandomState(0).randint(
        0, 32000, (batch, ctx)).astype(np.int64)

    out = {}
    for quant in (None, "int8"):
        need = (ctx + new + 63) // 64 + 1

        def fresh():
            c = PagedKVCache(cfg, num_pages=batch * need + 1,
                             pages_max=need, batch=batch, page=64,
                             kv_quant=quant)
            for b in range(batch):
                c.alloc_row(b, ctx)
            return c

        # warmup run compiles the fused program (memoised per cfg);
        # the timed run reuses it on a fresh cache
        _ = np.asarray(generate_paged(cfg, params, jnp.asarray(prompt),
                                      new, fresh(), fused=True))
        cache = fresh()
        t0 = time.perf_counter()
        toks = generate_paged(cfg, params, jnp.asarray(prompt), new,
                              cache, fused=True)
        _ = np.asarray(toks)
        dt = time.perf_counter() - t0
        out["paged_" + (quant or "bf16")] = round(batch * new / dt, 1)
    print(json.dumps({
        "experiment": "int8_kv_b32_equal",
        **out, "ref_dense_bf16": 1769.0, "ref_paged_bf16_r4": 1260.0}))
    return out


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("resnet", "all"):
        measure_resnet()
    if which in ("rmm", "all"):
        measure_rmm()
    if which in ("int8kv", "all"):
        measure_int8kv()


if __name__ == "__main__":
    main()
