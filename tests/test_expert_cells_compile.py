"""The expert training cell's whole step, as the benchmark builds it,
compiled for a DESCRIBED TPU v5e with no chip attached, and the kernels
only the two expert cells' shapes reach — the split flash form and the
mixers' four kernels at the expert cell's, both flash forms at the window
cell's 16k row, and the convolution cell's step with its two kernels
(fixtures and rules: ``tests/_tpu_compile.py``; the other
kernels and ``routed_ffn`` alone: ``tests/test_tpu_compile.py``; the
window, dense and hybrid cells' steps: ``tests/test_tpu_compile_cells.py``).
"""

import re

import pytest

import jax
import jax.numpy as jnp

from _tpu_compile import (KERNEL, ROWS_8K, _cell_step,  # noqa: F401
                          _experts_placed, _flash_module, _padded_from,
                          _placed, _routing_sorts, _sds, _text, _two_kernels,
                          compiled, one_chip, topo)


def test_train_step_of_the_expert_cell(one_chip, compiled):
    """The step of ``xing4.0-29b-a4b.pretrain-8k-moe`` as the benchmark
    builds it — a dense lead and four expert layers, every published
    width, 8 of 64 experts, 2 x 8192 tokens — fits a described v5e with
    NO compiler rematerialization (the test that chose the share: with 16
    experts and a quarter of the vocabulary it compiled with six
    ``.remat`` matrix products), runs attention and the grouped products
    as kernels, the mixers' passes over the four streams too, and holds
    no bf16 copy of an expert stack."""
    from benchmark import harness
    cell = harness.find_cell("xing4.0-29b-a4b.pretrain-8k-moe")
    assert cell.conf["num_hidden_layers"] == 5 and \
        (cell.traffic["batch"], cell.traffic["seq"]) == ROWS_8K
    c = _cell_step(one_chip, cell.name)
    text = c.as_text()
    for kernel in ("flash_fwd", "flash_bwd_dkv",
                   "grouped_mm", "grouped_mm_dw", "moe_sum_pairs"):
        assert kernel in text, kernel
    # the split form's backward is one pass at S 8192 (PR 42)
    assert "flash_bwd_dq" not in text
    # a dense lead: 2 flash (forward — full remat keeps its outputs, 5 x
    # 136 MB within ``KEPT_BYTES``, so the recompute has none —
    # and the one-pass backward); an expert layer: 2 flash, and the routed
    # path ON EACH OF ITS TWO BOUNDS (18,432 rows where the load's tiles
    # fit them, 67,584 otherwise: one ``conditional`` a pass): 2 grouped
    # products + the token side's sum forward, the same recomputed (the
    # mixer's ``hc_post`` reads the sublayer's output), 2 products + 2 dw
    # + the sum backward
    # + the mixers (``ops/pallas/hc_mix.py``), in the lead's loop and in
    # the expert layers': two sublayers forward (``hc_pre_fwd``,
    # ``hc_post_fwd``: 4), the same recomputed but the last X', which
    # nothing reads again (3), ``hc_post_bwd`` and ``hc_pre_bwd`` of
    # each backward (4)
    assert text.count(KERNEL) == 4 + 2 * (3 + 3 + 5) + 2 * (4 + 3 + 4)
    for kernel in ("hc_pre_fwd", "hc_post_fwd", "hc_post_bwd",
                   "hc_pre_bwd"):
        assert kernel in text, kernel
    # no fp32 copy of the streams is an ARRAY of the program (inside a
    # fusion — the trunk's two ends sum and pad in fp32 — it is a value
    # on its way through registers)
    arrays = re.sub(r"(?m)^%fused_computation\S* .*\{\n(?:.*\n)*?\}\n", "",
                    text)
    assert "fused_computation" in text and len(arrays) < len(text)
    assert not re.search(r"= f32\[(2,8192|16384),14336\]", arrays)
    assert len(re.findall(r" conditional\(", text)) == 3
    for rows in (18432, 67584):
        assert f"bf16[{rows},3584]" in text
    # full remat keeps the routing (PR 46): the router's ``top_k`` and the
    # plan's two sorts are in the forward loop alone, and the recompute's
    # gate | up product is written where it is kept — no pad to the
    # bound of any load
    assert _routing_sorts(text) == (3, 0)
    assert not _padded_from(text, 18432, 67584, 2048)
    assert ".remat" not in text
    assert not re.search(r"bf16\[(4,)?8,3584,2048\]", text)
    # nor an fp32 copy of a layer's experts (PR 51): the grouped products
    # read the panels of ``[4, 8, 3584, 2048]`` / ``[4, 8, 1024, 3584]``
    # at the layer's index — until then a ``dynamic-slice`` fusion wrote
    # both leaves of a layer out, 352 MB, in the forward loop and in the
    # backward loop (4 such fusions)
    assert not _experts_placed(text, 8, 3584, 1024, layers=4)
    ma = c.memory_analysis()
    assert ma.argument_size_in_bytes == 3_057_670_144
    # PR 43's reading with the outputs kept (15,981,031,936 without).
    # The figure is no allocation's size: the buffer assignment holds the
    # kept stacks once, and its one HBM temp allocation grew by
    # 392,691,712 B to 11,188,912,640 (PERF.md section 6); + 64,929,280
    # with the four layers' routing kept (PR 46: 17,408,482,816 before);
    # 17,473,412,096 until a layer's experts had no copy (PR 51)
    assert ma.temp_size_in_bytes <= 17_121_155_072


def test_train_step_of_the_convolution_cell(one_chip, compiled):
    """The step of ``lfm2-24b-a2b.pretrain-8k-conv-moe`` as the benchmark
    builds it — a dense lead and one period (attention, three convolution
    layers), every published width, 16 of 64 experts, 2 x 8192 tokens —
    fits a described v5e with NO compiler rematerialization (depth 9,
    ISSUE 48's first choice, is refused: 15.91G of 15.75G, 4.5G of it the
    copies of each kind's two runs' slices of its stack), runs the
    operator's middle as ``short_conv_fwd`` / ``short_conv_bwd`` on the
    in-projection's ``[2, 8192, 6144]`` WHERE IT LIES, and routes once a
    layer a step."""
    from benchmark import harness
    cell = harness.find_cell("lfm2-24b-a2b.pretrain-8k-conv-moe")
    assert cell.conf["num_hidden_layers"] == 5 and \
        (cell.traffic["batch"], cell.traffic["seq"]) == ROWS_8K
    c = _cell_step(one_chip, cell.name)
    text = c.as_text()
    call = lambda kernel: len(re.findall(
        rf'custom_call_target="tpu_custom_call".*/{kernel}/pallas_call',
        text))
    # a convolution kind's loop: the forward, the recompute's, the backward
    assert (call("short_conv_fwd"), call("short_conv_bwd")) == (4, 2)
    # the attention layer: full remat keeps ``flash_fwd``'s outputs, and
    # the backward at 32 / 8 heads of 64, S 8,192 is the query-major one
    # pass (the hybrid cell's shape); the rotation at head dim 64 is XLA's
    assert (call("flash_fwd"), call("flash_bwd_dq"), call("flash_bwd_dkv"),
            call("rope")) == (1, 1, 0, 0)
    # a routed kind's loop ON EACH OF ITS TWO BOUNDS (36,864 rows where
    # the load's tiles fit them, 69,632 otherwise): 2 grouped products +
    # the token side's sum forward, the gate | up product again for the
    # recompute (its result is what the backward keeps; the layer's
    # output is read by nothing), 2 products + 2 dw + the sum backward
    assert text.count(KERNEL) == 3 + (2 + 2 * 9) + (3 + 2 * 9) == 44
    assert len(re.findall(r" conditional\(", text)) == 2 * 3
    for rows in (36864, 69632):
        assert f"bf16[{rows},2048]" in text
    # the routing is kept (PR 46's rule, for the two new kinds): the
    # router's ``top_k`` and the plan's two sorts in the forward loops only
    assert _routing_sorts(text) == (2 * 3, 0)
    assert ".remat" not in text
    # no array of the operator's widths is only sliced, copied, padded or
    # joined between the in-projection and the kernels, or behind them
    assert not _placed(text, ROWS_8K, {6144})
    # nor a layer's fp32 experts, ``[16, 2048, 3072]`` and ``[16, 1536,
    # 2048]`` (604 MB), before the grouped products of either routed
    # kind's loops (PR 51; four ``dynamic-slice`` fusions until then:
    # the forward loop's and the backward loop's of the three
    # convolution layers' scan)
    assert not _experts_placed(text, 16, 2048, 1536, layers=3)
    ma = c.memory_analysis()
    assert ma.argument_size_in_bytes == 3_157_483_008
    # 9,712,517,632 with a layer's experts copied (PR 49's program)
    assert ma.temp_size_in_bytes <= 9_019_862_528


@pytest.mark.parametrize("kernels", [2, 3])
def test_flash_attention_split_at_the_expert_cell_s_shapes(one_chip,
                                                           compiled,
                                                           monkeypatch,
                                                           kernels):
    """Latent attention at 2 x 8192, 32 heads of 128 | 64 | 128: Mosaic
    takes the two operand pairs, the shared 64-wide key whole, and the
    VMEM the whole-row operands ask for; five gradients from TWO
    kernels — a head's fp32 dQ is 4 MiB, ``ONE_PASS_DQ_BYTES`` exactly,
    so ``flash_bwd_dkv`` sums dQ and dQ2 too (40 MiB of VMEM asked) and
    ``flash_bwd_dq`` is absent — or, both budgets set to 0 bytes, from
    the three a row past both keeps."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_split
    b, s = ROWS_8K
    assert s * 128 * 4 == _flash_module().ONE_PASS_DQ_BYTES
    if kernels == 3:
        _two_kernels(monkeypatch)
    wide = _sds(one_chip, (b, s, 32, 128), jnp.bfloat16)
    text = _text(jax.grad(
        lambda *a: flash_attention_split(*a, 0.1).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4)), wide,
        _sds(one_chip, (b, s, 32, 64), jnp.bfloat16), wide,
        _sds(one_chip, (b, s, 64), jnp.bfloat16), wide)
    assert text.count(KERNEL) == kernels
    for kernel in ("flash_fwd", "flash_bwd_dkv"):
        assert kernel in text
    assert ("flash_bwd_dq" in text) == (kernels == 3)


def test_mixer_kernels_at_the_expert_cell_s_shapes(one_chip, compiled):
    """One sublayer of the four residual streams, forward and backward,
    at 2 x 8192 x (4 x 3584): the four kernels of ``ops/pallas/hc_mix``
    compile within the VMEM they ask for, and the maps' few numbers a token lie with the TOKENS ON
    THE LANES through Sinkhorn's rounds (XLA would write the transposition
    out of the kernels' ``[T, 128]`` as a layout, an eighth of each vector
    register in use)."""
    import types
    from paddle_tpu.models import hybrid_trunk
    from paddle_tpu.ops.pallas import hc_mix
    n, c = 4, 3584
    cfg = types.SimpleNamespace(
        hc_mult=n, hidden_size=c, rms_norm_eps=1e-6, hc_sinkhorn_iters=20,
        hc_eps=1e-6, mhc_h_res_clamp_min=-30.0, mhc_h_res_clamp_max=30.0)
    x = _sds(one_chip, ROWS_8K + (n * c,), jnp.bfloat16)
    assert hc_mix.takes(x, n, c)
    bp = {"hc1_phi": _sds(one_chip, (n * c, n * n + 2 * n), jnp.float32),
          "hc1_alpha": _sds(one_chip, (3,), jnp.float32),
          "hc1_b": _sds(one_chip, (n * n + 2 * n,), jnp.float32)}

    def loss(bp, x, g):
        out = hybrid_trunk._hc_sublayer(
            bp, "hc1", x, lambda h: h * jnp.asarray(0.5, h.dtype), cfg)
        return jnp.sum((out * g).astype(jnp.float32))
    text = _text(jax.value_and_grad(loss, (0, 1)), bp, x, x)
    assert text.count(KERNEL) == 4
    for kernel in ("hc_pre_fwd", "hc_post_fwd", "hc_post_bwd", "hc_pre_bwd"):
        assert kernel in text, kernel
    assert len(re.findall(r"= f32\[16,16384\]\{1,0", text)) > 100
    assert not re.search(r"= f32\[16,16384\]\{0,1", text)


@pytest.mark.parametrize("window,kernels", [
    (None, ("flash_fwd", "flash_bwd_dq")),
    (4096, ("flash_win_fwd", "flash_win_bwd_dq"))])
def test_flash_attention_16k_at_the_window_cell_s_shapes(one_chip, compiled,
                                                         window, kernels):
    """One row of 16,384 tokens, 28 query / 4 KV heads of 128: BOTH forms
    compile for a described v5e.  A head's K and V are 16 MiB resident
    with the pipeline's two buffers, past Mosaic's own limit, so the
    calls ask for what they hold (until PR 44 the dense forward stopped
    near 8k at d 128): the forward 16 + 8 MiB.  7 * 16384 * 128 * 4 B of
    fp32 dQ is past ``ONE_PASS_DQ_BYTES`` and 2 * 16384 * 128 * 4 B of
    fp32 dK and dV IS ``ONE_PASS_DKV_BYTES``: the query-major one pass —
    K and V, the dk and dv blocks (16 MiB each with two buffers), the
    two fp32 sums (16) and 8 for the tiles: 56 MiB asked, and
    ``flash_(win_)bwd_dkv`` is absent."""
    from paddle_tpu.ops.pallas.flash_attention import (ONE_PASS_DKV_BYTES,
                                                       flash_attention)
    assert 2 * 16384 * 128 * 4 == ONE_PASS_DKV_BYTES
    q = _sds(one_chip, (1, 16384, 28, 128), jnp.bfloat16)
    kv = _sds(one_chip, (1, 16384, 4, 128), jnp.bfloat16)
    text = _text(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, True, window=window).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)), q, kv, kv)
    assert text.count(KERNEL) == 2
    for kernel in kernels:
        assert kernel in text, kernel
    assert "bwd_dkv" not in text
    if window:
        assert "flash_bwd_dq" not in text
    asked = [int(n) for n in re.findall(
        KERNEL + r'".*"scoped_memory_configs":\[\{"memory_space":"1",'
        r'"offset":"0","size":"(\d+)"', text)]
    assert sorted(asked) == [(16 + 8) << 20, (16 + 16 + 16 + 8) << 20]

