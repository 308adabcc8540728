"""Whole train steps compiled for a DESCRIBED TPU v5e with no chip
attached: the dp2 x mp2 sequence-parallel step at the 1.345B widths, and
the hybrid, the window and the dense training cells' steps as the
benchmark builds them (fixtures and rules: ``tests/_tpu_compile.py``; the
kernels alone: ``tests/test_tpu_compile.py``; the expert cell's step:
``tests/test_expert_cells_compile.py``).
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from _tpu_compile import (HEADS, KERNEL, MIXER_WIDTHS, ROWS_8K,  # noqa: F401
                          _cell_step, _cfg, _experts_placed, _padded_from,
                          _param_sds, _placed, _routing_sorts, _sds,
                          compiled, one_chip, topo)


@pytest.mark.parametrize("nkv", [HEADS, 8])
def test_train_step_dp2_mp2_sequence_parallel(topo, compiled, nkv):
    """The multi-chip train step with the Pallas kernels ON and the
    sequence-parallel constraint ON.  GSPMD refuses to partition a
    Mosaic kernel ("Mosaic kernels cannot be automatically
    partitioned"), so rope and flash run per shard
    (``llama_pretrain._per_shard``); the SP constraint follows the
    MESH's platform, so it is compiled here although the host is a
    CPU.  ``nkv`` 8: GQA, the heads split over ``mp`` 16/8 -> 8/4 a
    shard and the kernels keep the group ratio."""
    from paddle_tpu.models.llama_pretrain import (
        build_mesh, init_adafactor_state, make_train_step)
    mesh = build_mesh(dp=2, mp=2, devices=topo.devices)
    cfg = _cfg(1, train=True, sequence_parallel=True, nkv=nkv)
    with mesh:
        params = _param_sds(cfg, mesh)
        opt = jax.tree_util.tree_map(
            lambda x: _sds(mesh, x.shape, x.dtype),
            jax.eval_shape(init_adafactor_state, params))
        step = make_train_step(cfg, mesh, lr=1e-2, optimizer="adafactor")
        compiled_step = step.lower(
            params, opt,
            _sds(mesh, (8, 2049), jnp.int64, P("dp", None))).compile()
    text = compiled_step.as_text()
    assert text.count(KERNEL) >= 3           # rope + flash fwd/bwd
    assert "all-reduce" in text or "reduce-scatter" in text
    per_device = compiled_step.memory_analysis().argument_size_in_bytes
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    assert per_device < 0.75 * 4 * n_params   # sharded, not replicated


def test_train_step_by_kind_at_the_hybrid_cell_s_shapes(one_chip, compiled):
    """The step of ``granite-4.0-h-micro.pretrain-8k`` as the benchmark
    builds it — depth 10 (five state-space layers, one attention layer,
    four more), every published width, 2 x 8192 tokens — fits a
    described v5e with no compiler rematerialization, runs the scan and
    the convolution as kernels, holds no ``[256, 256]`` matrix, and
    between the in-projection and the scan writes no array of the
    mixer's that computes nothing (forward, recompute, backward: 8 a
    layer before the kernels took offsets, 16 in this text).  The one
    attention layer's ``flash_fwd`` runs once: full remat keeps its
    outputs (69 MB, within ``KEPT_BYTES``), and its backward is
    ONE pass since PR 45 (query-major, ``flash_bwd_dq``'s name;
    ``flash_bwd_dkv`` is absent)."""
    from benchmark import harness
    cell = harness.find_cell("granite-4.0-h-micro.pretrain-8k")
    assert cell.conf["num_hidden_layers"] == 10 and \
        (cell.traffic["batch"], cell.traffic["seq"]) == ROWS_8K
    c = _cell_step(one_chip, cell.name)
    text = c.as_text()
    for kernel in ("ssd_scan_fwd", "ssd_scan_bwd", "causal_conv_fwd",
                   "causal_conv_bwd", "flash_fwd", "flash_bwd_dq"):
        assert kernel in text, kernel
    assert "flash_bwd_dkv" not in text
    assert text.count(KERNEL) == 14
    assert ".remat" not in text
    assert not re.search(r"\[[\d,]*256,256\]", text)
    assert not _placed(text, ROWS_8K, MIXER_WIDTHS), \
        _placed(text, ROWS_8K, MIXER_WIDTHS)
    ma = c.memory_analysis()
    assert ma.argument_size_in_bytes == 3_813_571_072
    assert ma.temp_size_in_bytes <= 10_729_414_144      # PR 31's


def test_train_step_of_the_window_cell(one_chip, compiled):
    """The step of ``smallthinker-21b-a3b.pretrain-16k-moe`` as the
    benchmark builds it — two periods of a global and three window
    layers, every published width, 16 of 64 experts, ONE row of 16,384
    tokens — fits a described v5e with NO compiler rematerialization at
    depth 8 (the issue's first choice; 4 was its fallback), runs the
    global layers on the dense kernels and the window layers on the
    windowed form, the query-major ONE-pass backward in both (since PR
    45: ``flash_(win_)bwd_dkv`` absent), and ``flash_fwd`` /
    ``flash_win_fwd`` once a layer: full remat keeps their outputs (8 x
    119 MB = 954 MB, within ``KEPT_BYTES``)."""
    from benchmark import harness
    from paddle_tpu.models.llama_pretrain import keeps_flash_outputs
    cell = harness.find_cell("smallthinker-21b-a3b.pretrain-16k-moe")
    assert cell.conf["num_hidden_layers"] == 8 and \
        (cell.traffic["batch"], cell.traffic["seq"]) == (1, 16384)
    assert keeps_flash_outputs(1, 16384, 28, 128, jnp.bfloat16, 8)
    c = _cell_step(one_chip, cell.name)
    text = c.as_text()
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_win_fwd",
                   "flash_win_bwd_dq", "grouped_mm", "grouped_mm_dw",
                   "moe_sum_pairs", "rope"):
        assert kernel in text, kernel
    assert "bwd_dkv" not in text
    # four runs of layers (global, window x 3, global, window x 3), each a
    # forward loop and a backward loop.  A layer forward: flash 1 + the
    # routed path on each of its two bounds, 2 grouped products + the
    # token side's sum; backward: the recompute's gate | up product on
    # each bound (the routed path's backward reads that product alone;
    # attention's outputs are kept), flash's ONE backward kernel, and the
    # routed backward on each bound, 2 products + 2 dw + the sum; a window
    # layer rotates q and k: 2 rope kernels forward, 2 recomputed, 2
    # backward
    per_run = 1 + 2 * 3 + 2 * 1 + 1 + 2 * 5
    assert text.count(KERNEL) == 4 * per_run + 2 * 6 == 92
    assert len(re.findall(r" conditional\(", text)) == 4 * 3
    for rows in (53248, 102400):
        assert f"bf16[{rows},2560]" in text
    # full remat keeps the routing (PR 46): the sorts of the four runs'
    # routers and plans are in the forward loops alone, and no recompute
    # pads its gate | up product
    assert _routing_sorts(text) == (4 * 3, 0)
    assert not _padded_from(text, 53248, 102400, 1536)
    assert ".remat" not in text
    # no bf16 copy of an expert stack
    assert not re.search(r"bf16\[(\d+,)?16,2560,1536\]", text)
    # and no fp32 copy of any part of one (PR 51): each kind's layers lie
    # in TWO runs, and the grouped products read a layer's panels out of
    # the kind's whole ``[6 | 2, 16, 2560, 1536]`` / ``[6 | 2, 16, 768,
    # 2560]`` at the layer's index in it.  Until then: a run's slice of
    # each leaf, written once a step and held through it (four fusions
    # of two results, 8 x 377 MB), and a layer's two leaves before the
    # kernels of every loop (eight ``dynamic-slice`` fusions)
    assert not _experts_placed(text, 16, 2560, 768, layers=6)
    ma = c.memory_analysis()
    assert ma.argument_size_in_bytes == 4_484_826_624
    # 12,977,658,368 B with the two-kernel backward (PR 44), 12,977,271,296
    # with the one pass (PR 45): the delta arrays are gone, the sums live
    # in VMEM; + 52,790,272 with the eight layers' routing kept (PR 46):
    # 13,030,061,568 until the experts were read where they lie (PR 51)
    assert ma.temp_size_in_bytes <= 11_337_099_776


# sha256 of the dense cell's optimized step at depth 18 with the debug
# locations out (op metadata, the kernels' serialized bodies, which
# carry source paths, and the tables of files and frames): PR 35's — the
# kernels' ``cost_estimate`` is in the custom calls' backend config, and
# with it XLA places other arrays in its fast memory space (PERF.md §6;
# PR 31's was 96a31f47...047cd5)
DENSE_STEP_DIGEST = \
    "c1884670364c4b0226b6deb2a9d010fae7e0cfd3503d727ea5d831a5735a5577"


def test_dense_cell_step_is_the_recorded_program(one_chip, compiled):
    """``internlm2-1.8b.pretrain-2k`` runs no line of the state-space
    modules: its optimized HLO is, debug locations apart, the text whose
    digest is recorded above.  A PR that MEANS to change the dense
    cell's program records the new digest, and says so in PERF.md."""
    import hashlib
    text = _cell_step(one_chip, "internlm2-1.8b.pretrain-2k").as_text()
    assert text.count(KERNEL) == 9 and ".remat" not in text
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r'"body":"[^"]*"', '"body":""', text)
    text = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(?:.*\n)*?\n", "", text, flags=re.M)
    assert hashlib.sha256(text.encode()).hexdigest() == DENSE_STEP_DIGEST
