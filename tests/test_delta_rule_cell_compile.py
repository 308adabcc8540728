"""The delta-rule training cell's whole step
(``solar-open2-250b.pretrain-kda-moe``), as the benchmark builds it,
compiled for a DESCRIBED TPU v5e with no chip attached: the rung of
ISSUE 52's ladder that holds, by its counts, and — marked ``slow``: a
minute each, ``-m slow -k ladder`` — the two rungs above it, by their
refusals (fixtures and rules: ``tests/_tpu_compile.py``; the other
expert cells' steps: ``tests/test_expert_cells_compile.py``).
"""

import re

import pytest

from _tpu_compile import (KERNEL, _cell_step, _experts_placed,  # noqa: F401
                          _placed, _routing_sorts, compiled, one_chip, topo)


def test_train_step_of_the_delta_rule_cell(one_chip, compiled):
    """The step of ``solar-open2-250b.pretrain-kda-moe`` as the benchmark
    builds it — one period (gated GQA, three Kimi-Delta-Attention layers),
    every published width, 8 of 320 experts beside the shared one, 1 x
    8,192 tokens: rung (c) of ISSUE 52's ladder — fits a described v5e
    with NO compiler rematerialization (rung (a), 10 experts at 1 x
    16,384, is refused at 17.69G of 15.75G and rung (b), 8 experts at 1 x
    16,384, at 16.39G: 5.89G live and 5.65G of fragmentation; PERF.md
    section 4), runs the recurrence as ``kda_chunk_fwd`` /
    ``kda_chunk_bwd`` on the convolution's ``[1, 8192, 24576]`` WHERE IT
    LIES, and routes once a layer a step."""
    from benchmark import harness
    cell = harness.find_cell("solar-open2-250b.pretrain-kda-moe")
    assert cell.conf["num_hidden_layers"] == 4 and \
        cell.conf["n_routed_experts"] == 8 and \
        (cell.traffic["batch"], cell.traffic["seq"]) == (1, 8192)
    c = _cell_step(one_chip, cell.name)
    text = c.as_text()
    call = lambda kernel: len(re.findall(
        rf'custom_call_target="tpu_custom_call".*/{kernel}/pallas_call',
        text))
    # the delta-rule kind's loop: the forward and the backward — full
    # remat keeps ``kda_chunk_fwd``'s o and entering states (3 x 268 MB
    # beside the GQA layer's 136 MB, within ``KEPT_BYTES``: 941,621,248
    # B), so the recompute has none (PR 55; ``(2, 1)`` before)
    assert (call("kda_chunk_fwd"), call("kda_chunk_bwd")) == (1, 1)
    assert (call("causal_conv_fwd"), call("causal_conv_bwd")) == (2, 1)
    # the gated GQA layer: full remat keeps ``flash_fwd``'s outputs, and
    # the backward at 64 / 8 heads of 128, S 8,192 is the query-major one
    # pass; no rotation (``use_rope: false``)
    assert (call("flash_fwd"), call("flash_bwd_dq"), call("flash_bwd_dkv"),
            call("rope")) == (1, 1, 0, 0)
    # a routed kind's loop ON EACH OF ITS TWO BOUNDS (5,376 rows where the
    # load's tiles fit them — twice the 1,639 pairs top-8 of 320 sends to
    # 8 experts, and a tile an expert — 67,584 otherwise)
    assert text.count(KERNEL) == 2 + 3 + 2 + 2 * 2 * 9 == 43
    assert len(re.findall(r" conditional\(", text)) == 2 * 3
    for rows in (5376, 67584):
        assert f"bf16[{rows},4096]" in text
    # the routing is kept (PR 46's rule, for the two new kinds)
    assert _routing_sorts(text) == (2 * 3, 0)
    assert ".remat" not in text
    # no array of the mixer's widths is only sliced, copied, padded or
    # joined between the in-projection, the convolution and the recurrence
    assert not _placed(text, (1, 8192), {24576})
    # nor a layer's fp32 experts, ``[8, 4096, 2560]`` and ``[8, 1280,
    # 4096]`` (503 MB), before the grouped products of either kind's loops
    assert not _experts_placed(text, 8, 4096, 1280, layers=3)
    ma = c.memory_analysis()
    assert ma.argument_size_in_bytes == 5_188_808_704
    # what the donated parameters' new values take is in this figure;
    # 14,612,799,488 with the delta rule's outputs recomputed, +40.8 MB
    # with them kept (PR 55)
    assert ma.temp_size_in_bytes <= 14_653_609_472


@pytest.mark.slow
@pytest.mark.parametrize("experts,used", [(10, "17.69G"), (8, "16.39G")],
                         ids=["a", "b"])
def test_the_ladder_s_upper_rungs_do_not_compile_into_hbm(one_chip, compiled,
                                                          experts, used):
    """Rungs (a) and (b) of ISSUE 52's ladder — 10 experts held (32 chips
    a layer) and 8 (the floor; 40 chips), both at 1 x 16,384 — are REFUSED
    by the compiler for a described v5e, which is why the cell is rung
    (c): ``pytest tests/test_delta_rule_cell_compile.py -m slow``
    (a minute a rung; PERF.md section 4 has the refusals' figures)."""
    with pytest.raises(Exception) as refusal:
        _cell_step(one_chip, "solar-open2-250b.pretrain-kda-moe",
                   conf={"n_routed_experts": experts}, job={"seq": 16384})
    said = str(refusal.value)
    assert "RESOURCE_EXHAUSTED" in said and "memory space hbm" in said
    assert f"Used {used} of 15.75G hbm" in said, said[:600]
