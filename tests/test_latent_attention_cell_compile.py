"""The plain latent-attention training cell's whole step
(``kanana-2-30b-a3b.pretrain-16k-mla-moe``), as the benchmark builds it,
compiled for a DESCRIBED TPU v5e with no chip attached: rung (a) of
ISSUE 56's ladder — one stage of the deployment, a dense lead and six
expert layers — holds, so no rung is refused and none is held by a
``slow`` case (fixtures and rules: ``tests/_tpu_compile.py``; the other
expert cells' steps: ``tests/test_expert_cells_compile.py``,
``tests/test_delta_rule_cell_compile.py``).
"""

import collections
import re

from _tpu_compile import (KERNEL, _cell_step, _experts_placed,  # noqa: F401
                          _flash_module, _padded_from, _routing_sorts,
                          compiled, one_chip, topo)


def test_train_step_of_the_plain_latent_attention_cell(one_chip, compiled):
    """The step of ``kanana-2-30b-a3b.pretrain-16k-mla-moe`` as the
    benchmark builds it — a dense lead and six expert layers on ONE
    residual stream, every published width, a direct query, 32 of 128
    experts beside the two shared ones, 1 x 16,384 tokens — fits a
    described v5e with NO compiler rematerialization, runs attention as
    the split-score flash kernels (a block pair visited once a pass) and
    the routed path as the grouped products, names no mixer, and routes
    once a layer a step."""
    from benchmark import harness
    cell = harness.find_cell("kanana-2-30b-a3b.pretrain-16k-mla-moe")
    assert cell.conf["num_hidden_layers"] == 7 and \
        cell.conf["n_routed_experts"] == 32 and \
        (cell.traffic["batch"], cell.traffic["seq"]) == (1, 16384)
    c = _cell_step(one_chip, cell.name)
    text = c.as_text()
    calls = collections.Counter(re.findall(
        r'custom_call_target="tpu_custom_call".*?/([a-z_0-9]+)/pallas_call',
        text))
    # either kind's loop: ``flash_fwd`` once — full remat keeps its
    # outputs, 7 x 136.3 MB = 954 MB within ``KEPT_BYTES`` — and the
    # split form's backward in ONE key-major pass (PR 57): at S 16,384 a
    # head's fp32 dQ, 8 MiB, is past ``flash_attention.ONE_PASS_DQ_BYTES``
    # (the 8k cell's is within it) and its dQ and dQ2, a lane tile each
    # a row, are ``ONE_PASS_DKV_BYTES`` exactly — ``flash_bwd_dq`` absent
    flash = _flash_module()
    assert 16384 * 128 * 4 > flash.ONE_PASS_DQ_BYTES and \
        16384 * (128 + 128) * 4 == flash.ONE_PASS_DKV_BYTES
    assert (calls["flash_fwd"], calls["flash_bwd_dq"],
            calls["flash_bwd_dkv"]) == (2, 0, 2)
    # the routed path ON EACH OF ITS TWO BOUNDS (57,344 rows where the
    # load's tiles fit them — twice the 24,576 pairs top-6 of 128 sends
    # to 32 experts, and a tile an expert — 106,496 otherwise): 2
    # products + the token side's sum forward, the gate | up product
    # recomputed (on one stream nothing reads a sublayer's OUTPUT again:
    # the mixers' ``hc_post`` did, 3 kernels), 2 products + 2 dw + the
    # sum backward
    assert (calls["grouped_mm"], calls["grouped_mm_dw"],
            calls["moe_sum_pairs"]) == (2 * 5, 2 * 2, 2 * 2)
    assert text.count(KERNEL) == sum(calls.values()) == 6 + 2 * 9 - 2
    assert not [k for k in calls if k.startswith("hc_")]
    assert "hc_pre" not in text and "hc_post" not in text
    assert len(re.findall(r" conditional\(", text)) == 3
    for rows in (57344, 106496):
        assert f"bf16[{rows},2048]" in text
    # the routing is kept (PR 46): the router's ``top_k`` and the plan's
    # two sorts in the forward loop alone; the recompute's gate | up
    # product is written where it is kept — no pad to the bound of any
    # load
    assert _routing_sorts(text) == (3, 0)
    assert not _padded_from(text, 57344, 106496, 1536)
    assert ".remat" not in text
    # no copy of a layer's or a run's fp32 experts, ``[32, 2048, 1536]``
    # and ``[32, 768, 2048]`` (604 MB a layer), before the grouped
    # products (PR 51)
    assert not _experts_placed(text, 32, 2048, 768, layers=6)
    ma = c.memory_analysis()
    assert ma.argument_size_in_bytes == 5_015_072_768
    # what the donated parameters' new values take is in this figure
    assert ma.temp_size_in_bytes <= 14_521_268_736
