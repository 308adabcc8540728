"""The family ``xing_mhc_moe`` through the benchmark's own run of a
training cell, on the CPU at toy size: ``train_cell.run`` — the feed,
the REAL ``make_train_step`` in bf16, the plain reference, the checks,
the per-layer readers — on a COPY of ``benchmark/`` with the rehearsal's
patches (``rehearse.patch_for_cpu``: counts and verdicts, never a
time).  The family is files: nothing under ``benchmark/`` is edited to
run it, and nothing is put in ``make_train_step``'s place.
"""

import json
import os

import pytest

import _cell_rehearsal


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """Sound, then broken underneath: after every step the routed
    experts' down projections are 5 % larger."""
    return _cell_rehearsal.rehearse(
        tmp_path_factory, "xing", "config_xing.json", "train_job_xing.json",
        seed=2**31 + 33, drifts=("mla_moe", "we_down"))


def test_the_real_step_is_judged_correct_by_the_family_s_reference(rehearsed):
    sound = rehearsed["sound"]
    assert sound["rc"] == 0 and sound["correct"] is True
    assert sound["attempted"] >= 1 and sound["failed"] == 0


def test_a_step_broken_underneath_is_judged_not_correct(rehearsed):
    broken = rehearsed["broken"]
    assert broken["rc"] == 0 and broken["correct"] is False


def test_the_family_s_names_and_costs_are_the_ones_read(rehearsed):
    assert rehearsed["scopes_added"] == [
        "hc_pre", "hc_post", "mla_q", "mla_kv", "moe_route", "moe_dispatch",
        "moe_experts", "moe_combine", "moe_shared"]
    assert rehearsed["kernels_added"] == ["grouped_mm", "grouped_mm_dw"]
    kinds = rehearsed["kinds"]
    assert len(kinds) == 3 and kinds[1] == kinds[2] != kinds[0]
    dense, moe = kinds[0], kinds[1]
    # a token multiplies the EXPECTED share of the held experts (top-2
    # of 8, 2 held: half an expert), the layer holds both
    expert = 3 * 128 * 128
    assert moe[1] - moe[0] == 2 * expert - expert // 2
    assert dense[0] == dense[1]
    # attn_width: heads x (128 + 64 + 128) / 2; the cache: latent + rope
    assert dense[3:] == moe[3:] == [2 * 160, 128 + 64, 0]


def test_the_entered_cell_s_costs_are_the_issue_s_arithmetic():
    """The cut's parameter counts, from the configuration's own keys."""
    from benchmark import harness, kernel_costs
    cell = harness.find_cell("xing4.0-29b-a4b.pretrain-8k-moe")
    conf, fam = cell.conf, cell.family
    assert fam.attention_params(conf) == 28_409_856
    assert fam.expert_params(conf) == 11_010_048
    dense = kernel_costs.block_costs(conf, "mla_dense")
    moe = kernel_costs.block_costs(conf, "mla_moe")
    mixers = 2 * (4 * 3584 * 24 + 3 + 24)
    assert mixers == 688_182
    vectors = 2 * 3584 + 768 + 512
    assert dense.resident_params + dense.vector_params == \
        28_409_856 + 3 * 3584 * 9216 + mixers + vectors == 128_196_918
    outside = 28_409_856 + mixers + vectors + 3584 * 64 + 11_010_048
    assert outside == 40_345_910
    assert moe.resident_params + moe.vector_params == \
        outside + 8 * 11_010_048 == 128_426_294
    assert moe.matmul_params == moe.resident_params - 8 * 11_010_048 \
        + 11_010_048 // 2
    assert dense.attn_width == moe.attn_width == 32 * (192 + 128) // 2 == 5120
    assert kernel_costs.layer_costs(conf) == [dense] + [moe] * 4
    assert kernel_costs.total_params(conf) == \
        128_196_918 + 4 * 128_426_294 + 2 * 16_384 * 3584 + 3584 \
        == 759_346_190
    assert fam.expected_pairs_per_token(conf) == 0.5
    assert round(kernel_costs.train_flops_per_token(conf, 8192) / 1e9,
                 2) == 3.48
    # the share: eight chips a layer, and what the model publishes beside it
    assert conf["published"] == {
        "num_hidden_layers": 40, "first_k_dense_replace": 2,
        "n_routed_experts": 64, "vocab_size": 131072,
        "num_nextn_predict_layers": 1}
    assert conf["n_routed_experts"] * 8 == 64 and conf["vocab_size"] * 8 \
        == 131072 and conf["expert_first"] == 0
    assert sorted(conf["reduced"]) == sorted(conf["published"])
    # every number of the catalog row's config under the same key
    row = [json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"Xing4.0-29B-A4B"' in l] \
        if os.path.exists(
            "/opt/skills/guides/model-configs/architectures.jsonl") else []
    for key, value in (row[0]["config"].items() if row else ()):
        if key not in conf["reduced"]:
            assert conf[key] == value, key
