"""The family ``lfm2_conv_moe`` through the benchmark's own run of a
training cell, on the CPU at toy size: ``train_cell.run`` — the feed,
the REAL ``make_train_step`` in bf16 over the kinds ``conv_dense`` /
``conv_moe`` / ``gqa_qknorm_moe``, the plain reference, the checks, the
per-layer readers — on a COPY of ``benchmark/`` with the rehearsal's
patches (``rehearse.patch_for_cpu``: counts and verdicts, never a time).
The family is files: nothing under ``benchmark/`` is edited to run it.
And the entered cell's arithmetic: the cut's parameter counts, the needed
work, the kernel's bytes, the catalog row key by key.
"""

import json
import os

import pytest

import _cell_rehearsal


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """Sound, then broken underneath: after every step the expert
    convolution layers' out-projections are 5 % larger."""
    return _cell_rehearsal.rehearse(
        tmp_path_factory, "lfm2", "config_lfm2.json", "train_job_lfm2.json",
        seed=2**31 + 48, drifts=("conv_moe", "w_out"))


def test_the_real_step_is_judged_correct_by_the_family_s_reference(rehearsed):
    sound = rehearsed["sound"]
    assert sound["rc"] == 0 and sound["correct"] is True
    assert sound["attempted"] >= 1 and sound["failed"] == 0


def test_a_step_broken_underneath_is_judged_not_correct(rehearsed):
    broken = rehearsed["broken"]
    assert broken["rc"] == 0 and broken["correct"] is False


def test_the_family_s_names_and_costs_are_the_ones_read(rehearsed):
    assert rehearsed["scopes_added"] == [
        "conv_in_proj", "short_conv", "conv_out_proj", "qk_norm",
        "moe_route", "moe_dispatch", "moe_experts", "moe_combine"]
    assert rehearsed["kernels_added"] == [
        "short_conv_fwd", "short_conv_bwd", "grouped_mm", "grouped_mm_dw",
        "moe_sum_pairs"]
    kinds = rehearsed["kinds"]
    lead, attn, conv = kinds[0], kinds[1], kinds[2]
    assert kinds == [lead, attn, conv, conv, conv]
    c, expert = 128, 3 * 128 * 128
    operator = 4 * c * c            # [C, 3 C] and [C, C]
    # every token passes through the lead's every matrix; the operator's
    # middle is 2 K + 1 = 7 operations a channel and no matrix's
    assert lead == [operator + 3 * c * 256] * 2 + [2 * c + 3 * c, 0, 0, 7 * c]
    # a token multiplies the EXPECTED share of the held experts (top-3
    # of 8, 2 held: three quarters of an expert), the layer holds both;
    # the router and its bias are the published 8 wide
    assert conv[1] - conv[0] == attn[1] - attn[0] \
        == 2 * expert - 3 * expert // 4
    assert conv[0] == operator + c * 8 + 3 * expert // 4
    assert conv[2:] == [2 * c + 3 * c + 8, 0, 0, 7 * c]
    assert attn[0] == 2 * c * 32 * (4 + 2) + c * 8 + 3 * expert // 4
    assert attn[2:] == [2 * c + 2 * 32 + 8, 4 * 32, 2 * 2 * 32, 0]
    # one tied table
    assert rehearsed["total_params"] == sum(
        k[1] + k[2] for k in kinds) + 384 * c + c


def test_the_operator_s_readers_find_what_they_read(rehearsed):
    """On the CPU a trace holds no device op, so a reader of device time
    finds nothing and says so (None); the line leaves the metric out, as
    it does on a program without the operator."""
    for name in ("short_conv_mixer_pct.train",
                 "short_conv_roofline_pct.train",
                 "short_conv_bytes_declared_per_needed.train"):
        assert name not in rehearsed["metrics"]
    assert "input_wait_pct.train" in rehearsed["metrics"]


def test_the_entered_cell_s_costs_are_the_issue_s_arithmetic():
    """The cut's parameter counts and needed work, from the
    configuration's own keys."""
    from benchmark import harness, kernel_costs, kernel_costs_kernels
    cell = harness.find_cell("lfm2-24b-a2b.pretrain-8k-conv-moe")
    conf, fam, job = cell.conf, cell.family, cell.traffic
    assert fam.conv_params(conf) + 2048 * 3 == 16_783_360
    assert fam.attention_params(conf) + 2 * 64 == 10_485_888
    assert fam.expert_params(conf) == 9_437_184
    lead, attn, conv = (kernel_costs.block_costs(conf, k) for k in (
        "conv_dense", "gqa_qknorm_moe", "conv_moe"))
    whole = lambda k: k.resident_params + k.vector_params
    assert whole(lead) == 16_783_360 + 4_096 + 72_351_744 == 89_139_200
    assert whole(conv) == 16_783_360 + 4_096 + 131_136 \
        + 16 * 9_437_184 == 167_913_536
    assert whole(attn) == 10_485_888 + 4_096 + 131_136 \
        + 16 * 9_437_184 == 161_616_064
    period = whole(attn) + 3 * whole(conv)
    assert period == 665_356_672
    # THE DEPTH: ISSUE 48's fallback, the dense lead and ONE period (its
    # first choice, two, is 1,453,409,024 parameters and does not compile
    # into HBM: the configuration's ``reduced_why``)
    assert fam.layer_kinds(conf) == ("conv_dense", "gqa_qknorm_moe") \
        + ("conv_moe",) * 3
    assert kernel_costs.layer_costs(conf) == [lead, attn, conv, conv, conv]
    assert kernel_costs.total_params(conf) == \
        89_139_200 + period + 16_384 * 2048 + 2048 == 788_052_352
    assert 89_139_200 + 2 * period + 16_384 * 2048 + 2048 == 1_453_409_024
    # a token MULTIPLIES 4 x 16 / 64 of an expert in expectation
    assert fam.expected_pairs_per_token(conf) == 1.0
    assert conv.matmul_params == 4 * 2048 ** 2 + 2048 * 64 + 9_437_184
    assert fam.expert_flops_per_token(conf) == 9 * 2 * 2048 * 1536 * 4
    assert (job["batch"], job["seq"]) == (2, 8192)
    products = 6 * (lead.matmul_params + attn.matmul_params
                    + 3 * conv.matmul_params + 16_384 * 2048)
    pairs = 6 * 8192 * 32 * 64
    middle = 3 * 4 * 7 * 2048
    assert kernel_costs.train_flops_per_token(conf, job["seq"]) == \
        products + pairs + middle
    assert round(products / 1e6, 1) == 1330.6 and \
        round(pairs / 1e6, 1) == 100.7 and round(middle / 1e6, 2) == 0.17
    assert round((products + pairs + middle) * 16_384 / 1e12, 2) == 23.45
    assert kernel_costs_kernels.flash_attn_train_flops_per_token(
        conf, job["seq"]) == pairs
    # THE KERNEL'S BYTES: B, Cg, X in and the result out, then the four in
    # and dB, dCg, dX out, bf16, a token a convolution layer
    assert fam.short_conv_bytes_per_token(conf) == (4 + 7) * 2048 * 2 \
        == 45_056 and fam.conv_layers(conf) == 4
    # the share: four chips a layer, and what the model publishes beside it
    assert conf["published"]["num_hidden_layers"] == 40 and \
        conf["published"]["num_dense_layers"] == 2
    assert conf["num_experts"] * 4 == conf["published"]["num_experts"] == 64
    assert conf["vocab_size"] * 4 == conf["published"]["vocab_size"] == 65_536
    assert conf["expert_first"] == 0 and conf["tie_word_embeddings"] is True
    # the published layers 1 .. : the list less its first dense layer
    assert conf["layer_types"] == conf["published"]["layer_types"][1:]
    assert conf["num_dense_layers"] == 1
    assert sorted(conf["reduced"]) == sorted(conf["published"])
    entry = [c for c in cell.bench["configs"]
             if c["name"] == conf["name"]][0]
    assert entry["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"]
    # every key of the catalog row's config under the same key, letter
    # for letter; ``reduced`` names the only differences
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    row = [json.loads(l) for l in open(catalog)
           if '"name": "LFM2-24B-A2B"' in l] \
        if os.path.exists(catalog) else []
    for key, value in (row[0]["config"].items() if row else ()):
        if key in conf["reduced"]:
            assert conf[key] != value and conf["published"][key] == value
        else:
            assert conf[key] == value, key
    if row:
        assert entry["source"] == conf["source"] == row[0]["source_url"]
