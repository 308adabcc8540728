"""The family ``solar_kda_moe`` through the benchmark's own run of a
training cell, on the CPU at toy size: ``train_cell.run`` — the feed,
the REAL ``make_train_step`` in bf16 over the kinds ``kda_moe`` /
``gqa_gated_moe`` (the ``kda_chunk_*`` kernels in the interpreter), the
plain reference with its position-by-position recurrence, the checks,
the per-layer readers — on a COPY of ``benchmark/`` with the rehearsal's
patches (``rehearse.patch_for_cpu``: counts and verdicts, never a time).
The family is files: nothing under ``benchmark/`` is edited to run it.
And the entered cell's arithmetic: the cut's parameter counts, the needed
work, the recurrence's operations and bytes, the catalog row key by key.
"""

import json
import os

import pytest

import _cell_rehearsal


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """Sound, then broken underneath: after every step the delta-rule
    layers' out-projections are 5 % larger."""
    return _cell_rehearsal.rehearse(
        tmp_path_factory, "solar", "config_solar.json",
        "train_job_solar.json", seed=2**31 + 52, drifts=("kda_moe", "wo"))


def test_the_real_step_is_judged_correct_by_the_family_s_reference(rehearsed):
    sound = rehearsed["sound"]
    assert sound["rc"] == 0 and sound["correct"] is True
    assert sound["attempted"] >= 1 and sound["failed"] == 0


def test_a_step_broken_underneath_is_judged_not_correct(rehearsed):
    broken = rehearsed["broken"]
    assert broken["rc"] == 0 and broken["correct"] is False


def test_the_family_s_names_and_costs_are_the_ones_read(rehearsed):
    assert rehearsed["scopes_added"] == [
        "kda_in_proj", "kda_conv", "kda_gates", "kda_chunk", "kda_out_gate",
        "kda_out_proj", "attn_gate", "moe_route", "moe_dispatch",
        "moe_experts", "moe_combine", "moe_shared"]
    assert rehearsed["kernels_added"] == [
        "kda_chunk_fwd", "kda_chunk_bwd", "causal_conv_fwd",
        "causal_conv_bwd", "grouped_mm", "grouped_mm_dw", "moe_sum_pairs"]
    kinds = rehearsed["kinds"]
    attn, kda = kinds[0], kinds[1]
    assert kinds == [attn, kda, kda, kda]
    c, expert, d = 128, 3 * 128 * 128, 64
    wide = 2 * d
    # a token multiplies the EXPECTED share of the held experts (top-3 of
    # 8, 2 held: three quarters of an expert) and the shared expert whole;
    # the layer holds both and the shared one; the router is the
    # published 8 wide
    assert kda[1] - kda[0] == attn[1] - attn[0] \
        == 2 * expert - 3 * expert // 4
    mixer = 3 * c * wide + 2 * (c * d + d * wide) + c * 2 + wide * c
    assert kda[0] == mixer + c * 8 + expert + 3 * expert // 4
    # taps, A_log, dt_bias and gate_b, o_norm beside the two norms; the
    # recurrence at Q 64: 5 Q K + 6 K^2 a head a position
    assert kda[2:] == [2 * c + 3 * wide * 4 + 2 + 2 * wide + d, 0, 0,
                       2 * (5 * 64 * d + 6 * d * d)]
    assert attn[0] == c * 32 * (3 * 4 + 2 * 2) + c * 8 + expert \
        + 3 * expert // 4
    assert attn[2:] == [2 * c, 4 * 32, 2 * 2 * 32, 0]
    # untied: the table and the head
    assert rehearsed["total_params"] == sum(
        k[1] + k[2] for k in kinds) + 2 * 384 * c + c


def test_the_mixer_s_readers_find_what_they_read(rehearsed):
    """On the CPU a trace holds no device op, so a reader of device time
    finds nothing and says so (None); the line leaves the metric out, as
    it does on a program without the mixer."""
    for name in ("kda_mixer_pct.train", "kda_chunk_roofline_pct.train",
                 "kda_chunk_declared_per_needed.train"):
        assert name not in rehearsed["metrics"]
    assert "input_wait_pct.train" in rehearsed["metrics"]


def test_the_entered_cell_s_costs_are_the_issue_s_arithmetic():
    """The cut's parameter counts and needed work, from the
    configuration's own keys (ISSUE 52's Motivation, test 2)."""
    from benchmark import harness, kernel_costs, kernel_costs_kernels
    cell = harness.find_cell("solar-open2-250b.pretrain-kda-moe")
    conf, fam, job = cell.conf, cell.family, cell.traffic
    assert fam.kda_params(conf) == 100_663_296 + 2 * 1_572_864 + 262_144 \
        + 33_554_432 == 137_625_600
    assert fam.kda_vectors(conf) == 114_880
    assert fam.attention_params(conf) == 3 * 33_554_432 + 2 * 4_194_304 \
        == 109_051_904
    assert fam.expert_params(conf) == 15_728_640
    attn, kda = (kernel_costs.block_costs(conf, k)
                 for k in ("gqa_gated_moe", "kda_moe"))
    whole = lambda k: k.resident_params + k.vector_params
    every = 1_310_720 + 15_728_640 + 8_192      # router, shared, two norms
    held = conf["n_routed_experts"]
    assert whole(kda) == 137_625_600 + 114_880 + every + held * 15_728_640
    assert whole(attn) == 109_051_904 + every + held * 15_728_640
    period = whole(attn) + 3 * whole(kda)
    assert period == 590_463_552 + 4 * held * 15_728_640
    # THE RUNG: (c) of ISSUE 52's ladder — 8 experts held (the guide's
    # floor; 40 chips a layer) at 1 x 8,192; (a) 10 experts and (b) 8 at
    # 1 x 16,384 do not compile into HBM (the configuration's
    # ``reduced_why``)
    assert held == 8 and (job["batch"], job["seq"]) == (1, 8192)
    assert fam.layer_kinds(conf) == ("gqa_gated_moe",) + ("kda_moe",) * 3
    assert kernel_costs.layer_costs(conf) == [attn, kda, kda, kda]
    assert kernel_costs.total_params(conf) == \
        period + 2 * 24_576 * 4096 + 4096 == 1_295_110_720
    assert 590_463_552 + 40 * 15_728_640 + 201_326_592 + 4096 \
        == 1_420_939_840                          # rung (a)
    # a token MULTIPLIES 8 x 8 / 320 of an expert in expectation, and the
    # shared expert
    assert fam.expected_pairs_per_token(conf) == 0.2
    assert kda.matmul_params == 137_625_600 + 1_310_720 + 15_728_640 \
        + round(0.2 * 15_728_640)
    assert fam.expert_flops_per_token(conf) == 9 * 2 * 4096 * 1280 * 0.2 * 4
    products = 6 * (attn.matmul_params + 3 * kda.matmul_params
                    + 24_576 * 4096)
    pairs = 6 * job["seq"] * 64 * 128
    recurrence = 3 * 3 * fam.kda_chunk_flops_per_token(conf)
    assert kernel_costs.train_flops_per_token(conf, job["seq"]) == \
        products + pairs + recurrence
    assert round(products / 1e9, 2) == 4.22 and \
        round(pairs / 1e9, 2) == 0.40 and round(recurrence / 1e9, 3) == 0.08
    assert kernel_costs_kernels.flash_attn_train_flops_per_token(
        conf, job["seq"]) == pairs
    # THE RECURRENCE'S WORK: 5 Q K + 6 K^2 a head a position at Q 64;
    # q, k, v, o in bf16, the log decay in fp32 and beta forward, seven
    # bf16 and two fp32 arrays and beta twice backward
    assert fam.kda_chunk_flops_per_token(conf) == 64 * (
        5 * 64 * 128 + 6 * 128 * 128) == 8_912_896
    assert fam.kda_chunk_bytes_per_token(conf) == 98_560 + 180_736 \
        == 279_296 and fam.kda_layers(conf) == 3
    from paddle_tpu.ops import kda as op
    assert fam.KDA_CHUNK == op.CHUNK
    # the share: forty chips a layer, and what the model publishes beside it
    assert conf["published"]["num_hidden_layers"] == 48
    assert conf["n_routed_experts"] * 40 \
        == conf["published"]["n_routed_experts"] == 320
    assert conf["vocab_size"] * 8 == conf["published"]["vocab_size"] \
        == 196_608
    assert conf["expert_first"] == 0 and conf["tie_word_embeddings"] is False
    assert conf["gqa_layers"] == conf["published"]["gqa_layers"][:1] == [0]
    assert sorted(conf["reduced"]) == sorted(conf["published"])
    entry = [c for c in cell.bench["configs"]
             if c["name"] == conf["name"]][0]
    assert entry["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"]
    # every key of the catalog row's config under the same key, letter
    # for letter; ``reduced`` names the only differences
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    row = [json.loads(l) for l in open(catalog)
           if '"name": "Solar-Open2-250B"' in l] \
        if os.path.exists(catalog) else []
    for key, value in (row[0]["config"].items() if row else ()):
        if key in conf["reduced"]:
            assert conf[key] != value and conf["published"][key] == value
        else:
            assert conf[key] == value, key
    if row:
        assert entry["source"] == conf["source"] == row[0]["source_url"]
