"""The family ``smallthinker_moe`` through the benchmark's own run of a
training cell, on the CPU at toy size: ``train_cell.run`` — the feed,
the REAL ``make_train_step`` in bf16 over the kinds ``gqa_moe_global`` /
``gqa_moe_window``, the plain reference, the checks, the per-layer
readers — on a COPY of ``benchmark/`` with the rehearsal's patches
(``rehearse.patch_for_cpu``: counts and verdicts, never a time).  The
family is files: nothing under ``benchmark/`` is edited to run it.  And
the entered cell's arithmetic: the cut's parameter counts, the needed
work that counts the window, the catalog row key by key.
"""

import json
import os

import pytest

import _cell_rehearsal


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """Sound, then broken underneath: after every step the window
    layers' routed down projections are 5 % larger."""
    return _cell_rehearsal.rehearse(
        tmp_path_factory, "smallthinker", "config_smallthinker.json",
        "train_job_smallthinker.json", seed=2**31 + 33,
        drifts=("gqa_moe_window", "we_down"))


def test_the_real_step_is_judged_correct_by_the_family_s_reference(rehearsed):
    sound = rehearsed["sound"]
    assert sound["rc"] == 0 and sound["correct"] is True
    assert sound["attempted"] >= 1 and sound["failed"] == 0


def test_a_step_broken_underneath_is_judged_not_correct(rehearsed):
    broken = rehearsed["broken"]
    assert broken["rc"] == 0 and broken["correct"] is False


def test_the_family_s_names_and_costs_are_the_ones_read(rehearsed):
    assert rehearsed["scopes_added"] == [
        "moe_route", "moe_dispatch", "moe_experts", "moe_combine"]
    assert rehearsed["kernels_added"] == [
        "grouped_mm", "grouped_mm_dw", "moe_sum_pairs", "flash_win_fwd",
        "flash_win_bwd_dq", "flash_win_bwd_dkv"]
    kinds = rehearsed["kinds"]
    assert len(kinds) == 5
    glob, win = kinds[0], kinds[1]
    assert kinds == [glob, win, win, win, glob]
    # a token multiplies the EXPECTED share of the held experts (top-3
    # of 8, 2 held: three quarters of an expert), the layer holds both
    expert = 3 * 128 * 128
    assert glob[1] - glob[0] == 2 * expert - 3 * expert // 4
    # a global layer attends at heads x head_dim; a window layer states
    # no width and its attention as FLOPs a token: 4 x 512 x (64 - 64^2 /
    # (2 x 256)) at the toy's row, which is its max_position_embeddings
    assert glob[3:] == [4 * 128, 2 * 2 * 128, 0]
    assert win[3:] == [0, 2 * 2 * 128, 4 * 512 * 56]
    assert win[:3] == glob[:3]


def test_the_window_s_readers_find_what_they_read(rehearsed):
    """On the CPU a trace holds no device op, so a reader of device time
    finds nothing and says so (None); the line leaves the metric out, as
    it does on a program without the windowed form."""
    assert "flash_win_roofline_pct.train" not in rehearsed["metrics"]
    assert "input_wait_pct.train" in rehearsed["metrics"]


def test_the_entered_cell_s_costs_are_the_issue_s_arithmetic():
    """The cut's parameter counts and needed work, from the
    configuration's own keys."""
    from benchmark import harness, kernel_costs, kernel_costs_kernels
    cell = harness.find_cell("smallthinker-21b-a3b.pretrain-16k-moe")
    conf, fam, job = cell.conf, cell.family, cell.traffic
    assert fam.attention_params(conf) == 20_971_520
    assert fam.expert_params(conf) == 5_898_240
    glob = kernel_costs.block_costs(conf, "gqa_moe_global")
    win = kernel_costs.block_costs(conf, "gqa_moe_window")
    for kind in (glob, win):
        assert kind.resident_params + kind.vector_params == \
            20_971_520 + 2560 * 64 + 5_120 + 16 * 5_898_240 == 115_512_320
        assert kind.matmul_params == 20_971_520 + 163_840 \
            + 3 * 5_898_240 // 2 == 29_982_720
    assert kernel_costs.layer_costs(conf) == [glob, win, win, win] * 2
    assert kernel_costs.total_params(conf) == \
        8 * 115_512_320 + 2 * 37_984 * 2560 + 2560 == 1_118_579_200
    assert fam.expected_pairs_per_token(conf) == 1.5
    assert fam.expert_flops_per_token(conf) == \
        9 * 2 * 2560 * 768 * 1.5 * 8
    # THE NEEDED WORK COUNTS THE WINDOW: the row is the published
    # context, four windows long; a window layer's query sees 3,584 keys
    # of it on average, a global layer's 8,192
    assert job["seq"] == conf["max_position_embeddings"] == 16_384 \
        == 4 * conf["sliding_window_size"] and job["batch"] == 1
    assert fam.window_keys_per_query(conf, job["seq"]) == 3584.0
    assert (glob.attn_width, glob.scan_flops) == (3584, 0)
    assert (win.attn_width, win.scan_flops) == (0, 4 * 3584 * 3584)
    products = 6 * (8 * 29_982_720 + 37_984 * 2560)
    globals_ = 2 * 6 * 16_384 * 3584
    windows = 6 * 3 * 4 * 3584 * 3584
    assert kernel_costs.train_flops_per_token(conf, job["seq"]) == \
        products + globals_ + windows
    assert round(products / 1e6, 1) == 2022.6 and \
        round(globals_ / 1e6, 1) == 704.6 and \
        round(windows / 1e6, 1) == 924.8
    assert round((products + globals_ + windows) * 16_384 / 1e12, 1) == 59.8
    # the dense kernels' readers count the GLOBAL layers' work only, the
    # window kernels' readers the window layers'
    assert kernel_costs_kernels.flash_attn_train_flops_per_token(
        conf, job["seq"]) == globals_
    assert fam.window_attn_train_flops_per_token(conf, job["seq"]) \
        == windows
    # a window kernel that visited every causal pair would declare 528 /
    # 224 of the needed pairs; the windowed form's 252
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    assert fa._pairs(16_384, 512, True) == 528
    assert fa._pairs(16_384, 512, True, 4096) == 252
    assert 16_384 * 3584 / 512 ** 2 == 224
    # the share: four chips a layer, and what the model publishes beside it
    assert conf["published"] == {
        "num_hidden_layers": 52, "moe_num_primary_experts": 64,
        "vocab_size": 151936}
    assert conf["moe_num_primary_experts"] * 4 == 64 and \
        conf["vocab_size"] * 4 == 151936 and conf["expert_first"] == 0
    assert sorted(conf["reduced"]) == sorted(conf["published"])
    entry = [c for c in cell.bench["configs"]
             if c["name"] == conf["name"]][0]
    assert sorted(entry["reduced"]) == sorted(conf["reduced"])
    # every key of the catalog row's config under the same key, letter
    # for letter; ``reduced`` names the only differences
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    row = [json.loads(l) for l in open(catalog)
           if '"SmallThinker-21BA3B-Instruct"' in l] \
        if os.path.exists(catalog) else []
    for key, value in (row[0]["config"].items() if row else ()):
        if key in conf["reduced"]:
            assert conf[key] != value and conf["published"][key] == value
        else:
            assert conf[key] == value, key
    if row:
        assert entry["source"] == conf["source"] == row[0]["source_url"]


def test_every_why_and_source_fits_its_line():
    """A ``why`` or ``source`` over 200 characters is refused before
    any run (PR 33's second session)."""
    bench = json.load(open(os.path.join(_cell_rehearsal.REPO,
                                        "BENCHMARK.json")))
    for entry in bench["configs"] + bench["workloads"]:
        for key in ("why", "source"):
            assert 1 <= len(entry.get(key, "x")) <= 200, (entry["name"], key)
