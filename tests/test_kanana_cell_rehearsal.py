"""The family ``kanana_mla_moe`` through the benchmark's own run of a
training cell, on the CPU at toy size: ``train_cell.run`` — the feed,
the REAL ``make_train_step`` in bf16 over the kinds ``mla_dense`` /
``mla_moe`` on ONE residual stream with a direct query, the plain
reference, the checks, the per-layer readers — on a COPY of
``benchmark/`` with the rehearsal's patches (``rehearse.patch_for_cpu``:
counts and verdicts, never a time).  The family is files: nothing under
``benchmark/`` is edited to run it.  And the entered cell's arithmetic:
the cut's parameter counts, the needed work, the catalog row key by key.
"""

import json
import os

import pytest

import _cell_rehearsal

CELL = "kanana-2-30b-a3b.pretrain-16k-mla-moe"


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """Sound, then broken underneath: after every step the expert
    layers' direct query projections (nope columns) are 5 % larger."""
    return _cell_rehearsal.rehearse(
        tmp_path_factory, "kanana", "config_kanana.json",
        "train_job_kanana.json", seed=2**31 + 56,
        drifts=("mla_moe", "w_q_nope"))


def test_the_real_step_is_judged_correct_by_the_family_s_reference(rehearsed):
    sound = rehearsed["sound"]
    assert sound["rc"] == 0 and sound["correct"] is True
    assert sound["attempted"] >= 1 and sound["failed"] == 0


def test_a_step_broken_underneath_is_judged_not_correct(rehearsed):
    broken = rehearsed["broken"]
    assert broken["rc"] == 0 and broken["correct"] is False


def test_the_family_s_names_and_costs_are_the_ones_read(rehearsed):
    # one stream: no ``hc_pre`` / ``hc_post`` among the family's names
    assert rehearsed["scopes_added"] == [
        "mla_q", "mla_kv", "moe_route", "moe_dispatch", "moe_experts",
        "moe_combine", "moe_shared"]
    assert rehearsed["kernels_added"] == ["grouped_mm", "grouped_mm_dw",
                                          "moe_sum_pairs"]
    kinds = rehearsed["kinds"]
    assert len(kinds) == 3 and kinds[1] == kinds[2] != kinds[0]
    dense, moe = kinds[0], kinds[1]
    c, expert = 128, 3 * 128 * 128
    # the direct query 128 x 2 x 192, kv_a 128 x 128, kv_b 64 x 2 x 256,
    # wo 256 x 128
    attn = c * 384 + c * 128 + 64 * 512 + 256 * c
    assert dense[:2] == [attn + 3 * c * 256] * 2
    # a token multiplies the EXPECTED share of the held experts (top-3 of
    # 8, 2 held: three quarters of an expert) and the two shared experts
    # whole; the layer holds both; the router is the published 8 wide
    assert moe[0] == attn + c * 8 + 2 * expert + 3 * expert // 4
    assert moe[1] - moe[0] == 2 * expert - 3 * expert // 4
    # two norms and kv_norm; attn_width: heads x (128 + 64 + 128) / 2;
    # the cache: latent + rope
    assert dense[2:] == moe[2:] == [2 * c + 64, 2 * 160, 64 + 64, 0]
    # untied: the table and the head
    assert rehearsed["total_params"] == sum(
        k[1] + k[2] for k in kinds) + 2 * 384 * c + c


def test_the_mixer_s_reader_finds_what_it_reads(rehearsed):
    """On the CPU a trace holds no device op, so a reader of device time
    finds nothing and says so (None); the line leaves the metric out, as
    it does on a program without latent attention."""
    assert "mla_mixer_pct.train" not in rehearsed["metrics"]
    assert "input_wait_pct.train" in rehearsed["metrics"]


def test_the_entered_cell_s_costs_are_the_issue_s_arithmetic():
    """The cut's parameter counts and needed work, from the
    configuration's own keys (ISSUE 56's section 3)."""
    from benchmark import harness, kernel_costs, kernel_costs_kernels
    cell = harness.find_cell(CELL)
    conf, fam, job = cell.conf, cell.family, cell.traffic
    assert fam.attention_params(conf) == 2048 * 6144 + 2048 * 576 \
        + 512 * 32 * 256 + 4096 * 2048 == 26_345_472
    assert fam.expert_params(conf) == 4_718_592
    dense, moe = (kernel_costs.block_costs(conf, k)
                  for k in ("mla_dense", "mla_moe"))
    whole = lambda k: k.resident_params + k.vector_params
    norms = 2 * 2048 + 512
    assert whole(dense) == 26_345_472 + 3 * 2048 * 6144 + norms \
        == 64_098_816
    held = conf["n_routed_experts"]
    assert whole(moe) == 26_345_472 + 9_437_184 + 262_144 + norms \
        + held * 4_718_592 == 187_044_352
    # THE RUNG: (a) of ISSUE 56's ladder, one stage of the deployment — a
    # dense lead and SIX expert layers at 1 x 16,384; it compiles for a
    # described v5e with no ``.remat`` clone
    # (``tests/test_latent_attention_cell_compile.py``), so (b) 1 + 5 and
    # (c) 1 + 4 were not needed
    assert held == 32 and conf["num_hidden_layers"] == 7 \
        and (job["batch"], job["seq"]) == (1, 16384)
    assert fam.layer_kinds(conf) == ("mla_dense",) + ("mla_moe",) * 6
    assert kernel_costs.layer_costs(conf) == [dense] + [moe] * 6
    table = 2 * 16_032 * 2048 + 2048
    assert table == 65_669_120
    assert kernel_costs.total_params(conf) == \
        64_098_816 + 6 * 187_044_352 + table == 1_252_034_048
    assert 64_098_816 + 5 * 187_044_352 + table == 1_064_989_696    # (b)
    assert 64_098_816 + 4 * 187_044_352 + table == 877_945_344      # (c)
    # a token MULTIPLIES 6 x 32 / 128 of an expert in expectation, and
    # the two shared experts
    assert fam.expected_pairs_per_token(conf) == 1.5
    assert moe.matmul_params == 26_345_472 + 262_144 + 9_437_184 \
        + round(1.5 * 4_718_592)
    assert fam.expert_flops_per_token(conf) == 9 * 2 * 2048 * 768 * 1.5 * 6
    # 192-wide scores, 128-wide values
    assert dense.attn_width == moe.attn_width == 32 * (192 + 128) // 2 \
        == 5120
    pairs = 6 * job["seq"] * 5120 * 7
    assert kernel_costs_kernels.flash_attn_train_flops_per_token(
        conf, job["seq"]) == pairs
    products = 6 * (dense.matmul_params + 6 * moe.matmul_params
                    + 16_032 * 2048)
    assert kernel_costs.train_flops_per_token(conf, job["seq"]) == \
        products + pairs
    # a layer's forward by count (ISSUE 56's section 4): 52.7 MFLOP a
    # token of MLA projections, 167.8 of causal scores, 18.9 of shared
    # experts, 14.2 of routed, 0.5 of router
    per = lambda n: round(2 * n / 1e6, 1)
    assert (per(26_345_472), round(2 * job["seq"] * 5120 / 1e6, 1),
            per(9_437_184), per(1.5 * 4_718_592), per(262_144)) == (
        52.7, 167.8, 18.9, 14.2, 0.5)
    # the share: four chips a layer, the vocabulary in groups of eight,
    # and what the model publishes beside it
    assert conf["published"] == {"num_hidden_layers": 48,
                                 "n_routed_experts": 128,
                                 "vocab_size": 128_256}
    assert conf["n_routed_experts"] * 4 == 128 \
        and conf["vocab_size"] * 8 == 128_256
    assert conf["expert_first"] == 0 and conf["tie_word_embeddings"] is False
    assert sorted(conf["reduced"]) == sorted(conf["published"])
    entry = [c for c in cell.bench["configs"]
             if c["name"] == conf["name"]][0]
    assert entry["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    # the traffic, letter for letter (ISSUE 56's section 4)
    assert {k: job[k] for k in (
        "kind", "batch", "seq", "optimizer", "lr", "weight_decay",
        "remat_policy", "loss_chunks", "loader_workers", "rows",
        "reference_steps", "trace_from_step", "trace_steps")} == dict(
        kind="train_job", batch=1, seq=16384, optimizer="adafactor",
        lr=0.01, weight_decay=0.1, remat_policy="full", loss_chunks=8,
        loader_workers=2, rows=1024, reference_steps=2, trace_from_step=3,
        trace_steps=3)
    # every key of the catalog row's config under the same key, letter
    # for letter; ``reduced`` names the only differences
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    row = [json.loads(l) for l in open(catalog)
           if '"name": "kanana-2-30b-a3b-instruct-2601"' in l] \
        if os.path.exists(catalog) else []
    for key, value in (row[0]["config"].items() if row else ()):
        if key in conf["reduced"]:
            assert conf[key] != value and conf["published"][key] == value
        else:
            assert conf[key] == value, key
    if row:
        assert entry["source"] == conf["source"] == row[0]["source_url"]


def test_the_cell_is_in_the_lists_the_issue_names():
    """``BENCHMARK.json`` by addition: the cell's name at the END of the
    lists of ``train_tok_s_chip``, of the per-layer metrics every
    training cell reports and of the four expert readers — not of
    ``hc_mix_pct.train`` —, and one new metric of its own."""
    from benchmark import harness
    bench = harness.load_json(os.path.join(_cell_rehearsal.REPO,
                                           "BENCHMARK.json"))
    assert bench["workloads"][-1]["name"] == CELL \
        and len(bench["workloads"]) == 7 \
        and all(w["chips"] == 1 for w in bench["workloads"])
    mine = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if m.get("workloads", [None])[-1] == CELL]
    assert mine == [
        "train_tok_s_chip", "train_step_ms.train", "mfu_pct.train",
        "input_wait_pct.train", "device_idle_pct.train",
        "recompute_pct.train", "backward_pct.train",
        "flash_attn_roofline_pct.train", "loss_head_ms.train",
        "optimizer_ms.train", "unscoped_pct.train", "moe_ffn_pct.train",
        "moe_dispatch_ms.train", "moe_experts_roofline_pct.train",
        "kernel_undeclared_pct.train", "flops_declared_per_needed.train",
        "flash_attn_declared_per_needed.train",
        "moe_experts_declared_per_needed.train",
        "setup_step_compile_s.train", "setup_trace_lower_s.train",
        "setup_small_programs_s.train", "setup_cache_miss_pct.train",
        "setup_loader_start_s.train", "mla_mixer_pct.train"]
    assert not [m["name"] for m in bench["per_layer"]
                if CELL in m["workloads"][:-1]]
    new = bench["per_layer"][-1]
    assert new == {"name": "mla_mixer_pct.train", "unit": "%",
                   "better": "lower", "source": "device_trace",
                   "layer": "train step", "moves": "train_tok_s_chip",
                   "workloads": [CELL]}
    assert os.path.exists(os.path.join(
        _cell_rehearsal.BENCH, "layer_metrics", new["name"] + ".py"))
