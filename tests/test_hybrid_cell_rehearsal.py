"""The family ``granite_hybrid`` through the benchmark's own run of a
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
    """Sound, then broken underneath: after every step the state-space
    layers' skip weights are 5 % larger."""
    return _cell_rehearsal.rehearse(
        tmp_path_factory, "granite", "config_granite.json",
        "train_job_granite.json", seed=2**31 + 31, drifts=("mamba", "D"))


def test_the_real_step_is_judged_correct_by_the_family_s_reference(rehearsed):
    sound = rehearsed["sound"]
    assert sound["rc"] == 0 and sound["correct"] is True
    assert sound["attempted"] >= 1 and sound["failed"] == 0


def test_a_step_broken_underneath_is_judged_not_correct(rehearsed):
    broken = rehearsed["broken"]
    assert broken["rc"] == 0 and broken["correct"] is False


def test_the_family_s_names_and_costs_are_the_ones_read(rehearsed):
    assert rehearsed["scopes_added"] == [
        "ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm",
        "ssm_out_proj"]
    assert rehearsed["kernels_added"] == [
        "ssd_scan_fwd", "ssd_scan_bwd", "causal_conv_fwd",
        "causal_conv_bwd"]
    kinds = rehearsed["kinds"]
    assert len(kinds) == 10
    mamba, attention = kinds[0], kinds[5]
    assert all(k == mamba for i, k in enumerate(kinds) if i != 5)
    # attn_width, kv_values, scan_flops
    assert mamba[3:] == [0, 0, 2 * 128 * 16 + 2 * (2 * 128 * 64
                                                   + 4 * 16 * 64)]
    assert attention[3:] == [64, 64, 0]


def test_the_entered_cell_s_costs_are_the_issue_s_arithmetic():
    from benchmark import harness, kernel_costs
    cell = harness.find_cell("granite-4.0-h-micro.pretrain-8k")
    conf = cell.conf
    mamba = kernel_costs.block_costs(conf, "mamba")
    attention = kernel_costs.block_costs(conf, "attention")
    assert mamba.resident_params + mamba.vector_params == 76_182_976
    assert attention.resident_params + attention.vector_params == 60_821_504
    assert kernel_costs.total_params(conf) == 951_991_232
    assert mamba.scan_flops == 4_259_840 and attention.attn_width == 2048
    assert attention.kv_values == 1024 and mamba.attn_width == 0
    assert round(kernel_costs.train_flops_per_token(conf, 8192) / 1e9,
                 2) == 5.93
    # every number of the catalog row's config under the same key
    row = [json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"granite-4.0-h-micro"' in l] \
        if os.path.exists(
            "/opt/skills/guides/model-configs/architectures.jsonl") else []
    for key, value in (row[0]["config"].items() if row else ()):
        if key != "num_hidden_layers":
            assert conf[key] == value, key
