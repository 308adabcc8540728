"""Percentile, spread, interval and schedule arithmetic; the traffic
generator; the FLOP and byte counts against hand sums."""

import json
import os

import pytest

from benchmark import (harness, kernel_costs, kernel_costs_kernels, peaks,
                       stats, traffic)

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


def conf(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_percentile_and_spread():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == 50.5
    assert stats.percentile(v, 95) == pytest.approx(95.05)
    assert stats.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 95)
    # quartiles of 1..7 by statistics.quantiles(n=4): 2 and 6
    assert stats.spread([1, 2, 3, 4, 5, 6, 7]) == pytest.approx(1.0)


def test_interval_union():
    assert stats.interval_union([(0, 1), (0.5, 2), (3, 4)]) == 3.0
    assert stats.interval_union([(0, 5), (1, 2)]) == 5.0
    assert stats.interval_union([]) == 0.0


def test_same_seed_same_requests_other_seed_other_order():
    mix = harness.load_traffic("chat-steady")
    a = traffic.serving_phases(mix, 2**31 + 5, 10.0, 92544)
    b = traffic.serving_phases(mix, 2**31 + 5, 10.0, 92544)
    c = traffic.serving_phases(mix, 77, 10.0, 92544)
    assert a == b
    assert a[1]["requests"] != c[1]["requests"]
    # another seed is the same multiset of sizes and gaps, reordered
    lens = lambda ph: sorted(len(r["prompt"]) for r in ph["requests"])
    outs = lambda ph: sorted(r["max_new_tokens"] for r in ph["requests"])
    assert lens(a[1]) == lens(c[1]) and outs(a[1]) == outs(c[1])
    n = len(a[1]["requests"])
    assert n == round(mix["rate_rps"] * 10.0)
    dues = [r["due"] for r in a[1]["requests"]]
    assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 10.0
    mixp = mix["prompt_len"]
    assert mixp["min"] <= lens(a[1])[0] and lens(a[1])[-1] <= mixp["max"]


def test_backlog_is_all_due_at_the_start():
    mix = harness.load_traffic("longprompt-batch")
    ph = traffic.serving_phases(mix, 3, 4.0, 92544)[1]
    assert ph["cut_at_end"] and all(r["due"] == 0.0
                                    for r in ph["requests"])
    assert len(ph["requests"]) == round(mix["backlog_per_s"] * 4.0)


def test_shape_sweep_covers_every_page_count():
    mix = harness.load_traffic("chat-steady")
    sweep = traffic.shape_sweep(mix, 92544, 1)
    singles = [w for w in sweep if len(w) == 1]
    assert [len(w[0]["prompt"]) // 64 for w in singles] == \
        list(range(1, 33))
    # then 2, 3 and 4 of the longest prompt: buckets 4096 and 8192
    assert [len(w) for w in sweep if len(w) > 1] == [2, 3, 4]
    assert all(len(r["prompt"]) == 2048 for w in sweep[32:] for r in w)


def test_counts_against_hand_sums():
    i = conf("internlm2-1.8b")
    # q,o: 2048x2048 each; k,v: 2048x1024 each; gate,up,down: 2048x8192
    assert kernel_costs.block_costs(i).resident_params == \
        2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192 == 62914560
    assert kernel_costs.head_params(i) == 2048 * 92544 == 189530112
    assert kernel_costs.total_params(i) == \
        24 * (62914560 + 4096) + 2 * 189530112 + 2048 == 1889110016
    assert kernel_costs.kv_bytes_per_token(i) == 24 * 2 * 8 * 128 * 2 \
        == 96 * 1024
    m = conf("mistral-7b-v0.3")
    assert kernel_costs.block_costs(m).resident_params == \
        2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 == 218103808
    assert kernel_costs.total_params(m) == \
        32 * (218103808 + 8192) + 2 * 4096 * 32768 + 4096 == 7248023552
    assert kernel_costs.kv_bytes_per_token(m, chips=4) == 32 * 1024
    assert kernel_costs.weight_bytes_per_chip(m, chips=4) == \
        (32 * 218103808 + 4096 * 32768) * 2 / 4
    t = conf("internlm2-1.8b-train")
    L = t["num_hidden_layers"]
    assert kernel_costs.train_flops_per_token(t, 2048) == \
        6.0 * (L * 62914560 + 189530112) + 6.0 * L * 2048 * 2048
    # one 1000-token prompt: 2 FLOPs a matrix parameter a token, causal
    # QK^T + PV = 2 * S^2 * hidden a layer, the head once
    assert kernel_costs.prefill_flops(i, [1000]) == \
        2.0 * 24 * 62914560 * 1000 + 24 * 2.0 * 1000 * 1000 * 2048 \
        + 2.0 * 189530112
    assert kernel_costs.decode_step_bytes(i, 1000.0) == \
        (24 * 62914560 + 189530112) * 2 + 1000 * 96 * 1024


def test_the_dense_cells_counts_to_the_unit():
    """``internlm2-1.8b.pretrain-2k`` as every PR has read it: 8.39
    GFLOP a token, 1.51 B parameters; summed a layer at a time now, a
    family without kinds is eighteen layers of one kind."""
    t = conf("internlm2-1.8b-train")
    layers = kernel_costs.layer_costs(t)
    assert len(layers) == 18 and set(layers) == {kernel_costs.BlockCosts(
        62914560, 62914560, 4096, 2048, 2048, 0)}
    assert kernel_costs.over_layers(t, "matmul_params") == 1132462080
    assert kernel_costs.train_flops_per_token(t, 2048) == 8384937984.0
    assert kernel_costs_kernels.flash_attn_train_flops_per_token(
        t, 2048) == 452984832.0
    assert kernel_costs.total_params(t) == 1511598080
    assert kernel_costs.weight_bytes_per_chip(t) == \
        (1132462080 + 189530112) * 2
    assert kernel_costs.kv_bytes_per_token(t) == 18 * 2048 * 2
    # a tied table is held once; the head's product is still made
    tied = dict(t, tie_word_embeddings=True)
    assert kernel_costs.total_params(tied) == 1511598080 - 189530112
    assert kernel_costs.train_flops_per_token(tied, 2048) == 8384937984.0


def test_unknown_device_kind_raises():
    assert peaks.chip_peaks("TPU v5 lite").flops == 197e12
    assert peaks.chip_peaks("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(KeyError):
        peaks.chip_peaks("cpu")
