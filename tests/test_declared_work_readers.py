"""The five readers of DECLARED work (``benchmark/declared_work.py``,
``benchmark/layer_metrics/*declared_per_needed.train.py``,
``kernel_undeclared_pct.train.py``) on a small synthetic ``MetaTrace``:
ops with and without ``flops``, a ``while`` that carries its body's
total, a copy that inherits a kernel's op path, XLA's own custom call.
Held here and not under ``benchmark/tests/`` (the driver runs ``tests/``)."""

import importlib.util
import os

import pytest

from benchmark import (declared_work, harness, kernel_costs,
                       kernel_costs_kernels, xplane_meta)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DENSE, HYBRID, EXPERT = ("internlm2-1.8b.pretrain-2k",
                         "granite-4.0-h-micro.pretrain-8k",
                         "xing4.0-29b-a4b.pretrain-8k-moe")
BODY = "jit(step)/jvp(layer_scan)/while/body/closed_call/block/"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(REPO, "benchmark", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def trace(events, steps=1):
    """``events``: (name, start_s, end_s, op path, category, flops,
    bytes) -> a ``MetaTrace`` of one chip with ``steps`` executions of
    ``jit_step`` around them."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    selfs = xplane_meta._self_times([(e[1], e[2]) for e in events])
    ops = [xplane_meta.Op(n, s, e, self_s, path, "", cat, fl, by)
           for (n, s, e, path, cat, fl, by), self_s in zip(events, selfs)]
    span = max(e[2] for e in events) / steps
    modules = [(f"jit_step({i})", i * span, (i + 1) * span)
               for i in range(steps)]
    return xplane_meta.MetaTrace({0: ops}, {0: modules}, [])


def kernel(name, s, e, scope, flops, nbytes):
    return (f"%{name}.1 = custom-call()", s, e,
            f"{BODY}{scope}/{name}/pallas_call", "custom-call", flops, nbytes)


def read_all(monkeypatch, cell_name, mt):
    cell = harness.find_cell(cell_name)
    view = mt.named(*xplane_meta.names_of(cell))
    monkeypatch.setattr(xplane_meta, "of_cell", lambda c, t: view)
    counters = {"tokens_per_step": 16384, "chips": 1}
    names = [m["name"] for m in cell.per_layer()
             if "declared" in m["name"]]
    return cell, {n: reader(n)(object(), counters, {}, cell) for n in names}


def whole_step(flash=3e12, mm=1e14, flash_bytes=2e8):
    """A ``while`` over: a matrix-product fusion, a flash kernel, a copy
    beside it on the kernel's own op path, XLA's own custom call."""
    return [
        ("%while.1 = while()", 0.0, 0.8, "jit(step)/jvp(layer_scan)/while",
         "while", 7e14, 9e9),                   # its body's total: no leaf
        ("%fusion.1 = fusion()", 0.0, 0.4, BODY + "mlp/dot_general",
         "convolution fusion", mm, 1e9),
        kernel("flash_fwd", 0.4, 0.5, "attn", flash, flash_bytes),
        ("%copy.1 = copy()", 0.5, 0.6, BODY + "attn/flash_fwd/pallas_call",
         "data formatting", 0.0, 5e8),
        ('%custom-call.1 = custom-call(), custom_call_target='
         '"ConcatBitcast"', 0.6, 0.7, "jit(step)/jvp(layer_scan)/while",
         "custom-call", 0.0, 0.0),
    ]


def test_a_container_is_not_a_leaf_and_each_execution_counts_once():
    mt = trace(whole_step() + [kernel("rope", 0.9, 1.0, "rope", 5.0, 7.0),
                               kernel("rope", 1.0, 1.1, "rope", 5.0, 7.0)])
    leaves = declared_work.leaves(mt)
    assert [op.category for op in leaves].count("while") == 0
    assert len(leaves) == 6
    assert declared_work.declared(mt, "flops") == 1e14 + 3e12 + 10.0
    # the copy on flash_fwd's op path is XLA's, not the kernel
    assert declared_work.declared(mt, "bytes_accessed",
                                  ("flash_fwd",)) == 2e8
    assert declared_work.declared(mt, "bytes_accessed", ("rope",)) == 14.0
    assert [declared_work.is_kernel(op) for op in leaves] == \
        [False, True, False, False, True, True]


def test_undeclared_share_counts_only_silent_kernels(monkeypatch):
    silent = kernel("rope", 0.8, 1.0, "rope", 0.0, 0.0)
    _, got = read_all(monkeypatch, DENSE, trace(whole_step() + [silent]))
    # 0.2 s of 1.0 s of self time (the while's own share is 0.1)
    assert got["kernel_undeclared_pct.train"] == pytest.approx(20.0)
    # bytes alone are a declaration; XLA's ConcatBitcast (0 / 0) is no
    # kernel and never counts
    stated = kernel("rope", 0.8, 1.0, "rope", 0.0, 64.0)
    _, got = read_all(monkeypatch, DENSE, trace(whole_step() + [stated]))
    assert got["kernel_undeclared_pct.train"] == 0.0
    # no kernel at all: nothing to read
    _, got = read_all(monkeypatch, DENSE, trace(whole_step()[:2]))
    assert got["kernel_undeclared_pct.train"] is None


@pytest.mark.parametrize("steps", [1, 3])
def test_the_two_ratios_every_cell_reads(monkeypatch, steps):
    cell, got = read_all(monkeypatch, DENSE, trace(whole_step(), steps))
    seq = cell.traffic["seq"]
    need = kernel_costs.train_flops_per_token(cell.conf, seq) * 16384 * steps
    assert got["flops_declared_per_needed.train"] == \
        pytest.approx((1e14 + 3e12) / need)
    attn = kernel_costs_kernels.flash_attn_train_flops_per_token(
        cell.conf, seq) * 16384 * steps
    assert got["flash_attn_declared_per_needed.train"] == \
        pytest.approx(3e12 / attn)
    # the dense cell reads neither family's ratio
    assert set(got) == {"kernel_undeclared_pct.train",
                        "flops_declared_per_needed.train",
                        "flash_attn_declared_per_needed.train"}


def test_a_parent_s_trace_reads_nothing_for_its_kernels(monkeypatch):
    """Kernels that declare nothing (the parent of PR 35): XLA's own
    figure still gives the whole-step ratio, the kernels' ratio is left
    out, and the undeclared share says why."""
    silent = whole_step(0.0, flash_bytes=0.0)
    _, got = read_all(monkeypatch, DENSE, trace(silent))
    assert got["flash_attn_declared_per_needed.train"] is None
    assert got["flops_declared_per_needed.train"] > 0
    assert got["kernel_undeclared_pct.train"] == pytest.approx(100 / 8)


def test_the_scan_s_bytes_in_the_hybrid_cell_only(monkeypatch):
    scans = whole_step() + [
        kernel("ssd_scan_fwd", 0.8, 0.9, "ssm_scan", 1e9, 4e9),
        kernel("ssd_scan_bwd", 0.9, 1.0, "ssm_scan", 2e9, 6e9)]
    cell, got = read_all(monkeypatch, HYBRID, trace(scans))
    need = 9 * cell.family.scan_kernel_bytes_per_token(cell.conf) * 16384
    assert need == 9 * 61952 * 16384
    assert got["ssd_scan_bytes_declared_per_needed.train"] == \
        pytest.approx(1e10 / need)
    assert "moe_experts_declared_per_needed.train" not in got
    # no such kernel in the trace: nothing
    _, got = read_all(monkeypatch, HYBRID, trace(whole_step()))
    assert got["ssd_scan_bytes_declared_per_needed.train"] is None
    # a family that states no such cost: nothing, whatever the trace holds
    dense = harness.find_cell(DENSE)
    assert reader("ssd_scan_bytes_declared_per_needed.train")(
        object(), {"tokens_per_step": 16384, "chips": 1}, {}, dense) is None


def test_the_experts_tiles_in_the_expert_cell_only(monkeypatch):
    products = whole_step() + [
        kernel("grouped_mm", 0.8, 0.9, "moe_experts", 4e12, 1e9),
        kernel("grouped_mm_dw", 0.9, 1.0, "moe_experts", 2.5e12, 1e9),
        kernel("moe_sum_pairs", 1.0, 1.1, "moe_combine", 9e12, 1e9)]
    cell, got = read_all(monkeypatch, EXPERT, trace(products))
    need = cell.family.expert_flops_per_token(cell.conf) * 16384
    assert need == pytest.approx(9 * 2 * 3584 * 1024 * 0.5 * 4 * 16384)
    # the token side's sums are no product of the experts
    assert got["moe_experts_declared_per_needed.train"] == \
        pytest.approx(6.5e12 / need)
    assert "ssd_scan_bytes_declared_per_needed.train" not in got
    _, got = read_all(monkeypatch, EXPERT, trace(whole_step()))
    assert got["moe_experts_declared_per_needed.train"] is None
    dense = harness.find_cell(DENSE)
    assert reader("moe_experts_declared_per_needed.train")(
        object(), {"tokens_per_step": 16384, "chips": 1}, {}, dense) is None


PLAIN_MLA = "kanana-2-30b-a3b.pretrain-16k-mla-moe"


def test_the_plain_latent_attention_cell_reads_by_its_family_s_costs(
        monkeypatch):
    """PR 56's cell: the experts' tiles against ``kanana_mla_moe``'s
    ``expert_flops_per_token`` (1.5 pairs a token in six expert layers),
    the flash kernels' against the ``attn_width`` its ``block_costs``
    states (192-wide scores, 128-wide values, seven layers at 16,384),
    the whole step against the sum."""
    products = whole_step() + [
        kernel("grouped_mm", 0.8, 0.9, "moe_experts", 4e12, 1e9),
        kernel("grouped_mm_dw", 0.9, 1.0, "moe_experts", 2.5e12, 1e9),
        kernel("moe_sum_pairs", 1.0, 1.1, "moe_combine", 9e12, 1e9)]
    cell, got = read_all(monkeypatch, PLAIN_MLA, trace(products))
    need = cell.family.expert_flops_per_token(cell.conf) * 16384
    assert need == pytest.approx(9 * 2 * 2048 * 768 * 1.5 * 6 * 16384)
    assert got["moe_experts_declared_per_needed.train"] == \
        pytest.approx(6.5e12 / need)
    attn = kernel_costs_kernels.flash_attn_train_flops_per_token(
        cell.conf, 16384) * 16384
    assert attn == 6 * 16384 * 7 * 32 * 160 * 16384
    assert got["flash_attn_declared_per_needed.train"] == \
        pytest.approx(3e12 / attn)
    whole = kernel_costs.train_flops_per_token(cell.conf, 16384) * 16384
    assert got["flops_declared_per_needed.train"] == \
        pytest.approx((1e14 + 3e12 + 6.5e12 + 9e12) / whole)
    assert set(got) == {"kernel_undeclared_pct.train",
                        "flops_declared_per_needed.train",
                        "flash_attn_declared_per_needed.train",
                        "moe_experts_declared_per_needed.train"}
    # its own reader: the five scopes' share where an ``mla_*`` scope is
    # named (0.1 s of ``attn`` + 0.2 s of ``mla_q`` of 1.3 s), nothing on
    # a program that names none — the dense cell's ``attn`` alone
    mla = products + [("%fusion.2 = fusion()", 1.1, 1.3,
                       BODY + "mla_q/dot_general", "convolution fusion",
                       1e12, 1e9)]
    view = trace(mla).named(*xplane_meta.names_of(cell))
    monkeypatch.setattr(xplane_meta, "of_cell", lambda c, t: view)
    by = view.self_time_by("scope")
    assert reader("mla_mixer_pct.train")(object(), {}, {}, cell) == \
        pytest.approx(100 * (by["attn"] + by["mla_q"]) / sum(by.values()))
    assert by["mla_q"] == pytest.approx(0.2)
    dense = harness.find_cell(DENSE)
    plain = trace(products).named(*xplane_meta.names_of(dense))
    monkeypatch.setattr(xplane_meta, "of_cell", lambda c, t: plain)
    assert reader("mla_mixer_pct.train")(object(), {}, {}, dense) is None


def test_the_new_metrics_are_entered_as_the_issue_lists_them():
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    new = {m["name"]: m for m in bench["per_layer"]
           if "declared" in m["name"]}
    # PR 44 appended its cell to the lists that apply to it, and one
    # ratio of its own: the windowed kernels' declared work
    window = "smallthinker-21b-a3b.pretrain-16k-moe"
    # ... and PR 48 its cell likewise, with the gated short convolution's
    # declared bytes
    conv = "lfm2-24b-a2b.pretrain-8k-conv-moe"
    # ... and PR 52 its cell, with the chunked delta rule's declared FLOPs
    kda = "solar-open2-250b.pretrain-kda-moe"
    # ... and PR 56 its cell, which brings no ratio of its own
    every = [DENSE, HYBRID, EXPERT, window, conv, kda, PLAIN_MLA]
    assert {n: m["workloads"] for n, m in new.items()} == {
        "kernel_undeclared_pct.train": every,
        "flops_declared_per_needed.train": every,
        "flash_attn_declared_per_needed.train": every,
        "ssd_scan_bytes_declared_per_needed.train": [HYBRID],
        "moe_experts_declared_per_needed.train": [EXPERT, window, conv,
                                                  kda, PLAIN_MLA],
        "flash_win_declared_per_needed.train": [window],
        "short_conv_bytes_declared_per_needed.train": [conv],
        "kda_chunk_declared_per_needed.train": [kda]}
    for m in new.values():
        assert (m["source"], m["moves"], m["better"], m["layer"]) == (
            "program_counter", "train_tok_s_chip", "lower", "kernels")
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "layer_metrics", m["name"] + ".py"))
    # appended: the 17 entries that were there come first, unchanged
    # (and PR 44's device-trace reader before its declared ratio)
    assert [m["name"] for m in bench["per_layer"][17:]
            if "declared" in m["name"]] == list(new)
    assert [m["name"] for m in bench["per_layer"][22:]] == [
        "flash_win_roofline_pct.train",
        "flash_win_declared_per_needed.train",
        "short_conv_mixer_pct.train", "short_conv_roofline_pct.train",
        "short_conv_bytes_declared_per_needed.train",
        "kda_mixer_pct.train", "kda_chunk_roofline_pct.train",
        "kda_chunk_declared_per_needed.train",
        # PR 54: the five that move ``setup_s``, in every cell
        "setup_step_compile_s.train", "setup_trace_lower_s.train",
        "setup_small_programs_s.train", "setup_cache_miss_pct.train",
        "setup_loader_start_s.train",
        # PR 56: the latent-attention mixer's share, in its cell
        "mla_mixer_pct.train"]
    for m in bench["per_layer"][-6:-1]:
        assert (m["moves"], m["better"], m["layer"], m["workloads"]) == (
            "setup_s", "lower", "set-up", every)
