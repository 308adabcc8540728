"""The family ``granite_hybrid`` through the benchmark's own run of a
training cell, on the CPU at toy size: ``train_cell.run`` — the feed,
the REAL ``make_train_step`` in bf16, the plain reference, the checks,
the per-layer readers — on a COPY of ``benchmark/`` with the rehearsal's
patches (``rehearse.patch_for_cpu``: counts and verdicts, never a
time).  The family is files: nothing under ``benchmark/`` is edited to
run it, and nothing is put in ``make_train_step``'s place.
"""

import filecmp
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
LEFT_BEHIND = ("out", "__pycache__", ".pytest_cache")

DRIVER = '''import json, os, sys, types


def main():
    copy_root, repo = sys.argv[1], sys.argv[2]
    sys.path[:0] = [copy_root, repo]    # benchmark: the copy; the program: the repo's
    os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmark import (harness, kernel_costs, rehearse, train_cell,
                           xplane_meta)
    seen = rehearse.patch_for_cpu(harness)
    toy = os.path.join(os.path.dirname(harness.__file__), "tests", "toy")
    conf = harness.load_json(os.path.join(toy, "config_granite.json"))
    job = harness.load_json(os.path.join(toy, "train_job_granite.json"))
    cell = harness.Cell.detached("toy-granite.train_job", 1, conf, job)
    out = {"harness": harness.__file__}

    def run(name, override=None):
        args = types.SimpleNamespace(workload=cell.name, seed=2**31 + 31,
                                     seconds=1.0, trace=1)
        rc = train_cell.run(args, cell, step_override=override)
        out[name] = {"rc": rc, "correct": seen["correct"],
                     "attempted": seen["attempted"],
                     "failed": seen["failed"]}

    def drifting(compiled):
        """The timed path broken underneath: after every step the
        state-space layers' skip weights are 5 % larger."""
        def step(params, opt, tokens):
            new, opt, loss = compiled(params, opt, tokens)
            mamba = dict(new["blocks"]["mamba"])
            mamba["D"] = mamba["D"] * 1.05
            blocks = dict(new["blocks"], mamba=mamba)
            return dict(new, blocks=blocks), opt, loss
        return step
    run("sound")
    run("broken", drifting)
    scopes, kernels = xplane_meta.names_of(cell)
    out["scopes_added"] = scopes[len(xplane_meta.SCOPES):]
    out["kernels_added"] = kernels[len(xplane_meta.KERNELS):]
    out["kinds"] = [list(c) for c in kernel_costs.layer_costs(conf)]
    out["total_params"] = kernel_costs.total_params(conf)
    print("REHEARSED " + json.dumps(out), flush=True)


if __name__ == "__main__":      # the DataLoader's workers import this file
    main()
'''


def tree_files(root):
    out = set()
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x not in LEFT_BEHIND]
        out.update(os.path.relpath(os.path.join(d, f), root)
                   for f in files if not f.endswith(".pyc"))
    return out


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("hybrid_cell")
    copy = tmp_path / "benchmark"
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns(*LEFT_BEHIND))
    before = tree_files(copy)
    driver = tmp_path / "driver.py"
    driver.write_text(DRIVER)
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    p = subprocess.run([sys.executable, str(driver), str(tmp_path), REPO],
                       capture_output=True, text=True, timeout=900,
                       env=env, cwd=str(tmp_path))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = [l for l in p.stdout.splitlines()
            if l.startswith("REHEARSED ")][-1]
    got = json.loads(line[10:])
    assert os.path.dirname(got["harness"]) == str(copy)
    # the family is files: the run changed none of them
    assert tree_files(copy) == before
    for rel in sorted(before):
        assert filecmp.cmp(os.path.join(BENCH, rel), copy / rel,
                           shallow=False), rel
    return got


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
