"""The seam a block family plugs into: its reference (the blocks of each
kind of layer, the model's two ends), its costs and its names come from
files found by the configuration's ``"family"``, and a second
architecture is added as files, with no edit to a file that is there.  On the CPU, at the toy size: counts and verdicts, never a time.
"""

import filecmp
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

import test_kinds
from benchmark import (harness, kernel_costs, kernel_costs_kernels,
                       reference, rehearse, train_cell, xplane_meta)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
LEFT_BEHIND = ("out", "__pycache__", ".pytest_cache")     # of runs, not files


# -- a second family, as files only ---------------------------------------
TWIN = '''"""A second family for the seam's test: it drives the same program
block, and states costs and names of its own."""
from .llama_block import *                      # noqa: F401,F403
from .llama_block import block_costs as _dense

SCOPES = ("twin_router", "twin_experts")
KERNELS = ("twin_grouped_matmul",)


def block_costs(conf):
    """As if a token multiplied a quarter of what the block holds."""
    dense = _dense(conf)
    return dense._replace(matmul_params=dense.resident_params // 4)
'''
ROPE_LINE = 'q = rope(mm(y, w["wq"]).reshape(b, s, n, d), theta)'
NO_ROPE_LINE = 'q = mm(y, w["wq"]).reshape(b, s, n, d)'
# A family with KINDS of layer, a tied table and multipliers
# (``toy/hybrid_block*.py``), and three more whose reference is altered
# by one line each (``test_kinds.ALTERED``).  The repo's program runs
# one kind of layer, so a family with kinds cannot drive it: these drive
# the stand-in of ``toy/hybrid_program.py`` (whole-model autodiff, the
# program's state layout), which the driver below puts in the place of
# ``make_train_step`` / ``init_adafactor_state`` in its own process;
# ``train_cell.run`` and everything under it run unchanged.
HYBRIDS = ("kinded",) + tuple(sorted(test_kinds.ALTERED))
DRIVER = '''import importlib.util, json, os, sys, types


def main():
    copy_root, repo = sys.argv[1], sys.argv[2]
    # benchmark: the copy; the program: the repo's
    sys.path[:0] = [copy_root, repo]
    os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmark import (harness, kernel_costs, kernel_costs_kernels,
                           rehearse, train_cell, xplane_meta)
    from paddle_tpu.models import llama_pretrain
    seen = rehearse.patch_for_cpu(harness)
    toy = os.path.join(os.path.dirname(harness.__file__), "tests", "toy")
    job = harness.load_json(os.path.join(toy, "train_job.json"))
    out = {"harness": harness.__file__}

    def rehearse_family(name, trace):
        conf = harness.load_json(os.path.join(toy, "config_%s.json" % name))
        cell = harness.Cell.detached(name + ".train_job", 1, conf, job)
        args = types.SimpleNamespace(workload=cell.name, seed=2**31 + 99,
                                     seconds=1.0, trace=trace)
        rc = train_cell.run(args, cell)
        scopes, kernels = xplane_meta.names_of(cell)
        out[name] = {
            "rc": rc, "correct": seen["correct"],
            "readers": seen["metrics_read"],
            "reference": cell.block_reference.__name__,
            "family": cell.family.__name__,
            "layer_costs": [list(c) for c in kernel_costs.layer_costs(conf)],
            "train_flops": kernel_costs.train_flops_per_token(
                conf, job["seq"]),
            "attn_flops": kernel_costs_kernels.
            flash_attn_train_flops_per_token(conf, job["seq"]),
            "total_params": kernel_costs.total_params(conf),
            "scopes_added": scopes[len(xplane_meta.SCOPES):],
            "kernels_added": kernels[len(xplane_meta.KERNELS):]}
    for name in ("twin", "twin_norope"):
        rehearse_family(name, 1)
    spec = importlib.util.spec_from_file_location(
        "hybrid_program", os.path.join(toy, "hybrid_program.py"))
    stand_in = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(stand_in)
    llama_pretrain.make_train_step = stand_in.make_train_step
    llama_pretrain.init_adafactor_state = stand_in.init_adafactor_state
    for name in @HYBRIDS@:
        rehearse_family(name, 0)
    print("SEAM " + json.dumps(out), flush=True)


if __name__ == "__main__":      # the DataLoader's workers import this file
    main()
'''.replace("@HYBRIDS@", repr(HYBRIDS))


def tree_files(root):
    out = set()
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x not in LEFT_BEHIND]
        out.update(os.path.relpath(os.path.join(d, f), root)
                   for f in files if not f.endswith(".pyc"))
    return out


@pytest.fixture(scope="module")
def seam(tmp_path_factory):
    """In a copy of ``benchmark/``: six families added as files — two
    dense (the second's reference is the first's with rope left out of
    one line), one with kinds and its three altered ones — and the train
    rehearsal run on each, in one process.  Gives what that process
    read, and checks first that no file that was there has changed."""
    tmp_path = tmp_path_factory.mktemp("seam")
    copy = tmp_path / "benchmark"
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns(*LEFT_BEHIND))
    before = tree_files(copy)
    models, toy = copy / "models", copy / "tests" / "toy"
    dense_ref = (models / "llama_block_reference.py").read_text()
    assert dense_ref.count(ROPE_LINE) == 1
    conf = json.loads((toy / "config.json").read_text())
    hybrid_conf = json.loads((toy / "config_hybrid.json").read_text())
    families = [("twin", TWIN, dense_ref, conf),
                ("twin_norope", TWIN,
                 dense_ref.replace(ROPE_LINE, NO_ROPE_LINE), conf)]
    hybrid_side = (toy / "hybrid_block.py").read_text()
    hybrid_ref = (toy / "hybrid_block_reference.py").read_text()
    for name in HYBRIDS:
        old, new = test_kinds.ALTERED.get(name, ("", ""))
        assert name == "kinded" or hybrid_ref.count(old) == 1
        families.append((name, hybrid_side, hybrid_ref.replace(old, new),
                         hybrid_conf))
    added = set()
    for name, side, ref, base in families:
        (models / f"{name}_block.py").write_text(side)
        (models / f"{name}_block_reference.py").write_text(ref)
        (toy / f"config_{name}.json").write_text(json.dumps(
            dict(base, name=name, family=name + "_block")))
        added |= {f"models/{name}_block.py",
                  f"models/{name}_block_reference.py",
                  f"tests/toy/config_{name}.json"}
    driver = tmp_path / "driver.py"
    driver.write_text(DRIVER)
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    p = subprocess.run([sys.executable, str(driver), str(tmp_path), REPO],
                       capture_output=True, text=True, timeout=900,
                       env=env, cwd=str(tmp_path))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = [l for l in p.stdout.splitlines() if l.startswith("SEAM ")][-1]
    got = json.loads(line[5:])
    assert os.path.dirname(got["harness"]) == str(copy)

    # files only: what was there is byte for byte the tree's
    after = tree_files(copy)
    assert after - before == added
    for rel in sorted(before):
        assert filecmp.cmp(os.path.join(BENCH, rel), copy / rel,
                           shallow=False), rel
    assert before == tree_files(BENCH)
    return got, conf, hybrid_conf


def test_a_second_family_is_files_only(seam):
    """The sound dense family is found, rehearsed and judged correct by
    ITS reference, costs and names; the altered one is judged not
    correct."""
    got, conf, _ = seam
    twin, bad = got["twin"], got["twin_norope"]
    assert twin["rc"] == 0 and twin["correct"] is True
    assert bad["rc"] == 0 and bad["correct"] is False
    assert twin["readers"] > 0
    assert twin["family"] == "benchmark.models.twin_block"
    assert twin["reference"] == "benchmark.models.twin_block_reference"
    assert bad["reference"] == "benchmark.models.twin_norope_block_reference"
    # the family's costs, not the dense block's
    dense = kernel_costs.block_costs(conf)
    L, seq = conf["num_hidden_layers"], 128
    assert twin["layer_costs"] == [list(dense._replace(
        matmul_params=dense.resident_params // 4))] * L
    assert twin["train_flops"] == \
        6.0 * (L * (dense.resident_params // 4)
               + kernel_costs.head_params(conf)) \
        + 6.0 * L * seq * dense.attn_width
    assert twin["train_flops"] < kernel_costs.train_flops_per_token(conf,
                                                                    seq)
    assert twin["scopes_added"] == ["twin_router", "twin_experts"]
    assert twin["kernels_added"] == ["twin_grouped_matmul"]


def test_a_family_with_kinds_and_its_own_ends_is_files_only(seam):
    """Two kinds in a pattern, a tied table, a multiplier and a divisor,
    through ``train_cell.run``: found by its files and judged correct."""
    got, _, _ = seam
    sound = got["kinded"]
    assert sound["rc"] == 0 and sound["correct"] is True
    assert sound["family"] == "benchmark.models.kinded_block"
    assert sound["reference"] == "benchmark.models.kinded_block_reference"


@pytest.mark.parametrize("what", sorted(test_kinds.ALTERED))
def test_an_altered_family_with_kinds_is_not_correct(seam, what):
    got, _, _ = seam
    assert got[what]["rc"] == 0 and got[what]["correct"] is False
    assert got[what]["reference"] == \
        f"benchmark.models.{what}_block_reference"


def test_costs_are_summed_by_kind_in_the_familys_order(seam):
    """Hand sums of the toy hybrid (hidden 128, FFN 256, 2 heads / 1 KV
    head of 64, vocabulary 384, layers mix mix attention mix): the scan
    field enters the training count three times, the attention term
    counts the one layer that attends, the tied table counts once."""
    got, _, conf = seam
    sound = got["kinded"]
    h, f, q, kv, V, seq = 128, 256, 128, 64, 384, 128
    mix = 2 * h * h + 3 * h * f
    att = 2 * h * q + 2 * h * kv + 3 * h * f
    assert [c[0] for c in sound["layer_costs"]] == [mix, mix, att, mix]
    assert [c[3] for c in sound["layer_costs"]] == [0, 0, q, 0]
    assert [c[5] for c in sound["layer_costs"]] == [2 * h, 2 * h, 0, 2 * h]
    assert sound["train_flops"] == 6.0 * (3 * mix + att + h * V) \
        + 6.0 * seq * q + 3.0 * (3 * 2 * h)
    assert sound["attn_flops"] == 6.0 * seq * q
    # every parameter once: what the family's own tree holds
    assert sound["total_params"] == \
        3 * (mix + 3 * h) + (att + 2 * h) + h * V + h
    import math
    fam = test_kinds.load("hybrid_block")
    assert sound["total_params"] == sum(
        math.prod(shape) for shape in
        fam.leaf_shapes(fam.build_cfg(conf, train=True)).values())


# -- the dense block's reference is the parent's, from another file -------
# ``git archive 4864288`` (the parent of PR 26) unpacked under
# /root/scratch, its ``benchmark/reference.py`` driven as
# ``test_correct.py::test_training_control_fails_a_limit`` drives it:
# toy configuration, ``seed_key(5)``, ``default_rng(5)`` batches, two
# steps, float32.  Every digit as the parent printed it (CPU backend).
PARENT_SEED5 = {
    "loss": [6.760863780975342, 6.820850849151611],
    "grad": {"blocks/ln1": 0.19901065587665875,
             "blocks/ln2": 0.12415397806444876,
             "blocks/wq": 1.4664129930766954,
             "blocks/wk": 1.5041917059347383,
             "blocks/wv": 1.8357270789800622,
             "blocks/wo": 1.770999282653142,
             "blocks/w_gate": 1.3459838441514012,
             "blocks/w_up": 1.3828775315160269,
             "blocks/w_down": 1.3369102059999047,
             "embed": 2.719338539701495,
             "final_norm": 0.08800087213134868,
             "lm_head": 1.0065455198232505},
    "change": {"blocks/ln1": 0.31703729455486473,
               "blocks/ln2": 0.3280559989494362,
               "blocks/wq": 0.3152639703672686,
               "blocks/wk": 0.22318889252092844,
               "blocks/wv": 0.22457524745241284,
               "blocks/wo": 0.32007163574962744,
               "blocks/w_gate": 0.45009275083213124,
               "blocks/w_up": 0.44632946975054044,
               "blocks/w_down": 0.4488808397589371,
               "embed": 0.22671977700829646,
               "final_norm": 0.30034995429989786,
               "lm_head": 0.3097686252713272}}


def seed5_inputs(cell):
    job, fam = cell.traffic, cell.family
    cfg = fam.build_cfg(cell.conf, train=True, job=job)
    leaf0 = train_cell.leaf_maker(fam, cfg, fam.seed_key(5))
    rng = np.random.default_rng(5)
    return job, leaf0, [rng.integers(0, cfg.vocab_size,
                                     (job["batch"], job["seq"] + 1))
                        for _ in range(job["reference_steps"])]


def test_the_dense_reference_reads_what_the_parents_read():
    """The same jitted arithmetic from another file: the losses to the
    digit, the norms to the last few bits (they are sums of squares
    taken on the host in float64)."""
    cell = rehearse.toy_cell("train_job")
    got = train_cell.run_reference(cell, *seed5_inputs(cell))
    assert got["loss"] == PARENT_SEED5["loss"]
    for what in ("grad", "change"):
        mine = {"/".join(k): v for k, v in got[what].items()}
        assert mine.keys() == PARENT_SEED5[what].keys()
        for k, want in PARENT_SEED5[what].items():
            assert mine[k] == pytest.approx(want, rel=1e-12), (what, k)


# -- a block that adds to the loss ----------------------------------------
def test_a_blocks_own_loss_term_reaches_the_loss_and_the_gradients(
        monkeypatch):
    """The contract's second return value, through the seam: a family
    whose block penalises a statistic of each row's own tokens raises
    the step's loss by that term (summed over the layers) and changes
    every gradient below it, and the loop's two rows at a time give
    what the stated loss gives taken a row at a time."""
    import jax.numpy as jnp
    from benchmark.models import llama_block, llama_block_reference as dense
    c = 0.5

    def block(x, w, dims, precision="f32"):
        y, zero = dense.block(x, w, dims, precision)
        # the square of a row's mean, averaged over the rows given: no
        # sum over tokens, and a quantity of one row, as the contract
        # asks
        row = jnp.square(jnp.mean(jnp.abs(y), axis=(1, 2)))
        return y, zero + c * jnp.mean(row)
    ref = types.ModuleType("benchmark.models.termed_block_reference")
    ref.BLOCK_LEAVES, ref.dims_of = dense.BLOCK_LEAVES, dense.dims_of
    ref.block = block
    monkeypatch.setitem(sys.modules, "benchmark.models.termed_block",
                        llama_block)
    monkeypatch.setitem(sys.modules, ref.__name__, ref)
    toy = rehearse.toy_cell("train_job")
    cell = harness.Cell.detached(
        "toy.termed", 1, dict(toy.conf, family="termed_block"), toy.traffic)
    assert cell.block_reference is ref
    job, leaf0, batches = seed5_inputs(cell)
    assert job["batch"] == reference.ROW_BLOCK == 2
    plain = train_cell.run_reference(toy, job, leaf0, batches)
    termed = train_cell.run_reference(cell, job, leaf0, batches)
    # the weights of step 0 are the seed's on both sides, so the loss
    # differs by the blocks' terms alone
    assert termed["loss"][0] > plain["loss"][0] + 0.1
    g, g_t = plain["grad"], termed["grad"]
    assert g_t[("blocks", "wq")] != g[("blocks", "wq")]
    assert g_t[("embed",)] != g[("embed",)]
    # the head's gradient does not see the blocks' terms
    assert g_t[("lm_head",)] == g[("lm_head",)]
    assert g_t[("final_norm",)] == g[("final_norm",)]
    # a quantity of one row: a row at a time (each term at half weight)
    # gives the loss and the gradients of two rows together
    monkeypatch.setattr(reference, "ROW_BLOCK", 1)
    by_row = train_cell.run_reference(cell, job, leaf0, batches)
    assert by_row["loss"][0] == pytest.approx(termed["loss"][0], rel=1e-6)
    for k, v in termed["grad"].items():
        assert by_row["grad"][k] == pytest.approx(v, rel=1e-4), k

    # the loop's backward, layer by layer with the two rows together, is
    # the gradient of the loss the contract states, taken here a row at
    # a time: the tokens' mean NLL plus each row's terms at half weight
    # (one row of two)
    import jax
    dims, eps = dense.dims_of(cell.conf), float(cell.conf["rms_norm_eps"])
    names = [("blocks", nm) for nm in dense.BLOCK_LEAVES] + \
        [(nm,) for nm in reference.TOP_LEAVES]
    tok = jnp.asarray(batches[0])

    def whole_loss(p):
        total = 0.0
        for row in range(tok.shape[0]):
            x = jnp.take(p[("embed",)], tok[row:row + 1, :-1], axis=0)
            for i in range(cell.conf["num_hidden_layers"]):
                x, term = block(x, {nm: p[("blocks", nm)][i]
                                    for nm in dense.BLOCK_LEAVES}, dims)
                total = total + 0.5 * term
            logits = reference.matmul(
                reference.rms_norm(x, p[("final_norm",)], eps),
                p[("lm_head",)], "f32")
            nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
                logits, tok[row:row + 1, 1:, None], -1)[..., 0]
            total = total + jnp.sum(nll) / (tok.shape[0] * (tok.shape[1] - 1))
        return total
    value, grads = jax.value_and_grad(whole_loss)(
        {k: leaf0(k) for k in names})
    assert termed["loss"][0] == pytest.approx(float(value), rel=1e-6)
    for k in names:
        assert termed["grad"][k] == pytest.approx(
            float(jnp.sqrt(jnp.sum(jnp.square(grads[k])))), rel=1e-4), k


# -- the reference imports nothing of the program -------------------------
def test_a_familys_reference_imports_nothing_of_the_program():
    code = ("import sys; sys.path.insert(0, %r); "
            "import benchmark.models.llama_block_reference as r; "
            "import benchmark.reference; "
            "bad = sorted(m for m in sys.modules if m == 'paddle_tpu' "
            "or m.startswith('paddle_tpu.') "
            "or m == 'benchmark.models.llama_block'); "
            "print('BAD', bad)" % REPO)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip().splitlines()[-1] == "BAD []"


# -- costs: what a token multiplies apart from what is resident -----------
@pytest.fixture
def sparse_family(monkeypatch):
    """A family whose block holds 8 experts and routes a token to 2."""
    def block_costs(conf):
        h, f = conf["hidden_size"], conf["intermediate_size"]
        attn = 4 * h * h
        return kernel_costs.BlockCosts(
            matmul_params=attn + 2 * 3 * h * f,
            resident_params=attn + 8 * 3 * h * f,
            vector_params=2 * h, attn_width=h // 2, kv_values=h)
    fam = types.ModuleType("benchmark.models.sparse_block")
    fam.block_costs = block_costs
    monkeypatch.setitem(sys.modules, fam.__name__, fam)
    return {"family": "sparse_block", "hidden_size": 256,
            "intermediate_size": 128, "num_hidden_layers": 3,
            "vocab_size": 1000}


def test_flops_count_what_a_token_multiplies_bytes_what_is_resident(
        sparse_family):
    conf = sparse_family
    h, f, L, V = 256, 128, 3, 1000
    active = 4 * h * h + 6 * h * f
    held = 4 * h * h + 24 * h * f
    assert kernel_costs.block_costs(conf).resident_params == held
    assert kernel_costs.total_params(conf) == \
        L * (held + 2 * h) + 2 * h * V + h
    assert kernel_costs.train_flops_per_token(conf, 64) == \
        6.0 * (L * active + h * V) + 6.0 * L * 64 * (h // 2)
    assert kernel_costs_kernels.flash_attn_train_flops_per_token(
        conf, 64) == 6.0 * L * 64 * (h // 2)
    assert kernel_costs.prefill_flops(conf, [10, 20]) == \
        2.0 * L * active * 30 + L * 2.0 * (100 + 400) * (h // 2) \
        + 2.0 * h * V * 2
    assert kernel_costs.weight_bytes_per_chip(conf) == (L * held + h * V) * 2
    assert kernel_costs.kv_bytes_per_token(conf) == L * h * 2
    assert kernel_costs_kernels.paged_attn_step_bytes(conf, 7.0) == \
        7.0 * L * h * 2


# -- names: the base vocabulary and what a family adds --------------------
def test_a_familys_names_are_read_with_the_base_vocabulary(tmp_path,
                                                           monkeypatch):
    """On PR 24's recorded trace.  ``checkpoint`` and ``closed_call``
    stand on its op paths and are no scope of the base vocabulary: a
    family that names them gets them charged.  The file is parsed once
    (``load``'s cache) and the second cell's reading does not inherit
    the first's names, in either order."""
    import gzip
    run = tmp_path / "out"
    monkeypatch.setattr(harness, "OUT", str(run))
    base_cell = rehearse.toy_cell("train_job")
    named_cell = rehearse.toy_cell("train_job")
    named_cell.name = "toy.named"
    named_cell.family = types.SimpleNamespace(
        SCOPES=("closed_call",), KERNELS=("checkpoint",))
    for cell in (base_cell, named_cell):
        d = os.path.join(harness.run_dir(cell), "trace", "plugins",
                         "profile", "t0")
        os.makedirs(d)
        with gzip.open(os.path.join(
                HERE, "data", "train_3steps_scoped.xplane.pb.gz")) as f, \
                open(os.path.join(d, "t.xplane.pb"), "wb") as g:
            shutil.copyfileobj(f, g)
    trace = object()                    # "a trace was recorded"
    xplane_meta._load.cache_clear()

    named = xplane_meta.of_cell(named_cell, trace)
    base = xplane_meta.of_cell(base_cell, trace)
    again = xplane_meta.of_cell(named_cell, trace)
    assert xplane_meta._load.cache_info().misses == 2      # two files
    assert xplane_meta._load.cache_info().hits == 1
    assert named.ops is again.ops                           # parsed once
    assert base.scopes == xplane_meta.SCOPES
    assert named.scopes == xplane_meta.SCOPES + ("closed_call",)
    by_base, by_named = (t.self_time_by("scope") for t in (base, named))
    assert "closed_call" not in by_base
    assert by_named["closed_call"] > 0
    assert again.self_time_by("scope") == by_named
    # charged to the innermost name: what the family's scope takes, it
    # takes from the enclosing ones, and the total stands
    assert sum(by_named.values()) == pytest.approx(sum(by_base.values()),
                                                   rel=1e-12)
    assert by_named[xplane_meta.UNSCOPED] <= by_base[xplane_meta.UNSCOPED]
    # a kernel is the component before ``pallas_call``
    assert xplane_meta.kernel_of("jit(f)/moe/grouped/pallas_call") == ""
    assert xplane_meta.kernel_of("jit(f)/moe/grouped/pallas_call",
                                 named.kernels + ("grouped",)) == "grouped"
    assert base.self_time_by("kernel") == named.self_time_by("kernel")
    assert xplane_meta.of_cell(named_cell, None) is None
