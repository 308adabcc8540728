"""One scaffold for the cell rehearsals (``tests/test_*_cell_rehearsal.py``):
a family through the benchmark's own run of a training cell, on the CPU
at toy size — ``train_cell.run`` (the feed, the REAL ``make_train_step``
in bf16, the plain reference, the checks, the per-layer readers) in a
subprocess, on a COPY of ``benchmark/`` with the rehearsal's patches
(``rehearse.patch_for_cpu``: counts and verdicts, never a time).  The
family is files: the run may change none of the copy's.  A rehearsal file
keeps what its cell OWNS — the toy files, the leaf the broken run drifts,
the expected names, kinds and arithmetic.
"""

import filecmp
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
LEFT_BEHIND = ("out", "__pycache__", ".pytest_cache")

DRIVER = '''import json, os, sys, types


def main():
    copy_root, repo, spec = sys.argv[1], sys.argv[2], json.loads(sys.argv[3])
    sys.path[:0] = [copy_root, repo]    # benchmark: the copy; the program: the repo's
    os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmark import (harness, kernel_costs, rehearse, train_cell,
                           xplane_meta)
    seen = rehearse.patch_for_cpu(harness)
    counts_only = harness.result_line

    def with_names(cell, devices, traced, correct, attempted, failed,
                   metrics, *rest, **kw):
        seen["names"] = sorted(metrics)
        return counts_only(cell, devices, traced, correct, attempted,
                           failed, metrics, *rest, **kw)
    harness.result_line = with_names
    toy = os.path.join(os.path.dirname(harness.__file__), "tests", "toy")
    conf = harness.load_json(os.path.join(toy, spec["config"]))
    job = harness.load_json(os.path.join(toy, spec["job"]))
    cell = harness.Cell.detached(spec["cell"], 1, conf, job)
    out = {"harness": harness.__file__}

    def run(name, override=None):
        args = types.SimpleNamespace(workload=cell.name, seed=spec["seed"],
                                     seconds=1.0, trace=1)
        rc = train_cell.run(args, cell, step_override=override)
        out[name] = {"rc": rc, "correct": seen["correct"],
                     "attempted": seen["attempted"],
                     "failed": seen["failed"]}

    def drifting(compiled):
        """The timed path broken underneath: after every step one leaf
        of one kind is 5 % larger."""
        kind, leaf = spec["drifts"]

        def step(params, opt, tokens):
            new, opt, loss = compiled(params, opt, tokens)
            block = dict(new["blocks"][kind])
            block[leaf] = block[leaf] * 1.05
            blocks = dict(new["blocks"], **{kind: block})
            return dict(new, blocks=blocks), opt, loss
        return step
    run("sound")
    run("broken", drifting)
    scopes, kernels = xplane_meta.names_of(cell)
    out["scopes_added"] = scopes[len(xplane_meta.SCOPES):]
    out["kernels_added"] = kernels[len(xplane_meta.KERNELS):]
    out["kinds"] = [list(c) for c in kernel_costs.layer_costs(conf)]
    out["total_params"] = kernel_costs.total_params(conf)
    out["metrics"] = seen["names"]
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


def rehearse(tmp_path_factory, name, config, job, seed, drifts) -> dict:
    """Run the toy cell ``toy-<name>.train_job`` (``config`` and ``job``:
    files of ``benchmark/tests/toy``) sound, then with the leaf ``drifts
    = (kind, leaf)`` growing underneath the timed path; what the driver
    printed: both verdicts, the family's names, kinds and metric names."""
    tmp_path = tmp_path_factory.mktemp(f"{name}_cell")
    copy = tmp_path / "benchmark"
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns(*LEFT_BEHIND))
    before = tree_files(copy)
    driver = tmp_path / "driver.py"
    driver.write_text(DRIVER)
    spec = {"cell": f"toy-{name}.train_job", "config": config, "job": job,
            "seed": seed, "drifts": list(drifts)}
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    p = subprocess.run([sys.executable, str(driver), str(tmp_path), REPO,
                        json.dumps(spec)],
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
