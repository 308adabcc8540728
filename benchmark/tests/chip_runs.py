#!/usr/bin/env python3
"""Several runs of the benchmark in ONE chip call, each its own process
(this parent never touches JAX, so each child gets the chip):

    python3 benchmark/tests/chip_runs.py <tag> <workload>:<seed>:<seconds>:<trace> ...

Each run's output goes to ``chiprun_out/<tag>/``; the end of each and a
summary (median and spread of every metric per workload and trace flag)
are printed.  ``--keep-trace`` copies the last traced run's
``.xplane.pb`` out and prints a by-hand description of it.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import stats          # noqa: E402  (pure Python)


def main(argv) -> int:
    keep = "--keep-trace" in argv
    argv = [a for a in argv if a != "--keep-trace"]
    tag, specs = argv[1], argv[2:]
    out = os.path.join(ROOT, "chiprun_out", tag)
    os.makedirs(out, exist_ok=True)
    rows = []
    for k, spec in enumerate(specs):
        wl, seed, seconds, trace = spec.split(":")
        cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
               "--workload", wl, "--seed", seed, "--seconds", seconds,
               "--trace", trace]
        t0 = time.time()
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.time() - t0
        with open(os.path.join(out, f"run{k}.log"), "w") as f:
            f.write(p.stdout + "\n--- stderr ---\n" + p.stderr)
        lines = [l for l in p.stdout.splitlines() if l.strip()]
        print(f"=== run {k}: {spec} rc={p.returncode} wall={wall:.0f}s")
        for l in lines[-14:-1]:
            print("   ", l[:400])
        if p.returncode != 0:
            print("    stderr:", p.stderr[-1500:])
            continue
        doc = json.loads(lines[-1])
        brk = doc.pop("breakdown", None)
        print("    RESULT", json.dumps(doc))
        if brk:
            print("    BREAKDOWN", json.dumps(brk)[:1500])
        rows.append((wl, trace, doc))
        if keep and trace == "1":
            found = sorted(glob.glob(os.path.join(
                ROOT, "benchmark", "out", wl, "trace", "plugins",
                "profile", "*", "*.xplane.pb")))
            if found:
                dst = os.path.join(out, f"{wl}.xplane.pb")
                shutil.copy(found[-1], dst)
                print(f"    trace kept: {dst} "
                      f"({os.path.getsize(dst) / 2**20:.1f} MiB)")
                d = subprocess.run(
                    [sys.executable, "-c",
                     "import sys; sys.path.insert(0, %r); "
                     "from benchmark import xplane; "
                     "print(xplane.describe(%r))" % (ROOT, dst)],
                    capture_output=True, text=True,
                    env=dict(os.environ, JAX_PLATFORMS="cpu"))
                print(d.stdout[-6000:] or d.stderr[-2000:])
    print("=== summary")
    groups = {}
    for wl, trace, doc in rows:
        for name, m in doc["metrics"].items():
            groups.setdefault((wl, trace, name), []).append(m["value"])
        groups.setdefault((wl, trace, "correct"), []).append(
            float(doc["correct"]))
        groups.setdefault((wl, trace, "memory_peak_GiB"), []).append(
            doc["device"]["memory_peak_bytes"] / 2**30)
    for (wl, trace, name), v in sorted(groups.items()):
        sp = f" spread {stats.spread(v):.4f}" if len(v) >= 3 else ""
        print(f"{wl} trace={trace} {name}: n={len(v)} median "
              f"{stats.median(v):.6g}{sp} values "
              f"{[round(x, 4) for x in v]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
