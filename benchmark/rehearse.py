#!/usr/bin/env python3
"""CPU rehearsal: drives the SAME functions as ``run.py`` with a toy
configuration (``benchmark/tests/toy/``) on the CPU backend and prints
counts only — never a metric's name or value, because a number from a
CPU run says nothing about the chip.

    python3 benchmark/rehearse.py --kind train_job|open_loop|backlog [--chips 4]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests", "toy")


def toy_cell(kind: str, chips: int = 1):
    """A detached cell of the toy configuration under the toy traffic
    of ``kind`` (train_job, open_loop or backlog)."""
    from benchmark import harness
    return harness.Cell.detached(
        "toy." + kind, chips,
        harness.load_json(os.path.join(
            TOY, "config_tp4.json" if chips > 1 else "config.json")),
        harness.load_json(os.path.join(TOY, kind + ".json")))


def patch_for_cpu(harness) -> dict:
    """No chip here: take the CPU's devices, skip memory statistics,
    and print counts in place of the result line."""
    import jax
    seen = {}
    harness.require_chips = lambda chips: jax.devices()[:chips]
    harness.memory_peak_bytes = lambda devices: 0

    def counts_only(cell, devices, traced, correct, attempted, failed,
                    metrics, memory_peak, trace=None):
        seen.update(correct=correct, attempted=attempted, failed=failed,
                    metrics_read=len(metrics))
        print(f"[rehearsal] platform {devices[0].platform} x "
              f"{len(devices)}: correct={correct} attempted={attempted} "
              f"failed={failed} readers that found something: "
              f"{sorted(metrics)} "
              f"(counts only; nothing here is a measurement)", flush=True)
    harness.result_line = counts_only
    # the peaks table has no CPU and must not: a rehearsal only needs
    # the readers to run, and prints none of what they return
    from benchmark import peaks
    peaks.CHIP_PEAKS[jax.devices()[0].device_kind] = peaks.ChipPeaks(1.0, 1.0)
    plain = harness.log

    def quiet(msg):                 # lines that carry a timing or a rate
        if not any(w in msg for w in ("median", "tok_s", "setup_s",
                                      "end to end")):
            plain(msg)
    harness.log = quiet
    for mod in ("benchmark.train_cell", "benchmark.serve_cell"):
        if mod in sys.modules:
            sys.modules[mod].log = quiet
    return seen


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--kind", required=True,
                    choices=("train_job", "open_loop", "backlog"))
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=2**31 + 12345)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ["JAX_PLATFORMS"] = "cpu"
    if args.chips > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.chips}")
    from benchmark import harness
    cell = toy_cell(args.kind, args.chips)
    if cell.traffic["kind"] == "train_job":
        from benchmark import train_cell as runner
    else:
        from benchmark import serve_cell as runner
    seen = patch_for_cpu(harness)
    args.workload = cell.name
    rc = runner.run(args, cell)
    return rc if rc else (0 if seen.get("correct") else 1)


if __name__ == "__main__":
    sys.exit(main())
