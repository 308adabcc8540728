#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: loads, warms up, measures, prints the result line last and
exits.  Without the chips the cell asks for it exits non-zero and prints
no result.  Everything else goes on earlier lines or into
``benchmark/out/``.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from benchmark import harness
    args = harness.parse_args(argv)
    cell = harness.Cell(args.workload)
    kind = cell.traffic["kind"]
    if kind == "train_job":
        from benchmark import train_cell
        return train_cell.run(args, cell)
    if kind in ("open_loop", "backlog"):
        from benchmark import serve_cell
        return serve_cell.run(args, cell)
    raise SystemExit(f"traffic kind {kind!r} has no runner")


if __name__ == "__main__":
    sys.exit(main())
