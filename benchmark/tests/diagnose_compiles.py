#!/usr/bin/env python3
"""Which programs compile inside a serving window, and how long each
takes: one run of a cell with JAX's own compile log on (by hand).

    python3 benchmark/tests/diagnose_compiles.py <workload> <seed> <seconds> [trace]
"""

import logging
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> int:
    import jax
    from benchmark import harness, serve_cell
    jax.config.update("jax_log_compiles", True)
    logging.basicConfig(level=logging.WARNING,
                        format="%(relativeCreated)d %(message)s")
    cell = harness.find_cell(argv[1])
    args = types.SimpleNamespace(workload=cell.name, seed=int(argv[2]),
                                 seconds=float(argv[3]),
                                 trace=int(argv[4]) if len(argv) > 4 else 0)
    return serve_cell.run(args, cell)


if __name__ == "__main__":
    sys.exit(main(sys.argv))
