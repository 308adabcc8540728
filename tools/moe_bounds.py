#!/usr/bin/env python3
"""Which bound the routed experts' passes ran on, from a device trace:

    python3 tools/moe_bounds.py <trace.xplane.pb[.gz]>

``ops/moe.routed_ffn`` runs each pass (forward, the remat's forward,
backward) of each expert layer on the bound that follows the load
(scope ``moe_bound_load``) or on the bound of any load
(``moe_bound_all``); the scopes are in no reader's vocabulary, so the
trace is read here with the two as its vocabulary.  A pass is counted
by its ``grouped_mm`` runs: two forward, two backward.  Prints one JSON
line: device self seconds and passes by bound, and the share of passes
on the load's bound."""

from __future__ import annotations

import contextlib
import gzip
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
BOUNDS = ("moe_bound_load", "moe_bound_all")


def count(mt) -> dict:
    """``mt``: a ``benchmark.xplane_meta.MetaTrace``."""
    from benchmark import xplane_meta
    seconds = dict.fromkeys(BOUNDS, 0.0)
    passes = dict.fromkeys(BOUNDS, 0.0)
    for op in mt.ops.get(mt.chip(), []):
        bound = xplane_meta.scope_of(op.tf_op, BOUNDS)
        if bound in seconds:
            seconds[bound] += op.self_s
            if xplane_meta.kernel_of(op.tf_op, ("grouped_mm",)):
                passes[bound] += 0.5
    total = sum(passes.values())
    return {"self_s": seconds, "passes": passes,
            "share_on_the_load_bound":
                passes["moe_bound_load"] / total if total else None}


@contextlib.contextmanager
def unzipped(path: str):
    """The path of the trace as a plain ``.xplane.pb``."""
    if not path.endswith(".gz"):
        yield path
        return
    with tempfile.NamedTemporaryFile(suffix=".xplane.pb") as tmp:
        with gzip.open(path, "rb") as f:
            shutil.copyfileobj(f, tmp)
        tmp.flush()
        yield tmp.name


def read(path: str) -> dict:
    from benchmark import xplane_meta
    with unzipped(path) as plain:
        return count(xplane_meta.load(plain))


if __name__ == "__main__":
    print(json.dumps(read(sys.argv[1])))
