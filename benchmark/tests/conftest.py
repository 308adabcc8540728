"""Tests of the benchmark's own arithmetic and rehearsals.  Run by hand
(``python3 -m pytest benchmark/tests -q``), on the CPU; not part of the
repo's tier-1 suite."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
