"""Test support utilities that ship WITH the package (not under
tests/) because production modules consult them: the deterministic
fault-injection plane (:mod:`.faults`) is compiled into the serving
stack's degraded paths so every failure mode is exercisable on demand
— from pytest or from an operator shell.
"""

from . import faults  # noqa: F401
from . import mutants  # noqa: F401

__all__ = ["faults", "mutants"]
