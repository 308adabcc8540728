"""Block families.  A configuration file names its family by
``"family": "<name>"``, and everything the family means to the benchmark
is in two files of its own here, found by that name.  This docstring is
the contract; the other places point here.

``<name>.py`` — what faces the program, what a block costs, its names
    ``build_cfg(conf, train, job)``, ``leaf_shapes``, ``make_leaf``,
    ``make_params``, ``seed_key``: the program's config object and the
    weights from the seed.  The program is imported inside the
    functions, never at import.
    ``block_costs(conf)`` -> ``kernel_costs.BlockCosts``: the matrix
    parameters a token MULTIPLIES (FLOP counts) apart from those
    RESIDENT (weight bytes), the vector parameters, heads x head_dim,
    the values a token leaves in the cache.
    ``SCOPES``, ``KERNELS`` (optional tuples of strings): the names its
    program adds to the base vocabulary of ``xplane_meta``.

``<name>_reference.py`` — the plain reference of ONE block, which
``benchmark/reference.py`` drives.  It imports nothing of the program
and not ``<name>.py``; it takes the matrix product, the norm and the
rotation from ``benchmark/reference.py``, so that the int8 CONTROL
reaches every product of the block.
    ``BLOCK_LEAVES``: the names of one layer's leaves.  A layer's leaf
    may have any rank >= 1: the machinery slices the stacked leaf by
    its first axis and never looks inside.  One layer's leaf is one
    adafactor tensor: factored over its last two axes where both are
    >= 128, clipped and scaled by its own rms.
    ``dims_of(conf)``: everything ``block`` needs from the
    configuration, hashable (a static argument of the jitted calls).
    ``block(x, w, dims, precision)``: one block on x [rows, s, hidden]
    (float32), ``w`` the layer's float32 leaves.  Returns the block's
    output AND a float32 scalar that is added to the step's loss (a
    router's auxiliary terms; zero where the block penalises nothing).
    The scalar is the MEAN OVER THE ROWS GIVEN OF A QUANTITY OF ONE
    ROW (a statistic of a row's own tokens), so that how many rows go
    through together changes no result: the loop gives two at a time
    (``reference.ROW_BLOCK``), weights the scalar by the rows' share of
    the batch and carries its gradient back through the block.  A
    statistic ACROSS rows cannot be stated; a program whose auxiliary
    loss is compared takes it a row at a time.  Where two rows of a
    job's length do not fit at once, ``block`` takes them, or its
    heads or experts, in turn inside itself (``jax.lax.map``);
    ``tests/aot_compile.py reference <cell>`` says what it needs.

A new architecture adds these two files and edits none.
"""

from __future__ import annotations

import importlib


def family(conf: dict):
    """The family module a configuration names."""
    return importlib.import_module(f"{__name__}.{conf['family']}")


def block_reference(conf: dict):
    """The plain reference of the family's block.  Imports jax."""
    return importlib.import_module(f"{__name__}.{conf['family']}_reference")
