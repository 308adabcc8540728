"""Block families.  A configuration file names its family by
``"family": "<name>"``, and everything the family means to the benchmark
is in two files of its own here, found by that name.  This docstring is
the contract; the other places point here.

``<name>.py`` — what faces the program, what a block costs, its names
    ``build_cfg(conf, train, job)``, ``leaf_shapes``, ``make_leaf``,
    ``make_params``, ``seed_key``: the program's config object and the
    weights from the seed, under the SAME PATHS as the reference's
    (below), so that ``train_cell.grad_norms_from_adafactor`` and
    ``change_norms`` meet the reference leaf for leaf.  The program is
    imported inside the functions, never at import.
    ``block_costs(conf)`` -> ``kernel_costs.BlockCosts``: the matrix
    parameters a token MULTIPLIES (FLOP counts) apart from those
    RESIDENT (weight bytes), the vector parameters, heads x head_dim
    (0 where the layer attends to nothing), the values a token leaves
    in the cache, and the FLOPs a token costs forward that come from no
    matrix parameter and do not grow with the row (``scan_flops``: a
    chunked scan at the configuration's own chunk size; training
    counts it three times, like the products).  A family with KINDS
    (below) states ``layer_kinds(conf)`` here too and is asked
    ``block_costs(conf, kind)``; ``kernel_costs`` sums over the layers
    in that order, and counts a tied table
    (``"tie_word_embeddings": true``) once.  The operations and bytes of
    a KERNEL of the family's own are functions of this file, which the
    kernel's reader (``layer_metrics/<name>.py``) finds by
    ``cell.family``; ``kernel_costs_kernels.py`` keeps the base
    vocabulary's.
    ``SCOPES``, ``KERNELS`` (optional tuples of strings): the names its
    program adds to the base vocabulary of ``xplane_meta``.

``<name>_reference.py`` — the plain reference of the family's blocks
and, where they are its own, of the model's two ends, which
``benchmark/reference.py`` drives (``reference.Model`` reads it).  It
imports nothing of the program and not ``<name>.py``; it takes the
matrix product, the norm and the rotation from
``benchmark/reference.py``, so that the int8 CONTROL reaches every
product.
    ``dims_of(conf)``: everything the family's functions need from the
    configuration, hashable (a static argument of the jitted calls).
    ``BLOCK_LEAVES``: the names of one layer's leaves.  A layer's leaf
    may have any rank >= 1: the machinery slices the stacked leaf by
    its first axis and never looks inside.  One LAYER's leaf is one
    adafactor tensor: factored over its last two axes where both are
    >= 128, clipped and scaled by its own rms — never a stack's, and
    kinds change nothing in that.  (The program's rms over a stacked
    leaf is the program PR's to settle, against limits read on the
    chip.)
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
    ``tests/aot_compile.py reference <cell>`` says what each kind
    needs.
    LAYERS OF MORE THAN ONE KIND (optional; in place of
    ``BLOCK_LEAVES`` and ``block``): ``KINDS``, a dict from a kind's
    name to ``(its leaf names, its block)`` — each block with the
    signature and the loss-term rule above — and ``layer_kinds(conf)``,
    the kind of every layer in order, from the published key cut to
    ``num_hidden_layers``.  A kind's leaf is stacked over the layers OF
    THAT KIND in their order of occurrence, ``[L_kind, ...]``, under
    the path ``("blocks", <kind>, <leaf>)``; the machinery walks the
    layers by index and takes the kind and the index within the kind.
    A family that states no kinds is one kind under the paths
    ``("blocks", <leaf>)``.
    THE TWO ENDS (optional; all three or none): ``TOP_LEAVES``, the
    names of the leaves outside the blocks, each under the path
    ``(<leaf>,)``; ``first_input(top, ids, dims)``: ids [rows, s] ->
    the first block's input [rows, s, hidden]; ``logits(top, x, dims,
    precision)``: the last block's output ``[..., hidden]`` -> logits
    ``[..., vocab]``, its products through ``reference.matmul``.
    ``top`` holds the float32 top leaves by name.  Default: ``embed``,
    ``final_norm``, ``lm_head``, the bare lookup, ``rms_norm(x) .
    lm_head``.  The head returns logits only, no loss term of its own;
    the machinery keeps the NLL over them, the vjp of both ends (each
    with respect to the leaves it reads, which tracing finds), and the
    SUM of a leaf's gradient over every use of it: a tied table states
    one leaf, read by both ends, and gets the head's product and the
    lookup's scatter-add.  A multiplier or a divisor is arithmetic in
    these two functions (and in ``block``), nothing the machinery
    knows.

A new architecture adds these two files and edits none.
"""

from __future__ import annotations

import importlib


def family(conf: dict):
    """The family module a configuration names."""
    return importlib.import_module(f"{__name__}.{conf['family']}")


def tree_of(paths, leaf) -> dict:
    """The nested dict that ``paths`` describe (``("blocks", [<kind>,]
    <leaf>)``, ``(<leaf>,)``), with ``leaf(path)`` at each."""
    out = {}
    for path in paths:
        at = out
        for key in path[:-1]:
            at = at.setdefault(key, {})
        at[path[-1]] = leaf(path)
    return out


def block_reference(conf: dict):
    """The plain reference of the family's block.  Imports jax."""
    return importlib.import_module(f"{__name__}.{conf['family']}_reference")
