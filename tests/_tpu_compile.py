"""What the files that compile for a DESCRIBED TPU v5e share
(``tests/test_tpu_compile.py``, ``test_tpu_compile_cells.py``,
``test_expert_cells_compile.py``): the topology, a one-chip mesh on it, the
switch that makes kernels lower through Mosaic, shapes with shardings, a
cell's step as the benchmark builds it, and the readers of an optimized
module's text.

The rules these files keep (on-chip-measurement guide, section 2): the
topology is described inside a module-scoped fixture that skips when it
cannot be (never at import, never in a ``skipif``/``parametrize``
argument, not in conftest, not autouse) — the fixtures below are imported
BY NAME into a test file, so only a worker that runs one of these files
loads the TPU's library; every compile happens in the test's own process.
The tests stand in THREE files so that none is a worker's wall (ROADMAP
D12): the kernels and engine programs; the dp2 x mp2, hybrid, window and
dense steps; the expert cell's step with the kernels only the two expert
cells' shapes reach — six cases, not one: xdist hands files out by their
NUMBER OF TESTS, largest first, and a long file of few cases starts last
and is the run's tail (``tools/tier1_times.py`` prints that order's
wall).  Only one process at a time may load the library unless ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` is set, as the driver's
tier-1 command sets it: under xdist WITHOUT it the files that go to a
second worker SKIP (``tools/tier1_times.py`` prints a file's skips); in
one process, as ROADMAP's tier-1 line runs them, all three pass.
Code that asks ``jax.default_backend()`` still sees the CPU here, so the
one place the kernels ask (``_common.interpret``) is steered from the
``compiled`` fixture — not through an option of the program.
"""

import functools
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import _pallas_flash
from _pallas_flash import _flash_module

KERNEL = "tpu_custom_call"
# the 1.345B block (chip_smoke.py): widths are never cut
VOCAB, HIDDEN, FFN, HEADS, HEAD_DIM, PAGE = 32000, 2048, 5504, 16, 128, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from paddle_tpu.models.llama_pretrain import build_mesh
    return build_mesh(devices=topo.devices[:1])


@pytest.fixture
def compiled(monkeypatch):
    """Kernels lower through Mosaic (as on the chip), not the
    interpreter the CPU backend would pick."""
    from paddle_tpu.ops.pallas import _common
    monkeypatch.setattr(_common, "interpret", lambda: False)


def _sds(mesh, shape, dtype, spec=P()):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _cfg(depth, train, sequence_parallel=False, nkv=HEADS):
    from paddle_tpu.models.llama_pretrain import LlamaPretrainConfig
    return LlamaPretrainConfig(
        vocab_size=VOCAB, hidden_size=HIDDEN, intermediate_size=FFN,
        num_hidden_layers=depth, num_attention_heads=HEADS,
        num_key_value_heads=nkv, max_seq_len=2048,
        use_pallas_attention=True, sequence_parallel=sequence_parallel,
        remat=train, dtype=jnp.bfloat16,
        param_dtype=jnp.float32 if train else jnp.bfloat16,
        loss_chunks=4 if train else 0)


def _param_sds(cfg, mesh):
    """Parameter shapes from ``init_params`` itself (eval_shape: nothing
    is allocated), placed on ``mesh`` by the model's own specs."""
    from paddle_tpu.models.llama_pretrain import (build_mesh, init_params,
                                                  param_specs)
    host = build_mesh(devices=jax.devices()[:1])
    shapes = jax.eval_shape(lambda k: init_params(cfg, k, host),
                            jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(
        lambda x, sp: _sds(mesh, x.shape, x.dtype, sp), shapes,
        param_specs(cfg, 1, 1),
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _two_kernels(monkeypatch):
    """Both one-pass budgets at 0 bytes (the module's constants): the
    two-kernel backward at any shape."""
    _pallas_flash._two_kernels(monkeypatch, _flash_module())


# ---------------------------------------------------------------------------
# readers of an optimized module's text
# ---------------------------------------------------------------------------
# opcodes that only place data, and what may stand beside them in a fusion
# that still computes nothing (a cotangent's pad-and-add among them)
_PLACES = {"slice", "dynamic-slice", "copy", "pad", "concatenate"}
_IDLE = _PLACES | {"parameter", "constant", "bitcast", "convert", "add",
                   "tuple", "get-tuple-element", "broadcast", "reshape"}
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([a-z\-]+)\((.*)$")
# an instruction of several results — a fusion that writes both runs'
# slices of a stack is one — and a result of it
_SEVERAL = re.compile(r"^\s*(?:ROOT )?%(\S+) = \((.*?)\) ([a-z\-]+)\((.*)$")
_RESULT = re.compile(r"(\w+)\[([\d,]*)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


@functools.lru_cache(maxsize=2)
def _computations(text):
    """An optimized module's computations, each the (result, dtype,
    dims, opcode, rest) of its instructions — one a result where an
    instruction has several — and the names of those a fusion calls."""
    bodies, body = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) \(.*\) -> .* \{$", line)
        if head:
            body = bodies.setdefault(head.group(1), [])
        elif line.startswith("}"):
            body = None
        elif body is not None and _INSTRUCTION.match(line):
            body.append(_INSTRUCTION.match(line).groups())
        elif body is not None and _SEVERAL.match(line):
            result, shapes, opcode, rest = _SEVERAL.match(line).groups()
            body.extend((result, dtype, dims, opcode, rest)
                        for dtype, dims in _RESULT.findall(shapes))
    return bodies, set(re.findall(r"fusion\(.*?calls=%([^\s,]+)", text))


def _placed(text, rows, widths):
    """Instructions of an optimized module that WRITE an array ``rows +
    (one of widths,)`` to HBM and compute nothing: a bare slice (static
    or dynamic), copy, pad or concatenate, or a fusion of nothing else,
    of one result or several — what XLA puts before a custom call that
    was handed a piece of an array, or a layout it does not read.  Only
    a computation's own instructions count: inside a fusion such an op
    moves nothing through HBM (a pad fused into a matrix product's
    operand is free)."""
    bodies, fused = _computations(text)
    found = []
    for name, instructions in bodies.items():
        if name in fused:
            continue
        for result, dtype, dims, opcode, rest in instructions:
            dims = tuple(int(n) for n in dims.split(",") if n)
            if dims[:-1] != rows or dims[-1] not in widths \
                    or dtype not in _BYTES:
                continue
            if opcode == "fusion":
                callee = re.search(r"calls=%([^\s,]+)", rest).group(1)
                inside = {op for *_, op, _ in bodies[callee]}
                if not (inside <= _IDLE and inside & _PLACES):
                    continue
            elif opcode not in _PLACES:
                continue
            scope = re.search(r'op_name="([^"]*)"', rest)
            found.append((result, opcode, dims[-1],
                          scope.group(1).rsplit("/", 2)[-2:] if scope
                          else None))
    return found


def _experts_placed(text, held, c, f, layers) -> list:
    """What of a routed kind's fp32 experts — gate | up ``[held, c, 2
    f]``, down ``[held, f, c]`` — an optimized module only MOVES: one
    layer's (what a kernel handed ``stack[layer]`` gets), a run's slice
    of ``1 .. layers`` of them, a whole stack's."""
    return [found for lead in [()] + [(n,) for n in range(1, layers + 1)]
            for found in (_placed(text, lead + (held, c), {2 * f})
                          + _placed(text, lead + (held, f), {c}))]


# the hybrid cell's rows, and the widths only its mixer has: d_inner,
# the convolution's channels, the in-projection
ROWS_8K, MIXER_WIDTHS = (2, 8192), {4096, 4352, 8512}


def _cell_step(mesh, name, conf=None, job=None):
    """A training cell's step as the benchmark builds it, compiled for
    ``mesh``; ``conf`` / ``job``: keys of the configuration / the traffic
    stated otherwise (another rung of a cut's ladder)."""
    import functools
    import operator
    from benchmark import harness, models
    from paddle_tpu.models.llama_pretrain import (
        init_adafactor_state, make_train_step, param_specs)
    cell = harness.find_cell(name)
    job, fam = dict(cell.traffic, **(job or {})), cell.family
    cfg = fam.build_cfg(dict(cell.conf, **(conf or {})), train=True, job=job)
    specs, shapes = param_specs(cfg, 1), fam.leaf_shapes(cfg)
    with mesh:
        params = models.tree_of(shapes, lambda path: _sds(
            mesh, shapes[path], cfg.param_dtype,
            functools.reduce(operator.getitem, path, specs)))
        opt = jax.tree_util.tree_map(
            lambda x: _sds(mesh, x.shape, x.dtype),
            jax.eval_shape(init_adafactor_state, params))
        step = make_train_step(cfg, mesh, lr=job["lr"],
                               weight_decay=job["weight_decay"],
                               optimizer=job["optimizer"])
        return step.lower(params, opt, _sds(
            mesh, (job["batch"], job["seq"] + 1), jnp.int64)).compile()


def _padded_from(text, rows, to_rows, width) -> list:
    """The ``pad`` instructions that write a bf16 ``[rows, width]`` array
    out again at ``to_rows`` rows."""
    return re.findall(rf"bf16\[{to_rows},{width}\]\S* pad\(.*"
                      rf"padding=0_{to_rows - rows}x0_0", text)


def _routing_sorts(text) -> tuple:
    """(forward, backward) ``sort`` instructions of the routed path — the
    router's ``top_k`` is one, the plan has two — by the loop their op
    path names: the backward loops hold the recompute."""
    paths = re.findall(r' sort\(.*op_name="([^"]*/moe_[^"]*)"', text)
    backward = sum("transpose(jvp(" in path for path in paths)
    return len(paths) - backward, backward
