"""Compile the main path's kernels and step programs for a DESCRIBED TPU
v5e, at the real 1.345B widths, with no chip attached.

The TPU compiler is installed wherever the tests run; it compiles for a
topology that is described, not attached, and refuses what the chip's
compiler would refuse (a lane slice Mosaic cannot prove aligned, a
kernel GSPMD cannot partition, a program over HBM).  Nothing runs, so
these say nothing about results or time — ``chip_smoke.py`` and
``tests/test_pallas_tpu.py`` do that on the chip.

Rules this file keeps (on-chip-measurement guide §2): ONE file; the
topology is described inside a module-scoped fixture that skips when it
cannot be (never at import, never in a ``skipif``/``parametrize``
argument, not in conftest, not autouse); every compile happens in the
test's own process.  Code that asks ``jax.default_backend()`` still sees
the CPU here, so the one place the kernels ask (``_common.interpret``)
is steered from the ``compiled`` fixture — not through an option of the
program.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

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


# ---------------------------------------------------------------------------
# kernels, at the shapes the main path gives them
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("nkv", [16, 4])
@pytest.mark.parametrize("kv_quant", [None, "int8"])
def test_paged_decode_attention(one_chip, compiled, kv_quant, nkv):
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_decode_attention, paged_decode_attention_q8)
    B, pages_max, num_pages = 32, 32, 257
    q = _sds(one_chip, (B, HEADS, HEAD_DIM), jnp.bfloat16)
    tables = _sds(one_chip, (B, pages_max), jnp.int32)
    lens = _sds(one_chip, (B,), jnp.int32)
    pool = (num_pages, nkv, PAGE, HEAD_DIM)
    if kv_quant == "int8":
        kp = _sds(one_chip, pool, jnp.int8)
        sc = _sds(one_chip, pool[:-1], jnp.float32)
        text = _text(paged_decode_attention_q8, q, kp, kp, sc, sc,
                     tables, lens)
    else:
        kp = _sds(one_chip, pool, jnp.bfloat16)
        text = _text(paged_decode_attention, q, kp, kp, tables, lens)
    assert KERNEL in text


def _flash_module():
    import importlib
    return importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def _two_kernels(monkeypatch):
    """Both one-pass budgets at 0 bytes (the module's constants): the
    two-kernel backward at any shape."""
    monkeypatch.setattr(_flash_module(), "ONE_PASS_DQ_BYTES", 0)
    monkeypatch.setattr(_flash_module(), "ONE_PASS_DKV_BYTES", 0)


@pytest.mark.parametrize("s,nkv,backward", [
    (2048, HEADS, ["dkv"]),     # MHA: the backward in one pass, key-major
    (4096, 8, ["dkv"]),         # 4 MiB of fp32 dQ a group: asks for more VMEM
    # 8 MiB: past that rule; 8 MiB of fp32 dK and dV a KV head are within
    # the second: ONE pass, query-major, under flash_bwd_dq's name
    (8192, 8, ["dq"]),
    (8192, 8, ["dq", "dkv"])])  # both rules at 0 bytes: the two kernels
def test_flash_attention_fwd_bwd(one_chip, compiled, monkeypatch, s, nkv,
                                 backward):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    if len(backward) == 2:
        _two_kernels(monkeypatch)
    q = _sds(one_chip, (2, s, HEADS, HEAD_DIM), jnp.bfloat16)
    kv = _sds(one_chip, (2, s, nkv, HEAD_DIM), jnp.bfloat16)
    text = _text(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, True).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)), q, kv, kv)
    assert text.count(KERNEL) == 1 + len(backward)
    for name in ("dq", "dkv"):
        assert (f"flash_bwd_{name}" in text) == (name in backward)


@pytest.mark.parametrize("kernels", [2, 3])
@pytest.mark.parametrize("s,d", [(192, 128), (576, 128), (320, 64),
                                 (24, 32)])
def test_flash_attention_small_blocks(one_chip, compiled, monkeypatch, s, d,
                                      kernels):
    """Lengths whose largest dividing block is under 128 (64, 64, 64, 8)
    stay on the kernels, GQA 4/2, causal and not, the backward in one
    pass (2 kernels) or, the VMEM rules set to 0 bytes, in two (3): the
    statistics are ``[b, h, s/block, 1, block]``, a block taken by its
    index on an untiled axis, so Mosaic is never asked to prove a lane
    offset of 64 aligned."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    if kernels == 3:
        _two_kernels(monkeypatch)
    q = _sds(one_chip, (2, s, 4, d), jnp.bfloat16)
    kv = _sds(one_chip, (2, s, 2, d), jnp.bfloat16)
    for causal in (True, False):
        text = _text(jax.grad(
            lambda q, k, v: flash_attention(q, k, v, causal).astype(
                jnp.float32).sum(), argnums=(0, 1, 2)), q, kv, kv)
        assert text.count(KERNEL) == kernels


def _moved(text, elements):
    """Instructions of a compiled module that only MOVE an array of at
    least ``elements`` elements: copies, transposes, the reshapes the
    TPU compiler could not make a bitcast, and broadcasts of one."""
    found = []
    for shape, opcode in re.findall(
            r"= \w+\[([\d,]+)\]\S* (copy|transpose|reshape|broadcast)\(",
            text):
        if np.prod([int(n) for n in shape.split(",")]) >= elements:
            found.append(f"{opcode} [{shape}]")
    return found


@pytest.mark.parametrize("kernels", [2, 3])
def test_flash_attention_gqa_reads_the_projections_where_they_lie(
        one_chip, compiled, monkeypatch, kernels):
    """The pretraining cell's shape, 16 query / 8 KV heads of 128 at 8 x
    2048, as the train step has it: the projections' ``[b, s, heads*d]``
    through rope and flash attention, forward and backward.  The flash
    kernels — ``flash_fwd`` and the one-pass ``flash_bwd_dkv``, with
    ``flash_bwd_dq`` ABSENT (2 MiB of fp32 dQ a group fits); present
    with the VMEM rules set to 0 bytes — and rope's, and NOTHING that
    moves a K/V-sized array between them: no transpose, no relayout
    copy or reshape, no GQA broadcast (the kernels take ``head //
    group``)."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.rope import fused_rope, rope_tables
    if kernels == 3:
        _two_kernels(monkeypatch)
    b, s, nkv = 8, 2048, 8
    q = _sds(one_chip, (b, s, HEADS * HEAD_DIM), jnp.bfloat16)
    kv = _sds(one_chip, (b, s, nkv * HEAD_DIM), jnp.bfloat16)

    def fwd_bwd(q, k, v, dout):
        def attn(q, k, v):
            cos, sin = rope_tables(s, HEAD_DIM)
            q = fused_rope(q.reshape(b, s, HEADS, HEAD_DIM), cos, sin)
            k = fused_rope(k.reshape(b, s, nkv, HEAD_DIM), cos, sin)
            v = v.reshape(b, s, nkv, HEAD_DIM)
            return flash_attention(q, k, v, True).reshape(b, s, -1)
        out, vjp = jax.vjp(attn, q, k, v)
        return (out,) + vjp(dout)

    text = _text(fwd_bwd, q, kv, kv, q)
    for kernel in ("flash_fwd", "flash_bwd_dkv", "rope"):
        assert kernel in text, kernel
    assert ("flash_bwd_dq" in text) == (kernels == 3)
    assert text.count(KERNEL) == kernels + 4    # rope: q, k, dq, dk
    assert not _moved(text, b * s * nkv * HEAD_DIM), \
        _moved(text, b * s * nkv * HEAD_DIM)


@pytest.mark.parametrize("T,nkv", [(2048, 16), (2048, 4), (64, 16)])
def test_flash_varlen_segmented_fwd_bwd(one_chip, compiled, T, nkv):
    """T=2048: the packed-pretrain / long-prefill shape, MHA and GQA.
    T=64: the engine's smallest packed-prefill bucket — ONE block under
    128 lanes, which Mosaic refused until the segment-id row was read
    whole ("cannot statically prove that index in dimension 2 is a
    multiple of 128")."""
    from paddle_tpu.ops.pallas import flash_varlen
    q = _sds(one_chip, (1, T, HEADS, HEAD_DIM), jnp.bfloat16)
    kv = _sds(one_chip, (1, T, nkv, HEAD_DIM), jnp.bfloat16)
    seg = _sds(one_chip, (1, T), jnp.int32)
    before = flash_varlen.dense_fallback_count
    text = _text(jax.grad(
        lambda q, k, v, s: flash_varlen.flash_attention_segmented(
            q, k, v, s, causal=True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)), q, kv, kv, seg)
    assert text.count(KERNEL) >= 2
    assert flash_varlen.dense_fallback_count == before


@pytest.mark.parametrize("N", [FFN, VOCAB])
def test_int8_matmul(one_chip, compiled, N):
    from paddle_tpu.ops.pallas.int8_matmul import int8_matmul
    text = _text(int8_matmul,
                 _sds(one_chip, (32, HIDDEN), jnp.bfloat16),
                 _sds(one_chip, (HIDDEN, N), jnp.int8),
                 _sds(one_chip, (N,), jnp.float32))
    assert KERNEL in text


# ---------------------------------------------------------------------------
# the jitted programs around them (depth cut; widths real)
# ---------------------------------------------------------------------------
def _pools(cfg, mesh, num_pages, spec=P()):
    return _sds(mesh, (cfg.num_hidden_layers, num_pages,
                       cfg.num_key_value_heads, PAGE, cfg.head_dim),
                jnp.bfloat16, spec)


def test_engine_decode_step(one_chip, compiled):
    from paddle_tpu.models.paged_decode import make_paged_decode_step
    cfg = _cfg(2, train=False)
    B = 32
    pool = _pools(cfg, one_chip, 128)
    text = make_paged_decode_step(cfg, 0.0).lower(
        _param_sds(cfg, one_chip), pool, pool,
        _sds(one_chip, (B, 32), jnp.int32), _sds(one_chip, (B,), jnp.int32),
        _sds(one_chip, (B,), jnp.int64),
        _sds(one_chip, (2,), jnp.uint32)).compile().as_text()
    assert KERNEL in text


@pytest.mark.parametrize("T", [64, 512, 2048])
def test_engine_packed_prefill(one_chip, compiled, T):
    """Every packed bucket is prefill_bucket (= page 64) times a power
    of two; none may quietly lose the segmented kernel to the dense
    path.  64 and 2048 are the two waves chip_smoke.py's server phase
    makes (the fixed prompt alone, then seven prompts together)."""
    from paddle_tpu.models.paged_decode import _prefill_packed
    cfg = _cfg(2, train=False)
    pool = _pools(cfg, one_chip, 128)
    i32 = _sds(one_chip, (T,), jnp.int32)
    flag = _sds(one_chip, (T,), jnp.bool_)
    dummy = _sds(one_chip, (1,), jnp.float32)
    text = _prefill_packed(cfg, False, False).lower(
        _param_sds(cfg, one_chip), _sds(one_chip, (1, T), jnp.int64),
        _sds(one_chip, (1, T), jnp.int32), _sds(one_chip, (1, T), jnp.int32),
        pool, pool, dummy, dummy, i32, i32, flag, i32,
        flag).compile().as_text()
    assert KERNEL in text


def test_tp_decode_step_has_cross_device_all_reduce(topo, compiled):
    from paddle_tpu.models.llama_pretrain import build_mesh
    from paddle_tpu.models.paged_decode import make_paged_decode_step_tp
    mesh = build_mesh(mp=4, devices=topo.devices)
    cfg = _cfg(2, train=False)
    B = 32
    pool = _pools(cfg, mesh, 128, P(None, None, "mp", None, None))
    text = make_paged_decode_step_tp(cfg, mesh, 0.0).lower(
        _param_sds(cfg, mesh), pool, pool,
        _sds(mesh, (B, 32), jnp.int32), _sds(mesh, (B,), jnp.int32),
        _sds(mesh, (B,), jnp.int64),
        _sds(mesh, (2,), jnp.uint32)).compile().as_text()
    assert KERNEL in text
    assert "all-reduce" in text and "replica_groups={{0,1,2,3}}" in text


@pytest.mark.parametrize("nkv", [HEADS, 8])
def test_train_step_dp2_mp2_sequence_parallel(topo, compiled, nkv):
    """The multi-chip train step with the Pallas kernels ON and the
    sequence-parallel constraint ON.  GSPMD refuses to partition a
    Mosaic kernel ("Mosaic kernels cannot be automatically
    partitioned"), so rope and flash run per shard
    (``llama_pretrain._per_shard``); the SP constraint follows the
    MESH's platform, so it is compiled here although the host is a
    CPU.  ``nkv`` 8: GQA, the heads split over ``mp`` 16/8 -> 8/4 a
    shard and the kernels keep the group ratio."""
    from paddle_tpu.models.llama_pretrain import (
        build_mesh, init_adafactor_state, make_train_step)
    mesh = build_mesh(dp=2, mp=2, devices=topo.devices)
    cfg = _cfg(1, train=True, sequence_parallel=True, nkv=nkv)
    with mesh:
        params = _param_sds(cfg, mesh)
        opt = jax.tree_util.tree_map(
            lambda x: _sds(mesh, x.shape, x.dtype),
            jax.eval_shape(init_adafactor_state, params))
        step = make_train_step(cfg, mesh, lr=1e-2, optimizer="adafactor")
        compiled_step = step.lower(
            params, opt,
            _sds(mesh, (8, 2049), jnp.int64, P("dp", None))).compile()
    text = compiled_step.as_text()
    assert text.count(KERNEL) >= 3           # rope + flash fwd/bwd
    assert "all-reduce" in text or "reduce-scatter" in text
    per_device = compiled_step.memory_analysis().argument_size_in_bytes
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    assert per_device < 0.75 * 4 * n_params   # sharded, not replicated


# ---------------------------------------------------------------------------
# layers by kind: the hybrid cell's kernels and step, at the cell's shapes
# ---------------------------------------------------------------------------
# opcodes that only place data, and what may stand beside them in a fusion
# that still computes nothing (a cotangent's pad-and-add among them)
_PLACES = {"slice", "copy", "pad", "concatenate"}
_IDLE = _PLACES | {"parameter", "constant", "bitcast", "convert", "add",
                   "tuple", "get-tuple-element", "broadcast", "reshape"}
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([a-z\-]+)\((.*)$")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def _placed(text, rows, widths):
    """Instructions of an optimized module that WRITE an array ``rows +
    (one of widths,)`` to HBM and compute nothing: a bare slice, copy,
    pad or concatenate, or a fusion of nothing else — what XLA puts
    before a custom call that was handed a piece of an array, or a
    layout it does not read.  Only a computation's own instructions
    count: inside a fusion such an op moves nothing through HBM (a pad
    fused into a matrix product's operand is free)."""
    bodies, body = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) \(.*\) -> .* \{$", line)
        if head:
            body = bodies.setdefault(head.group(1), [])
        elif line.startswith("}"):
            body = None
        elif body is not None and _INSTRUCTION.match(line):
            body.append(_INSTRUCTION.match(line).groups())
    fused = set(re.findall(r"fusion\(.*?calls=%([^\s,]+)", text))
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


def test_placed_counts_what_is_written_and_computes_nothing():
    """The counter on a module made by hand: a bare slice and a
    pad-and-add fusion count, a pad inside a fusion that multiplies and
    one fused into another fusion's body do not, nor another shape."""
    text = """
%pads (p0: bf16[2,64,128], p1: bf16[2,64,256]) -> bf16[2,64,256] {
  %p0 = bf16[2,64,128]{2,1,0} parameter(0)
  %c = bf16[] constant(0)
  %pad.1 = bf16[2,64,256]{2,1,0} pad(%p0, %c), padding=0_0x0_0x0_128
  %p1 = bf16[2,64,256]{2,1,0} parameter(1)
  ROOT %add.1 = bf16[2,64,256]{2,1,0} add(%pad.1, %p1)
}

%scaled (p0: bf16[2,64,128]) -> bf16[2,64,256] {
  %p0 = bf16[2,64,128]{2,1,0} parameter(0)
  %c = bf16[] constant(0)
  %pad.2 = bf16[2,64,256]{2,1,0} pad(%p0, %c), padding=0_0x0_0x0_128
  ROOT %mul.1 = bf16[2,64,256]{2,1,0} multiply(%pad.2, %pad.2)
}

ENTRY %main (a: bf16[2,64,512], b: bf16[2,64,128]) -> bf16[2,64,256] {
  %a = bf16[2,64,512]{2,1,0} parameter(0)
  %b = bf16[2,64,128]{2,1,0} parameter(1)
  %slice.1 = bf16[2,64,256]{2,1,0} slice(%a), slice={[0:2], [0:64], [0:256]}, metadata={op_name="jit(f)/ssm_in_proj/slice"}
  %other = bf16[4,64,256]{2,1,0} slice(%a), slice={[0:2], [0:64], [0:256]}
  %f.1 = bf16[2,64,256]{2,1,0} fusion(%b, %slice.1), kind=kLoop, calls=%pads, metadata={op_name="jit(f)/ssm_conv/add_any"}
  ROOT %f.2 = bf16[2,64,256]{2,1,0} fusion(%b), kind=kLoop, calls=%scaled
}
"""
    assert _placed(text, (2, 64), {256}) == [
        ("slice.1", "slice", 256, ["ssm_in_proj", "slice"]),
        ("f.1", "fusion", 256, ["ssm_conv", "add_any"])]


# the hybrid cell's rows, and the widths only its mixer has: d_inner,
# the convolution's channels, the in-projection
ROWS_8K, MIXER_WIDTHS = (2, 8192), {4096, 4352, 8512}


@pytest.mark.parametrize("in_place", [False, True])
def test_ssd_scan_kernels_fwd_bwd(one_chip, compiled, in_place):
    """``ssd_scan_fwd`` / ``ssd_scan_bwd`` at 2 x 8192 positions, 64
    heads of 64, state 128, chunk 256: both lower through Mosaic, and no
    ``[256, 256]`` matrix of a head is an array of the program.  In
    place: x, B and C inside the convolution's ``[2, 8192, 4352]``, x
    read again by a skip — and nothing of that size is sliced, copied,
    padded or joined around the two kernels."""
    from paddle_tpu.ops.ssd_scan import ssd_scan, ssd_scan_xbc
    b, s, h, p, n = 2, 8192, 64, 64, 128
    dt = _sds(one_chip, (b, s, h), jnp.float32)
    A = _sds(one_chip, (h,), jnp.float32)
    if in_place:
        def loss(xbc, dt, A):
            y, x = ssd_scan_xbc(xbc, dt, A, n, 256)
            return jnp.square((y + x).astype(jnp.float32)).sum()
        args = (_sds(one_chip, (b, s, h * p + 2 * n), jnp.bfloat16), dt, A)
    else:
        loss = lambda *a: jnp.square(ssd_scan(*a, chunk=256).astype(
            jnp.float32)).sum()
        args = (_sds(one_chip, (b, s, h, p), jnp.bfloat16), dt, A,
                _sds(one_chip, (b, s, n), jnp.bfloat16),
                _sds(one_chip, (b, s, n), jnp.bfloat16))
    text = _text(jax.value_and_grad(loss, argnums=tuple(range(len(args)))),
                 *args)
    assert text.count(KERNEL) == 2
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text
    assert not re.search(r"\[[\d,]*256,256\]", text)
    if in_place:
        assert not _placed(text, ROWS_8K, MIXER_WIDTHS)


@pytest.mark.parametrize("width,offset", [(4352, 0), (8512, 4096)])
def test_causal_conv_kernels_fwd_bwd(one_chip, compiled, width, offset):
    """The cell's 4352 channels, alone and where the in-projection
    leaves them: lane tiles 32..65 of ``[2, 8192, 8512]``."""
    from paddle_tpu.ops.pallas.causal_conv import causal_conv_silu
    args = (_sds(one_chip, (2, 8192, width), jnp.bfloat16),
            _sds(one_chip, (4352, 4), jnp.float32),
            _sds(one_chip, (4352,), jnp.float32))
    text = _text(jax.value_and_grad(
        lambda *a: jnp.square(causal_conv_silu(*a, offset).astype(
            jnp.float32)).sum(), argnums=(0, 1, 2)), *args)
    assert text.count(KERNEL) == 2
    assert "causal_conv_fwd" in text and "causal_conv_bwd" in text


@pytest.mark.parametrize("kernels", [2, 3])
def test_flash_attention_8k_head_dim_64(one_chip, compiled, monkeypatch,
                                        kernels):
    """The hybrid cell's one attention layer: 32 query / 8 KV heads of
    64 at S 8192 — the transposed entry; 8 MiB of fp32 dQ a group is past
    the key-major rule and 8 MiB of fp32 dK and dV a KV head (a 64-wide
    row fills a lane tile) within the query-major one: ONE pass under
    ``flash_bwd_dq``'s name — or, both rules at 0 bytes, the two
    kernels."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    if kernels == 3:
        _two_kernels(monkeypatch)
    q = _sds(one_chip, (2, 8192, 32, 64), jnp.bfloat16)
    kv = _sds(one_chip, (2, 8192, 8, 64), jnp.bfloat16)
    text = _text(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, True).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)), q, kv, kv)
    assert text.count(KERNEL) == kernels and "flash_bwd_dq" in text
    assert ("flash_bwd_dkv" in text) == (kernels == 3)


def _cell_step(mesh, name):
    """A training cell's step as the benchmark builds it, compiled for
    ``mesh``."""
    import functools
    import operator
    from benchmark import harness, models
    from paddle_tpu.models.llama_pretrain import (
        init_adafactor_state, make_train_step, param_specs)
    cell = harness.find_cell(name)
    job, fam = cell.traffic, cell.family
    cfg = fam.build_cfg(cell.conf, train=True, job=job)
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


def test_train_step_by_kind_at_the_hybrid_cell_s_shapes(one_chip, compiled):
    """The step of ``granite-4.0-h-micro.pretrain-8k`` as the benchmark
    builds it — depth 10 (five state-space layers, one attention layer,
    four more), every published width, 2 x 8192 tokens — fits a
    described v5e with no compiler rematerialization, runs the scan and
    the convolution as kernels, holds no ``[256, 256]`` matrix, and
    between the in-projection and the scan writes no array of the
    mixer's that computes nothing (forward, recompute, backward: 8 a
    layer before the kernels took offsets, 16 in this text).  The one
    attention layer's ``flash_fwd`` runs once: full remat keeps its
    outputs (69 MB, within ``FLASH_KEPT_BYTES``), and its backward is
    ONE pass since PR 45 (query-major, ``flash_bwd_dq``'s name;
    ``flash_bwd_dkv`` is absent)."""
    from benchmark import harness
    cell = harness.find_cell("granite-4.0-h-micro.pretrain-8k")
    assert cell.conf["num_hidden_layers"] == 10 and \
        (cell.traffic["batch"], cell.traffic["seq"]) == ROWS_8K
    c = _cell_step(one_chip, cell.name)
    text = c.as_text()
    for kernel in ("ssd_scan_fwd", "ssd_scan_bwd", "causal_conv_fwd",
                   "causal_conv_bwd", "flash_fwd", "flash_bwd_dq"):
        assert kernel in text, kernel
    assert "flash_bwd_dkv" not in text
    assert text.count(KERNEL) == 14
    assert ".remat" not in text
    assert not re.search(r"\[[\d,]*256,256\]", text)
    assert not _placed(text, ROWS_8K, MIXER_WIDTHS), \
        _placed(text, ROWS_8K, MIXER_WIDTHS)
    ma = c.memory_analysis()
    assert ma.argument_size_in_bytes == 3_813_571_072
    assert ma.temp_size_in_bytes <= 10_729_414_144      # PR 31's


@pytest.mark.parametrize("kernels", [2, 3])
def test_flash_attention_split_at_the_expert_cell_s_shapes(one_chip,
                                                           compiled,
                                                           monkeypatch,
                                                           kernels):
    """Latent attention at 2 x 8192, 32 heads of 128 | 64 | 128: Mosaic
    takes the two operand pairs, the shared 64-wide key whole, and the
    VMEM the whole-row operands ask for; five gradients from TWO
    kernels — a head's fp32 dQ is 4 MiB, ``ONE_PASS_DQ_BYTES`` exactly,
    so ``flash_bwd_dkv`` sums dQ and dQ2 too (40 MiB of VMEM asked) and
    ``flash_bwd_dq`` is absent — or, the rule set to 0 bytes, from the
    three a longer row keeps."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_split
    b, s = ROWS_8K
    assert s * 128 * 4 == _flash_module().ONE_PASS_DQ_BYTES
    if kernels == 3:
        monkeypatch.setattr(_flash_module(), "ONE_PASS_DQ_BYTES", 0)
    wide = _sds(one_chip, (b, s, 32, 128), jnp.bfloat16)
    text = _text(jax.grad(
        lambda *a: flash_attention_split(*a, 0.1).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4)), wide,
        _sds(one_chip, (b, s, 32, 64), jnp.bfloat16), wide,
        _sds(one_chip, (b, s, 64), jnp.bfloat16), wide)
    assert text.count(KERNEL) == kernels
    for kernel in ("flash_fwd", "flash_bwd_dkv"):
        assert kernel in text
    assert ("flash_bwd_dq" in text) == (kernels == 3)


@pytest.mark.parametrize("held,kernels,conditionals", [(8, 16, 2),
                                                       (64, 8, 0)])
def test_routed_experts_at_the_expert_cell_s_shapes(one_chip, compiled,
                                                    held, kernels,
                                                    conditionals):
    """Plan, forward and backward of ``ops/moe.routed_ffn`` alone at
    16,384 tokens of 3584, experts of 1024, top-4 of 64: with 8 held
    the path is built on BOTH bounds (3 + 5 kernels each, one
    ``conditional`` forward and one backward), with all 64 held on the
    one there is.  Mosaic takes the token side's kernel at both sizes of
    its slots.  What the forward keeps of the gate | up product has the
    67,584 rows of any load, and the load's bound writes its 18,432 there
    from the kernel (PR 46): nothing pads it."""
    from paddle_tpu.ops import moe
    T, C, F, K, PUB = 16384, 3584, 1024, 4, 64

    def f(x, gate, wgu, wd, idx, co):
        p = moe.plan(idx, 0, held, PUB)
        y, vjp = jax.vjp(lambda *a: moe.routed_ffn(*a, p), x, gate, wgu, wd)
        return (y,) + vjp(co)
    text = _text(f, _sds(one_chip, (T, C), jnp.bfloat16),
                 _sds(one_chip, (T, K), jnp.float32),
                 _sds(one_chip, (held, C, 2 * F), jnp.float32),
                 _sds(one_chip, (held, F, C), jnp.float32),
                 _sds(one_chip, (T, K), jnp.int32),
                 _sds(one_chip, (T, C), jnp.bfloat16))
    assert text.count(KERNEL) == kernels
    assert len(re.findall(r" conditional\(", text)) == conditionals
    for kernel in ("grouped_mm", "grouped_mm_dw", "moe_sum_pairs"):
        assert kernel in text, kernel
    assert ("bf16[18432,3584]" in text) == (held == 8)
    assert not _padded_from(text, 18432, 67584, 2 * F)


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


def test_mixer_kernels_at_the_expert_cell_s_shapes(one_chip, compiled):
    """One sublayer of the four residual streams, forward and backward,
    at 2 x 8192 x (4 x 3584): the four kernels of ``ops/pallas/hc_mix``
    compile within the VMEM they ask for, and the maps' few numbers a token lie with the TOKENS ON
    THE LANES through Sinkhorn's rounds (XLA would write the transposition
    out of the kernels' ``[T, 128]`` as a layout, an eighth of each vector
    register in use)."""
    import types
    from paddle_tpu.models import hybrid_trunk
    from paddle_tpu.ops.pallas import hc_mix
    n, c = 4, 3584
    cfg = types.SimpleNamespace(
        hc_mult=n, hidden_size=c, rms_norm_eps=1e-6, hc_sinkhorn_iters=20,
        hc_eps=1e-6, mhc_h_res_clamp_min=-30.0, mhc_h_res_clamp_max=30.0)
    x = _sds(one_chip, ROWS_8K + (n * c,), jnp.bfloat16)
    assert hc_mix.takes(x, n, c)
    bp = {"hc1_phi": _sds(one_chip, (n * c, n * n + 2 * n), jnp.float32),
          "hc1_alpha": _sds(one_chip, (3,), jnp.float32),
          "hc1_b": _sds(one_chip, (n * n + 2 * n,), jnp.float32)}

    def loss(bp, x, g):
        out = hybrid_trunk._hc_sublayer(
            bp, "hc1", x, lambda h: h * jnp.asarray(0.5, h.dtype), cfg)
        return jnp.sum((out * g).astype(jnp.float32))
    text = _text(jax.value_and_grad(loss, (0, 1)), bp, x, x)
    assert text.count(KERNEL) == 4
    for kernel in ("hc_pre_fwd", "hc_post_fwd", "hc_post_bwd", "hc_pre_bwd"):
        assert kernel in text, kernel
    assert len(re.findall(r"= f32\[16,16384\]\{1,0", text)) > 100
    assert not re.search(r"= f32\[16,16384\]\{0,1", text)


def test_train_step_of_the_expert_cell(one_chip, compiled):
    """The step of ``xing4.0-29b-a4b.pretrain-8k-moe`` as the benchmark
    builds it — a dense lead and four expert layers, every published
    width, 8 of 64 experts, 2 x 8192 tokens — fits a described v5e with
    NO compiler rematerialization (the test that chose the share: with 16
    experts and a quarter of the vocabulary it compiled with six
    ``.remat`` matrix products), runs attention and the grouped products
    as kernels, the mixers' passes over the four streams too, and holds
    no bf16 copy of an expert stack."""
    from benchmark import harness
    cell = harness.find_cell("xing4.0-29b-a4b.pretrain-8k-moe")
    assert cell.conf["num_hidden_layers"] == 5 and \
        (cell.traffic["batch"], cell.traffic["seq"]) == ROWS_8K
    c = _cell_step(one_chip, cell.name)
    text = c.as_text()
    for kernel in ("flash_fwd", "flash_bwd_dkv",
                   "grouped_mm", "grouped_mm_dw", "moe_sum_pairs"):
        assert kernel in text, kernel
    # the split form's backward is one pass at S 8192 (PR 42)
    assert "flash_bwd_dq" not in text
    # a dense lead: 2 flash (forward — full remat keeps its outputs, 5 x
    # 136 MB within ``FLASH_KEPT_BYTES``, so the recompute has none —
    # and the one-pass backward); an expert layer: 2 flash, and the routed
    # path ON EACH OF ITS TWO BOUNDS (18,432 rows where the load's tiles
    # fit them, 67,584 otherwise: one ``conditional`` a pass): 2 grouped
    # products + the token side's sum forward, the same recomputed (the
    # mixer's ``hc_post`` reads the sublayer's output), 2 products + 2 dw
    # + the sum backward
    # + the mixers (``ops/pallas/hc_mix.py``), in the lead's loop and in
    # the expert layers': two sublayers forward (``hc_pre_fwd``,
    # ``hc_post_fwd``: 4), the same recomputed but the last X', which
    # nothing reads again (3), ``hc_post_bwd`` and ``hc_pre_bwd`` of
    # each backward (4)
    assert text.count(KERNEL) == 4 + 2 * (3 + 3 + 5) + 2 * (4 + 3 + 4)
    for kernel in ("hc_pre_fwd", "hc_post_fwd", "hc_post_bwd",
                   "hc_pre_bwd"):
        assert kernel in text, kernel
    # no fp32 copy of the streams is an ARRAY of the program (inside a
    # fusion — the trunk's two ends sum and pad in fp32 — it is a value
    # on its way through registers)
    arrays = re.sub(r"(?m)^%fused_computation\S* .*\{\n(?:.*\n)*?\}\n", "",
                    text)
    assert "fused_computation" in text and len(arrays) < len(text)
    assert not re.search(r"= f32\[(2,8192|16384),14336\]", arrays)
    assert len(re.findall(r" conditional\(", text)) == 3
    for rows in (18432, 67584):
        assert f"bf16[{rows},3584]" in text
    # full remat keeps the routing (PR 46): the router's ``top_k`` and the
    # plan's two sorts are in the forward loop alone, and the recompute's
    # gate | up product is written where it is kept — no pad to the
    # bound of any load
    assert _routing_sorts(text) == (3, 0)
    assert not _padded_from(text, 18432, 67584, 2048)
    assert ".remat" not in text
    assert not re.search(r"bf16\[(4,)?8,3584,2048\]", text)
    ma = c.memory_analysis()
    assert ma.argument_size_in_bytes == 3_057_670_144
    # PR 43's reading with the outputs kept (15,981,031,936 without).
    # The figure is no allocation's size: the buffer assignment holds the
    # kept stacks once, and its one HBM temp allocation grew by
    # 392,691,712 B to 11,188,912,640 (PERF.md section 6); + 64,929,280
    # with the four layers' routing kept (PR 46: 17,408,482,816 before)
    assert ma.temp_size_in_bytes <= 17_473_412_096


@pytest.mark.parametrize("window,kernels", [
    (None, ("flash_fwd", "flash_bwd_dq")),
    (4096, ("flash_win_fwd", "flash_win_bwd_dq"))])
def test_flash_attention_16k_at_the_window_cell_s_shapes(one_chip, compiled,
                                                         window, kernels):
    """One row of 16,384 tokens, 28 query / 4 KV heads of 128: BOTH forms
    compile for a described v5e.  A head's K and V are 16 MiB resident
    with the pipeline's two buffers, past Mosaic's own limit, so the
    calls ask for what they hold (until PR 44 the dense forward stopped
    near 8k at d 128): the forward 16 + 8 MiB.  7 * 16384 * 128 * 4 B of
    fp32 dQ is past ``ONE_PASS_DQ_BYTES`` and 2 * 16384 * 128 * 4 B of
    fp32 dK and dV IS ``ONE_PASS_DKV_BYTES``: the query-major one pass —
    K and V, the dk and dv blocks (16 MiB each with two buffers), the
    two fp32 sums (16) and 8 for the tiles: 56 MiB asked, and
    ``flash_(win_)bwd_dkv`` is absent."""
    from paddle_tpu.ops.pallas.flash_attention import (ONE_PASS_DKV_BYTES,
                                                       flash_attention)
    assert 2 * 16384 * 128 * 4 == ONE_PASS_DKV_BYTES
    q = _sds(one_chip, (1, 16384, 28, 128), jnp.bfloat16)
    kv = _sds(one_chip, (1, 16384, 4, 128), jnp.bfloat16)
    text = _text(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, True, window=window).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)), q, kv, kv)
    assert text.count(KERNEL) == 2
    for kernel in kernels:
        assert kernel in text, kernel
    assert "bwd_dkv" not in text
    if window:
        assert "flash_bwd_dq" not in text
    asked = [int(n) for n in re.findall(
        KERNEL + r'".*"scoped_memory_configs":\[\{"memory_space":"1",'
        r'"offset":"0","size":"(\d+)"', text)]
    assert sorted(asked) == [(16 + 8) << 20, (16 + 16 + 16 + 8) << 20]


def test_train_step_of_the_window_cell(one_chip, compiled):
    """The step of ``smallthinker-21b-a3b.pretrain-16k-moe`` as the
    benchmark builds it — two periods of a global and three window
    layers, every published width, 16 of 64 experts, ONE row of 16,384
    tokens — fits a described v5e with NO compiler rematerialization at
    depth 8 (the issue's first choice; 4 was its fallback), runs the
    global layers on the dense kernels and the window layers on the
    windowed form, the query-major ONE-pass backward in both (since PR
    45: ``flash_(win_)bwd_dkv`` absent), and ``flash_fwd`` /
    ``flash_win_fwd`` once a layer: full remat keeps their outputs (8 x
    119 MB = 954 MB, within ``FLASH_KEPT_BYTES``)."""
    from benchmark import harness
    from paddle_tpu.models.llama_pretrain import keeps_flash_outputs
    cell = harness.find_cell("smallthinker-21b-a3b.pretrain-16k-moe")
    assert cell.conf["num_hidden_layers"] == 8 and \
        (cell.traffic["batch"], cell.traffic["seq"]) == (1, 16384)
    assert keeps_flash_outputs(1, 16384, 28, 128, jnp.bfloat16, 8)
    c = _cell_step(one_chip, cell.name)
    text = c.as_text()
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_win_fwd",
                   "flash_win_bwd_dq", "grouped_mm", "grouped_mm_dw",
                   "moe_sum_pairs", "rope"):
        assert kernel in text, kernel
    assert "bwd_dkv" not in text
    # four runs of layers (global, window x 3, global, window x 3), each a
    # forward loop and a backward loop.  A layer forward: flash 1 + the
    # routed path on each of its two bounds, 2 grouped products + the
    # token side's sum; backward: the recompute's gate | up product on
    # each bound (the routed path's backward reads that product alone;
    # attention's outputs are kept), flash's ONE backward kernel, and the
    # routed backward on each bound, 2 products + 2 dw + the sum; a window
    # layer rotates q and k: 2 rope kernels forward, 2 recomputed, 2
    # backward
    per_run = 1 + 2 * 3 + 2 * 1 + 1 + 2 * 5
    assert text.count(KERNEL) == 4 * per_run + 2 * 6 == 92
    assert len(re.findall(r" conditional\(", text)) == 4 * 3
    for rows in (53248, 102400):
        assert f"bf16[{rows},2560]" in text
    # full remat keeps the routing (PR 46): the sorts of the four runs'
    # routers and plans are in the forward loops alone, and no recompute
    # pads its gate | up product
    assert _routing_sorts(text) == (4 * 3, 0)
    assert not _padded_from(text, 53248, 102400, 1536)
    assert ".remat" not in text
    # no bf16 copy of an expert stack
    assert not re.search(r"bf16\[(\d+,)?16,2560,1536\]", text)
    ma = c.memory_analysis()
    assert ma.argument_size_in_bytes == 4_484_826_624
    # 12,977,658,368 B with the two-kernel backward (PR 44), 12,977,271,296
    # with the one pass (PR 45): the delta arrays are gone, the sums live
    # in VMEM; + 52,790,272 with the eight layers' routing kept (PR 46)
    assert ma.temp_size_in_bytes <= 13_030_061_568


# sha256 of the dense cell's optimized step at depth 18 with the debug
# locations out (op metadata, the kernels' serialized bodies, which
# carry source paths, and the tables of files and frames): PR 35's — the
# kernels' ``cost_estimate`` is in the custom calls' backend config, and
# with it XLA places other arrays in its fast memory space (PERF.md §6;
# PR 31's was 96a31f47...047cd5)
DENSE_STEP_DIGEST = \
    "c1884670364c4b0226b6deb2a9d010fae7e0cfd3503d727ea5d831a5735a5577"


def test_dense_cell_step_is_the_recorded_program(one_chip, compiled):
    """``internlm2-1.8b.pretrain-2k`` runs no line of the state-space
    modules: its optimized HLO is, debug locations apart, the text whose
    digest is recorded above.  A PR that MEANS to change the dense
    cell's program records the new digest, and says so in PERF.md."""
    import hashlib
    text = _cell_step(one_chip, "internlm2-1.8b.pretrain-2k").as_text()
    assert text.count(KERNEL) == 9 and ".remat" not in text
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r'"body":"[^"]*"', '"body":""', text)
    text = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(?:.*\n)*?\n", "", text, flags=re.M)
    assert hashlib.sha256(text.encode()).hexdigest() == DENSE_STEP_DIGEST
