"""Compile the main path's kernels and the serving programs for a
DESCRIBED TPU v5e, at the real 1.345B widths and at the cells' shapes,
with no chip attached.

The TPU compiler is installed wherever the tests run; it compiles for a
topology that is described, not attached, and refuses what the chip's
compiler would refuse (a lane slice Mosaic cannot prove aligned, a
kernel GSPMD cannot partition, a program over HBM).  Nothing runs, so
these say nothing about results or time — ``chip_smoke.py`` and
``tests/test_pallas_tpu.py`` do that on the chip.  The four training
cells' whole steps: ``tests/test_tpu_compile_cells.py`` and
``tests/test_expert_cells_compile.py`` (with the kernels that only the
two expert cells' shapes reach); the fixtures and the rules
all three keep: ``tests/_tpu_compile.py``.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from _tpu_compile import (HEAD_DIM, HEADS, HIDDEN, FFN, KERNEL,  # noqa: F401
                          MIXER_WIDTHS, PAGE, ROWS_8K, VOCAB, _cfg,
                          _padded_from, _param_sds, _placed, _sds, _text,
                          _two_kernels, compiled, one_chip, topo)


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
