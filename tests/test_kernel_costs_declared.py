"""A kernel's work is its ``cost_estimate`` (docs/OBSERVABILITY.md,
"Profiler spans and scopes", the third rule): every ``pallas_call`` site
declares the FLOPs, HBM bytes and transcendentals it EXECUTES, from the
static values that build its grid and BlockSpecs.  ``cost_estimate`` is
a parameter of the ``pallas_call`` equation, so it is read off the jaxpr
on the CPU; nothing runs.  The numbers here are worked out by hand from
each kernel's grid, in the comments."""

import importlib

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu  # noqa: F401  (x64 before any array)
from paddle_tpu.ops.pallas.causal_conv import (causal_conv_silu,
                                               short_conv_gated)
from paddle_tpu.ops.pallas.flash_attention import (flash_attention,
                                                   flash_attention_split)
from paddle_tpu.ops.pallas.flash_varlen import flash_attention_segmented
from paddle_tpu.ops.pallas.fused_adamw import fused_adamw
from paddle_tpu.ops.pallas.grouped_mm import grouped_mm, grouped_mm_dw
from paddle_tpu.ops.pallas.hc_mix import (hc_post_bwd, hc_post_fwd,
                                          hc_pre_bwd, hc_pre_fwd)
from paddle_tpu.ops.pallas.int8_matmul import int8_matmul
from paddle_tpu.ops.pallas.kda_chunk import kda_chunked
from paddle_tpu.ops.pallas.moe_sum_pairs import moe_sum_pairs
from paddle_tpu.ops.pallas.paged_attention import (
    paged_decode_attention, paged_decode_attention_q8)
from paddle_tpu.ops.pallas.rms_norm import rms_norm
from paddle_tpu.ops.pallas.rope import fused_rope
from paddle_tpu.ops.pallas.ssd_scan import ssd_chunked, ssd_chunked_xbc

BF16, F32 = jnp.bfloat16, jnp.float32


def _calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _calls(inner)


def declared(name, fn, *args):
    """``(flops, bytes_accessed, transcendentals)`` of the one
    ``pallas_call`` called ``name`` in the trace of ``fn(*args)``."""
    found = [e.params["cost_estimate"]
             for e in _calls(jax.make_jaxpr(fn)(*args).jaxpr)
             if e.params["name"] == name]
    assert len(found) == 1, (name, len(found))
    c = found[0]
    assert c is not None, f"{name} declares no cost_estimate"
    return c.flops, c.bytes_accessed, c.transcendentals


def z(shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


def grad_of(fn, n=1):
    """fn's vjp with respect to its first ``n`` arguments, pulled back
    from ones: the backward kernels are in its trace."""
    def run(*args):
        out, pull = jax.vjp(lambda *a: fn(*a, *args[n:]), *args[:n])
        return pull(jax.tree_util.tree_map(jnp.ones_like, out))
    return run


def two_kernels(fn):
    """``fn`` traced with both one-pass budgets at 0 bytes (the module
    constants, as the parity tests set them): the backward a row past
    both budgets runs."""
    def run(*args):
        with pytest.MonkeyPatch.context() as patch:
            flash = importlib.import_module(
                "paddle_tpu.ops.pallas.flash_attention")
            patch.setattr(flash, "ONE_PASS_DQ_BYTES", 0)
            patch.setattr(flash, "ONE_PASS_DKV_BYTES", 0)
            return fn(*args)
    return run


I32, I8 = jnp.int32, jnp.int8
_causal = lambda q, k, v: flash_attention(q, k, v, causal=True)
_window = lambda q, k, v: flash_attention(q, k, v, causal=True, window=512)
_split = lambda q, q2, k, k2, v: flash_attention_split(q, q2, k, k2, v, 0.1)
_varlen = lambda q, k, v, seg: flash_attention_segmented(q, k, v, seg,
                                                         causal=True)
_xbc = lambda xbc, dt, cum: ssd_chunked_xbc(xbc, dt, cum, 128)
_conv = lambda x, w, b: causal_conv_silu(x, w, b, 256)
_kda = lambda qkv, g, beta: kda_chunked(qkv, g, beta, 64)
_paged = lambda *a: paged_decode_attention(*a, force_kernel=True)
_paged_q8 = lambda *a: paged_decode_attention_q8(*a, force_kernel=True)

# one row of 2,048 tokens, blocks of 512: 4 + 3 + 2 + 1 = 10 pairs a head
Q2, Q8, KV1 = z((1, 2048, 2, 128)), z((1, 2048, 8, 128)), z((1, 2048, 1, 128))
SPLIT = (z((1, 1024, 2, 128)), z((1, 1024, 2, 64)), z((1, 1024, 2, 128)),
         z((1, 1024, 64)), z((1, 1024, 2, 128)))
SPLIT_16K = (z((1, 16384, 1, 128)), z((1, 16384, 1, 64)),
             z((1, 16384, 1, 128)), z((1, 16384, 64)), z((1, 16384, 1, 128)))
VARLEN = (z((1, 1024, 4, 128)), z((1, 1024, 2, 128)), z((1, 1024, 2, 128)),
          z((1, 1024), I32))
SSD = (z((1, 2, 256, 4, 64)), z((1, 2, 256, 4), F32), z((1, 2, 256, 4), F32),
       z((1, 2, 256, 128)), z((1, 2, 256, 128)))
XBC = (z((1, 512, 512)), z((1, 2, 256, 4), F32), z((1, 2, 256, 4), F32))
CONV = (z((2, 1024, 768)), z((512, 4), F32), z((512,), F32))
GATED = (z((2, 1024, 768)), z((256, 3), F32))
# one row of 512 positions, two heads of 128: two blocks of four chunks
KDA = (z((1, 512, 768)), z((1, 512, 256), F32), z((1, 512, 2), F32))
POOL = z((64, 2, 16, 128))
PAGED = (z((4, 8, 128)), POOL, POOL, z((4, 6), I32), z((4,), I32))
PAGED_Q8 = (z((4, 8, 128)), z(POOL.shape, I8), z(POOL.shape, I8),
            z((64, 2, 16), F32), z((64, 2, 16), F32), z((4, 6), I32),
            z((4,), I32))

# 256 tokens (two tiles) of 4 streams of 256: rows of 1,024; the maps'
# numbers a token in [256, 128] fp32
HC_X, HC_Y, HC_M = z((256, 1024)), z((256, 256)), z((256, 128), F32)
HC_PHI = (z((1024, 24), F32), z((), F32), z((24,), F32))
_hc_pre_fwd = lambda x, phi, a, b: hc_pre_fwd(x, phi, a, b, 4, 1e-6)
_hc_post_fwd = lambda x, y, m: hc_post_fwd(x, y, m, 4)
_hc_post_bwd = lambda g, x, y, m: hc_post_bwd(g, x, y, m, 4)
_hc_pre_bwd = lambda g, x, dh, mr, dmr, m, phi, a, b: hc_pre_bwd(
    g, x, dh, mr, dmr, m, phi, a, b, 4)

# (case, kernel name, fn, args, flops, bytes_accessed, transcendentals)
CASES = [
    # 2 heads x 10 pairs of [512, 512] scores, two 128-deep products and
    # 4 passes: 20 * 512^2 * (4 * 128 + 4); bytes 2 * 2048 * (2 * 256 q,
    # o + 2 * 128 K, V once the GROUP) + 4 * 2 * 2048 lse; exps 20 * 512 *
    # 513 and 2 * 2048 logs
    ("flash_fwd", "flash_fwd", _causal, (Q2, KV1, KV1),
     2_705_326_080, 3_162_112, 5_257_216),
    # a group of 2 at S 2,048 holds its fp32 dQ in 2 MiB: ONE pass, five
    # products a pair: 20 * 512^2 * (10 * 128 + 5) + delta 2 * 2 * 2048 *
    # 128; q, dO, o and lse of a head again at each of the 1 * 4 * 2 grid
    # steps: 8 * 2048 * (2 * 384 + 4), + 2 * 2048 * (4 * 128 K V dk dv +
    # 256 dq)
    ("flash_bwd_dkv, one pass", "flash_bwd_dkv", grad_of(_causal, 3),
     (Q2, KV1, KV1), 6_738_149_376, 15_794_176, 5_242_880),
    # a group of 8 holds 8 MiB of dQ, past the key-major budget, and 2 MiB
    # of fp32 dK and dV a KV head: ONE pass, QUERY-major, under
    # ``flash_bwd_dq``'s name — five products a pair, 80 * 512^2 * (10 *
    # 128 + 5) + delta 2 * 8 * 2048 * 128; K and V once the KV head, dk
    # and dv out once: 2 * 2048 * (4 * 1024 q o dO dq + 256 K V + 256 dk
    # dv) + lse 4 * 8 * 2048
    ("flash_bwd_dq, one pass", "flash_bwd_dq", grad_of(_causal, 3),
     (Q8, KV1, KV1), 80 * 512 ** 2 * 1285 + 4_194_304,
     2 * 2048 * (4096 + 512) + 65_536, 20_971_520),
    # past both budgets (here: both at 0 bytes) the two kernels; dq: three
    # products a pair, 80 * 512^2 * (6 * 128 + 5) + delta 2 * 8 * 2048 *
    # 128; bytes 2 * 2048 * (4 * 1024 q o dO dq + 256 K V) + 2 * 4 * 8 *
    # 2048
    ("flash_bwd_dq", "flash_bwd_dq", two_kernels(grad_of(_causal, 3)),
     (Q8, KV1, KV1), 16_215_179_264, 17_956_864, 20_971_520),
    # ... and dkv FOUR products a pair, not the one pass's five: 80 * 512^2
    # * (8 * 128 + 5); a head's q, dO, lse, delta at each of 4 * 8 grid
    # steps: 32 * 2048 * (2 * 256 + 8), + 2 * 2048 * 4 * 128
    ("flash_bwd_dkv, two kernels", "flash_bwd_dkv",
     two_kernels(grad_of(_causal, 3)),
     (Q8, KV1, KV1), 21_579_694_080, 36_175_872, 20_971_520),
    # THE WINDOWED FORM, a window of one 512-block on the same row: a q
    # block meets two k blocks at most, 1 + 2 + 2 + 2 = 7 pairs a head
    # where 10 are causal — both ends' blocks run whole, masked; the
    # whole-row operands are fetched as the dense form fetches them, so
    # the bytes are its.  Forward, 2 heads: 14 * 512^2 * (4 * 128 + 4);
    # exps 14 * 512 * 513 and 2 * 2048 logs
    ("flash_win_fwd", "flash_win_fwd", _window, (Q2, KV1, KV1),
     14 * 512 ** 2 * 516, 3_162_112, 14 * 512 * 513 + 4096),
    # a group of 8: the query-major one pass on the window's 56 pairs, 56
    # * 512^2 * (10 * 128 + 5) + delta; the dense form's bytes
    ("flash_win_bwd_dq, one pass", "flash_win_bwd_dq", grad_of(_window, 3),
     (Q8, KV1, KV1), 56 * 512 ** 2 * 1285 + 4_194_304,
     2 * 2048 * (4096 + 512) + 65_536, 56 * 512 ** 2),
    # past both budgets the two kernels: dq 56 * 512^2 * (6 * 128 + 5)
    # + delta 2 * 8 * 2048 * 128 ...
    ("flash_win_bwd_dq", "flash_win_bwd_dq",
     two_kernels(grad_of(_window, 3)),
     (Q8, KV1, KV1), 56 * 512 ** 2 * 773 + 4_194_304, 17_956_864,
     56 * 512 ** 2),
    # ... and dkv 56 * 512^2 * (8 * 128 + 5)
    ("flash_win_bwd_dkv", "flash_win_bwd_dkv",
     two_kernels(grad_of(_window, 3)),
     (Q8, KV1, KV1), 56 * 512 ** 2 * 1029, 36_175_872, 56 * 512 ** 2),
    # split scores, S 1,024: 2 heads x 3 pairs, a 64-deep product beside
    # the 128-deep one: 6 * 512^2 * (2 * (256 + 64) + 4); bytes 2 * 1024 *
    # (512 q o + 512 K V + 128 q2 + 64 k2 once) + 4 * 2 * 1024
    ("flash_fwd, split", "flash_fwd", _split, SPLIT,
     1_012_924_416, 2_498_560, 1_577_984),
    # a head's fp32 dQ at S 1,024 is 512 KiB: ONE pass, dS k and dS k2
    # beside the two-kernel form's six products: 6 * 512^2 * (2 * (5 * 128
    # + 3 * 64) + 5) + delta 2 * 2 * 1024 * 128; a group of ONE: a head's
    # rows stay, 2 visits of 1024 * (2 * (3 * 128 q dO o + 64 q2) + 4 lse),
    # + 2 * 1024 * (4 * 256 K V dk dv + 128 k2 + 2 * (128 + 64) dq dq2)
    # + dk2 in fp32 4 * 1024 * 2 * 64
    ("flash_bwd_dkv, split one pass", "flash_bwd_dkv", grad_of(_split, 5),
     SPLIT, 2_625_634_304, 5_513_216, 1_572_864),
    # the plain-MLA cell's row, ONE head of it: a head's fp32 dQ at S
    # 16,384 is 8 MiB, past the first budget, and dQ with dQ2 the second's
    # 16 MiB exactly — the SAME pass and the same declaration (PR 57).
    # 32 * 33 / 2 = 528 pairs: 528 * 512^2 * 1669 + delta 2 * 16384 * 128;
    # one visit of 16384 * (2 * (3 * 128 + 64) + 4) + 2 * 16384 * (4 * 128
    # + 64 + 192) + dk2 in fp32 4 * 16384 * 64
    ("flash_bwd_dkv, split one pass at 16k", "flash_bwd_dkv",
     grad_of(_split, 5), SPLIT_16K, 231_013_875_712, 44_105_728,
     138_412_032),
    # past both budgets (here: both at 0 bytes) the split form keeps
    # the two kernels.  6 * 512^2 * (2 * (384 + 128) + 5) + 2 * 2 * 1024 *
    # 128; bytes 2 * 1024 * (1024 + 512 + 2 * 128 q2 dq2 + 64) + 8 * 2 *
    # 1024
    ("flash_bwd_dq, split", "flash_bwd_dq",
     two_kernels(grad_of(_split, 5)), SPLIT,
     1_619_001_344, 3_817_472, 1_572_864),
    # 6 * 512^2 * (2 * (512 + 128) + 5); a group of ONE: a head's rows
    # stay, 2 visits of 1024 * (2 * (256 + 64) + 8), + 2 * 1024 * (4 * 256
    # + 128 k2) + dk2 in fp32 4 * 1024 * 2 * 64
    ("flash_bwd_dkv, split", "flash_bwd_dkv",
     two_kernels(grad_of(_split, 5)), SPLIT,
     2_021_130_240, 4_210_688, 1_572_864),
    # the bound: one segment a row.  4 heads x 3 pairs: 12 * 512^2 * 516;
    # bytes 2 * 1024 * 128 * (8 + 4) + ids, lse 4 * 1024 * (2 * 4 + 1)
    ("flash_varlen_fwd", "flash_varlen_fwd", _varlen, VARLEN,
     1_623_195_648, 3_182_592, 3_155_968),
    # 12 * 512^2 * (6 * 128 + 5); 2 * 1024 * 128 * (12 + 4) + 4 * 1024 * 13
    ("flash_varlen_bwd_dq", "flash_varlen_bwd_dq", grad_of(_varlen, 3),
     VARLEN, 2_431_647_744, 4_247_552, 3_145_728),
    # 12 * 512^2 * (8 * 128 + 5); 2 * 2 * 2 = 8 visits of 1024 * (512 + 8),
    # K V 2 * 2 * 2 * 1024 * 128, ids 4 * 1024 * 3, fp32 dk dv 8 * 2 * 1024
    # * 128
    ("flash_varlen_bwd_dkv", "flash_varlen_bwd_dkv", grad_of(_varlen, 3),
     VARLEN, 3_236_954_112, 7_417_856, 3_145_728),
    # 3 an element of [2, 1024, 4, 128]; in and out 2 * 2 MiB, the fp32
    # tables (2 * 256 KiB) once a batch row
    ("rope", "rope", fused_rope,
     (z((2, 1024, 4, 128)), z((1024, 64), F32), z((1024, 64), F32)),
     3_145_728, 5_242_880, 0),
    # [512, 1024]: 4 an element + 2 a row; bf16 in, fp32 out (w is fp32),
    # w, rstd
    ("rms_norm", "rms_norm", rms_norm, (z((512, 1024)), z((1024,), F32)),
     2_098_176, 3_151_872, 512),
    # 9 an element; x in and dx out bf16, dO fp32, w, rstd, [8, 1024] fp32
    ("rms_norm_bwd", "rms_norm_bwd", grad_of(rms_norm, 2),
     (z((512, 1024)), z((1024,), F32)), 4_718_592, 4_233_216, 0),
    # 2 chunks of 256, 4 heads of 64 (two a lane tile), state 128: a head
    # 2 * 128 * 256 * (256 + 256) + 4 * 256^2, a chunk 2 * 256^2 * 128;
    # bytes 2 * 512 * (2 * 256 x y + 256 B C) + 4 fp32 [512, 4] + states
    # 4 * 2 * 128 * 256
    ("ssd_scan_fwd", "ssd_scan_fwd", ssd_chunked, SSD,
     304_087_040, 1_081_344, 528_384),
    # a head 2 * 128 * 256 * (512 + 512) + 12 * 256^2, a chunk 3 products;
    # bytes 2 * 512 * (3 * 256 x dy dx + 512 B C dB dC) + 8 fp32 [512, 4]
    # + states
    ("ssd_scan_bwd", "ssd_scan_bwd", grad_of(ssd_chunked, 5), SSD,
     643_825_664, 1_638_400, 528_384),
    # x, B, C in one array: one tile more a grid step, what is owed to x
    ("ssd_scan_bwd, one array", "ssd_scan_bwd", grad_of(_xbc, 3), XBC,
     643_825_664, 1_900_544, 528_384),
    # 512 channels at offset 256 of [2, 1024, 768], 4 taps: (8 + 4) an
    # output; in and out 2 * 2 MiB, 2 tiles x 8 halo rows a batch row,
    # fp32 tables [9, 512] once a batch row
    ("causal_conv_fwd", "causal_conv_fwd", _conv, CONV,
     12_582_912, 4_263_936, 1_048_576),
    # (8 + 8) on 1,040 rows a row, (16 + 1) on its 1,024; x, g, dx; three
    # halos; tables; [2, 8, 512] fp32 sums
    ("causal_conv_bwd", "causal_conv_bwd", grad_of(_conv, 3), CONV,
     34_865_152, 6_459_392, 1_064_960),
    # B | Cg | X of 256 channels in [2, 1024, 768], 3 taps: B X, the taps
    # and the gate, 7 an output; four [2, 1024, 256] bf16 passes, 2 tiles x
    # 8 halo rows a batch row of B and of X, the fp32 taps [8, 256] once
    # (one channel tile)
    ("short_conv_fwd", "short_conv_fwd", short_conv_gated, GATED,
     3_670_016, 4_235_264, 0),
    # 21 an element of a tile's own rows (the convolution again, g Cg,
    # what the taps hand back, dB dCg dX, dw's sums), 1 on the 8 rows after
    # each of 4 tiles; seven passes, four halos, the taps, [2, 8, 256] fp32
    # sums
    ("short_conv_bwd", "short_conv_bwd", grad_of(short_conv_gated, 2), GATED,
     11_018_240, 7_430_144, 0),
    # 16 (head, chunk)s of Q 64, K 128: 9 products of 2 Q^2 K (two an
    # anchor x 3, g's running sum, T on [Q, 2 K]), 4 of 2 Q^3 and the
    # blocks' spread 2 Q^2 16 (the inverse), 3 of 2 Q K^2 and P N,
    # 20,244,480 with the 140 passes over [Q, K] and the 15 eliminations'
    # 6 over [Q, 16]; 24 exps a key channel a position + 128; q, k, v, o
    # bf16 + g fp32 = 12 B a channel, beta [512, 2], four states
    ("kda_chunk_fwd", "kda_chunk_fwd", _kda, KDA,
     323_911_680, 1_839_104, 3_147_776),
    # that forward again and twice more; seven bf16 tiles, g and dg,
    # beta and d beta, the states
    ("kda_chunk_bwd", "kda_chunk_bwd", grad_of(_kda, 3), KDA,
     971_735_040, 3_153_920, 3_147_776),
    # the bound it is launched at: ALL 8 tiles of 256 rows; x once a
    # 1024-column panel (2), the fp32 stack once, the result
    ("grouped_mm, 2,048 rows", "grouped_mm", grouped_mm,
     (z((2048, 256)), z((4, 256, 2048), F32), z((8,), I32), z((1,), I32)),
     2_147_483_648, 18_874_368, 0),
    # the same product launched at a quarter of the rows declares a
    # quarter of the work
    ("grouped_mm, 512 rows", "grouped_mm", grouped_mm,
     (z((512, 256)), z((4, 256, 2048), F32), z((2,), I32), z((1,), I32)),
     536_870_912, 11_010_048, 0),
    # the experts of three layers stacked and a layer's index: the work of
    # ONE layer's call, the first case's to the byte
    ("grouped_mm, a layer of a stack", "grouped_mm",
     lambda x, w, te, n, at: grouped_mm(x, w, te, n, layer=at),
     (z((2048, 256)), z((3, 4, 256, 2048), F32), z((8,), I32), z((1,), I32),
      z((1,), I32)),
     2_147_483_648, 18_874_368, 0),
    # x [2048, 3584] once (one panel of dy's 512 columns), dy twice (two
    # 1792-row panels of K), [4, 3584, 512] fp32 out
    ("grouped_mm_dw", "grouped_mm_dw",
     lambda x, dy, te, n: grouped_mm_dw(x, dy, te, n, 4),
     (z((2048, 3584)), z((2048, 512)), z((8,), I32), z((1,), I32)),
     7_516_192_768, 48_234_496, 0),
    # 1,024 slots = 4 pieces of 256 rows, + 1 a grid step (2): 6 products
    # of [256, 256] x [256, 256]; slots' tokens 4 KiB, 512 sums out
    ("moe_sum_pairs", "moe_sum_pairs", moe_sum_pairs,
     (z((1024, 256)), z((1024,), I32), z((513,), I32)),
     201_326_592, 1_052_672, 0),
    # an element of X [256, 1024]: 2 its square, 2 * 128 the lane tile of
    # phi, 2 its share of h; 8 on each of a token's 128 lanes (scale,
    # H_pre's affine and sigmoid); bf16 X, h [256, 256] and phi [1024, 128]
    # once, fp32 table [8, 128] and mr; a sigmoid a lane, a rsqrt a token
    ("hc_pre_fwd", "hc_pre_fwd", _hc_pre_fwd, (HC_X,) + HC_PHI,
     256 * 1024 * 260 + 256 * 128 * 8,
     2 * (256 * 1024 + 256 * 256 + 1024 * 128) + 4 * 264 * 128, 256 * 129),
    # an element of X': 4 + 1 products summed; X in, X' out, y, the maps
    ("hc_post_fwd", "hc_post_fwd", _hc_post_fwd, (HC_X, HC_Y, HC_M),
     256 * 1024 * 10, 2 * (2 * 256 * 1024 + 256 * 256) + 4 * 256 * 128, 0),
    # an element of dX': its share of dy and its products with y and
    # the four X_i, 2 each; dX', X, y in, dy out, maps in, dmaps out
    ("hc_post_bwd", "hc_post_bwd", _hc_post_bwd, (HC_X, HC_X, HC_Y, HC_M),
     256 * 1024 * 12, 2 * (2 * 256 * 1024 + 2 * 256 * 256)
     + 4 * 2 * 256 * 128, 0),
    # an element of X: 2 with dh, 2 * 7 the terms of dX, 2 * 128 the lane
    # tile of phi^T, 2 * 32 the rows of phi's gradient (24 padded to
    # packed bf16 tiles); 16 a lane a token; dX', X in, dX out, dh, phi^T
    # [128, 1024], four [256, 128] in, one out, the table, [32, 1024] fp32
    ("hc_pre_bwd", "hc_pre_bwd", _hc_pre_bwd,
     (HC_X, HC_X, HC_Y, HC_M, HC_M, HC_M) + HC_PHI,
     256 * 1024 * 336 + 256 * 128 * 16,
     2 * (3 * 256 * 1024 + 256 * 256 + 128 * 1024) + 4 * (4 * 256 + 8) * 128
     + 4 * 32 * 1024, 256 * 128),
    # the bound: 4 rows x 6 pages; 8 heads x 16 slots x (4 * 128 + 4); a K
    # and a V page of [2, 16, 128] bf16 a step, q in and out
    ("paged_attn", "paged_attn", _paged, PAGED, 1_585_152, 409_600, 3_264),
    # int8 pages and their fp32 scales; two more passes over the scores
    ("paged_attn_q8", "paged_attn_q8", _paged_q8, PAGED_Q8,
     1_591_296, 219_136, 3_264),
    # 1,030 rows padded to 1,032 = 129 blocks of 8; the weights' two
    # column blocks and their scales again for every row block
    ("int8_matmul", "int8_matmul", int8_matmul,
     (z((1030, 512)), z((512, 1024), I8), z((1024,), F32)),
     1_083_187_200, 71_331_840, 0),
    # 300,000 numbers padded to 300,032: 14 an element, a sqrt; bf16 p in
    # and out, five fp32 passes
    ("fused_adamw", "fused_adamw",
     lambda p, g, m, v: fused_adamw(p, g, m, v, 3, 1e-3),
     (z((1000, 300)), z((1000, 300)), z((1000, 300), F32),
      z((1000, 300), F32)), 4_200_448, 7_200_768, 300_032),
]


@pytest.mark.parametrize("name,fn,args,flops,nbytes,exps",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_a_kernel_declares_what_it_executes(name, fn, args, flops, nbytes,
                                            exps):
    assert declared(name, fn, *args) == (flops, nbytes, exps)


def test_every_kernel_name_has_a_case():
    from test_trace_names import pallas_call_names
    assert {c[1] for c in CASES} == set(pallas_call_names())


def test_one_pass_and_two_kernels_declare_different_work():
    """``flash_bwd_dkv`` is one name for three amounts of work and
    ``flash_bwd_dq`` for two (ROADMAP D14); the declared FLOPs of a pair
    tell them apart in a trace."""
    by = {c[0]: c for c in CASES}

    def per_score(case, heads, pairs, s):
        flops = by[case][4]
        if "one pass" in case:
            flops -= 2 * heads * s * 128        # delta
        return flops // (heads * pairs * 512 * 512)
    assert per_score("flash_bwd_dkv, one pass", 2, 10, 2048) == \
        2 * 5 * 128 + 5
    assert per_score("flash_bwd_dkv, two kernels", 8, 10, 2048) == \
        2 * 4 * 128 + 5
    # ``flash_bwd_dq`` (and ``flash_win_bwd_dq``) is one name for TWO
    # amounts of work since PR 45: the query-major one pass, five products
    # a pair, against the first of two kernels, three; K and V cross HBM
    # once a KV head either way, and the one pass writes dk and dv once
    for name, pairs in (("flash_bwd_dq", 10), ("flash_win_bwd_dq", 7)):
        assert per_score(f"{name}, one pass", 8, pairs, 2048) == \
            2 * 5 * 128 + 5
        assert (by[name][4] - 2 * 8 * 2048 * 128) \
            // (8 * pairs * 512 * 512) == 2 * 3 * 128 + 5
        assert by[f"{name}, one pass"][5] - by[name][5] == \
            2 * 2048 * 2 * 128 - 4 * 8 * 2048      # + dk, dv; - delta
    # the split form: 1,669 a score in one pass against 1,285 (and
    # ``flash_bwd_dq``'s 1,029 beside them)
    assert per_score("flash_bwd_dkv, split one pass", 2, 3, 1024) == \
        2 * (5 * 128 + 3 * 64) + 5 == 1669
    assert per_score("flash_bwd_dkv, split one pass at 16k", 1, 528,
                     16384) == 1669
    assert per_score("flash_bwd_dkv, split", 2, 3, 1024) == \
        2 * (4 * 128 + 2 * 64) + 5 == 1285


@pytest.mark.parametrize("s,executed,of", [(2048, 10, 16), (8192, 136, 256),
                                           (512, 1, 1)])
def test_flash_fwd_counts_the_pairs_its_grid_executes(s, executed, of):
    """Causal, a q block runs the k blocks up to its own and the
    diagonal's whole, masked: at S 2,048 / block 512 that is 10 of the
    16 pairs (the triangle NEEDS 8.25 — PERF.md section 7, fault 5)."""
    from paddle_tpu.ops.pallas.flash_attention import _pick_blocks
    bq, bk = _pick_blocks(s)
    # the kernel's own loop: fori_loop(0, qi) unmasked + the diagonal
    assert sum(qi + 1 for qi in range(s // bq)) == executed
    assert (s // bq) * (s // bk) == of
    flops, _, _ = declared("flash_fwd", _causal, z((1, s, 1, 128)),
                           z((1, s, 1, 128)), z((1, s, 1, 128)))
    assert flops == executed * bq * bk * (4 * 128 + 4)
    full, _, _ = declared(
        "flash_fwd", lambda q, k, v: flash_attention(q, k, v, causal=False),
        z((1, s, 1, 128)), z((1, s, 1, 128)), z((1, s, 1, 128)))
    assert full == of * bq * bk * (4 * 128 + 4)
