#!/usr/bin/env python3
"""Rehearsal 3 (on-chip-measurement guide, section 2): compile each
cell's step programs at their REAL sizes for a described ``v5e:2x2``,
with no chip attached, and print what the compiler says they need.
Nothing runs: this settles what fits (training depth, pool sizes,
packed-prefill buckets), never a time.  A cell is a workload of
BENCHMARK.json or ``<config>:<traffic>:<chips>``; its family's module
gives the config object and the leaves' shapes.  Run by hand:

    JAX_PLATFORMS=cpu python3 benchmark/tests/aot_compile.py train internlm2-1.8b.pretrain-2k 17 18 19 20
    JAX_PLATFORMS=cpu python3 benchmark/tests/aot_compile.py reference internlm2-1.8b.pretrain-2k
    JAX_PLATFORMS=cpu python3 benchmark/tests/aot_compile.py serve internlm2-1.8b:chat-steady:1 64 2048 4096
    JAX_PLATFORMS=cpu python3 benchmark/tests/aot_compile.py serve mistral-7b-v0.3:chat-steady-tp4:4 2048
"""

from __future__ import annotations

import functools
import operator
import os
import re
import sys
import time
from collections import Counter

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

GIB = 2.0 ** 30


def sds(mesh, shape, dtype, spec=P()):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def param_sds(fam, cfg, mesh):
    from paddle_tpu.models.llama_pretrain import param_specs
    from benchmark import models
    specs, shapes = param_specs(cfg, 1), fam.leaf_shapes(cfg)
    return models.tree_of(shapes, lambda path: sds(
        mesh, shapes[path], cfg.param_dtype,
        functools.reduce(operator.getitem, path, specs)))


# an instruction the compiler's own rematerialization pass cloned, as
# ``as_text()`` defines it: ``%fusion.381.remat3 = bf16[...] fusion(...)``
REMAT_CLONE = re.compile(
    r"^\s*(?:ROOT )?%(\S+?\.remat\d*) = \S+ ([a-z\-]+)\((.*)$", re.M)
MATMUL_PATH = re.compile(r'op_name="[^"]*/dot_general"')


def remat_clones(text: str) -> str:
    """What XLA rematerialized because the program was at the HBM
    limit: the fusions that are matrix products by name (each runs a
    second time, every step), and the other clones by opcode."""
    matmuls, others = [], Counter()
    for name, opcode, rest in REMAT_CLONE.findall(text):
        if opcode == "fusion" and MATMUL_PATH.search(rest):
            matmuls.append(name)
        else:
            others[opcode] += 1
    return (f"{len(matmuls)} .remat matrix-product fusions "
            f"{sorted(matmuls)}, {sum(others.values())} other .remat "
            f"clones {dict(others)}")


def report(what, compiled, t0):
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.temp_size_in_bytes +
             ma.output_size_in_bytes - ma.alias_size_in_bytes)
    text = compiled.as_text()
    print(f"{what}: compiled in {time.time() - t0:.0f}s; per device: args "
          f"{ma.argument_size_in_bytes / GIB:.2f} GiB "
          f"({ma.argument_size_in_bytes} B), temp "
          f"{ma.temp_size_in_bytes / GIB:.2f} "
          f"({ma.temp_size_in_bytes} B), out "
          f"{ma.output_size_in_bytes / GIB:.2f}, aliased "
          f"{ma.alias_size_in_bytes / GIB:.2f}, live {total / GIB:.2f}; "
          f"{text.count('tpu_custom_call')} kernels, "
          f"{text.count('all-reduce(')} all-reduce; "
          f"{remat_clones(text)}", flush=True)


def main(argv) -> int:
    from jax.experimental import topologies
    from benchmark import harness
    from paddle_tpu.models.llama_pretrain import build_mesh
    from paddle_tpu.ops.pallas import _common
    _common.interpret = lambda: False       # Mosaic, as on the chip
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    if argv[1] == "reference":
        # what the plain reference's largest program OF EACH KIND
        # needs: the backward of that kind's block on the rows the loop
        # gives it, beside the float32 weights and adafactor state the
        # reference holds
        from benchmark import kernel_costs, reference
        cell = harness.find_cell(argv[2])
        job, fam = cell.traffic, cell.family
        model = reference.Model(cell.block_reference, cell.conf)
        mesh = build_mesh(devices=topo.devices[:1])
        cfg = fam.build_cfg(cell.conf, train=True, job=job)
        shapes = fam.leaf_shapes(cfg)
        f32 = lambda shape: sds(mesh, shape, jnp.float32)
        x = f32((reference.ROW_BLOCK, job["seq"], cell.conf["hidden_size"]))
        for kind, (leaves, block) in model.kinds.items():
            w = {nm: f32(shapes[model.path(kind, nm)][1:]) for nm in leaves}
            t0 = time.time()
            with mesh:
                c = reference._block_bwd.lower(
                    x, w, x, f32(()), block, model.dims, "f32").compile()
            report(f"{cell.name} reference block backward"
                   f"{'' if kind is None else ' of kind ' + kind}, "
                   f"{reference.ROW_BLOCK} rows of {job['seq']}", c, t0)
        n = kernel_costs.total_params(cell.conf)
        print(f"{cell.name} reference holds {n} float32 parameters "
              f"({4 * n / GIB:.2f} GiB) and their adafactor state",
              flush=True)
        return 0
    if argv[1] == "train":
        from paddle_tpu.models.llama_pretrain import (
            init_adafactor_state, make_train_step)
        cell = harness.find_cell(argv[2])
        job, fam = cell.traffic, cell.family
        mesh = build_mesh(devices=topo.devices[:cell.chips])
        for depth in map(int, argv[3:]):
            conf = dict(cell.conf, num_hidden_layers=depth)
            cfg = fam.build_cfg(conf, train=True, job=job)
            t0 = time.time()
            try:
                with mesh:
                    params = param_sds(fam, cfg, mesh)
                    opt = jax.tree_util.tree_map(
                        lambda x: sds(mesh, x.shape, x.dtype),
                        jax.eval_shape(init_adafactor_state, params))
                    step = make_train_step(
                        cfg, mesh, lr=job["lr"],
                        weight_decay=job["weight_decay"],
                        optimizer=job["optimizer"])
                    c = step.lower(params, opt, sds(
                        mesh, (job["batch"], job["seq"] + 1),
                        jnp.int64)).compile()
                report(f"{cell.name} train step depth {depth} loss_chunks "
                       f"{job['loss_chunks']}", c, t0)
            except Exception as e:
                print(f"{cell.name} train step depth {depth}: REFUSED after "
                      f"{time.time() - t0:.0f}s: {str(e)[:600]}", flush=True)
        return 0
    cell = harness.find_cell(argv[2])
    fam, sv = cell.family, cell.traffic["server"]
    cfg = fam.build_cfg(cell.conf, train=False)
    tp = cell.chips > 1
    mesh = build_mesh(mp=cell.chips, devices=topo.devices[:cell.chips])
    params = param_sds(fam, cfg, mesh)
    pool = sds(mesh, (cfg.num_hidden_layers, sv["num_pages"],
                      cfg.num_key_value_heads, sv["page"], cfg.head_dim),
               jnp.bfloat16, P(None, None, "mp", None, None) if tp else P())
    B = sv["slots"]
    from paddle_tpu.models import paged_decode as pd
    t0 = time.time()
    step = pd.make_paged_decode_step_tp(cfg, mesh, 0.0) if tp \
        else pd.make_paged_decode_step(cfg, 0.0)
    c = step.lower(params, pool, pool,
                   sds(mesh, (B, sv["pages_max"]), jnp.int32),
                   sds(mesh, (B,), jnp.int32), sds(mesh, (B,), jnp.int64),
                   sds(mesh, (2,), jnp.uint32)).compile()
    report(f"{cell.name} decode step slots {B} pages {sv['num_pages']}",
           c, t0)
    for T in map(int, argv[3:]):
        t0 = time.time()
        run = pd._prefill_packed_tp(cfg, mesh, False, False) if tp \
            else pd._prefill_packed(cfg, False, False)
        i32, flag = sds(mesh, (T,), jnp.int32), sds(mesh, (T,), jnp.bool_)
        dummy = sds(mesh, (1,), jnp.float32)
        try:
            c = run.lower(params, sds(mesh, (1, T), jnp.int64),
                          sds(mesh, (1, T), jnp.int32),
                          sds(mesh, (1, T), jnp.int32), pool, pool, dummy,
                          dummy, i32, i32, flag, i32, flag).compile()
            report(f"{cell.name} packed prefill T={T}", c, t0)
        except Exception as e:
            print(f"{cell.name} packed prefill T={T}: REFUSED: "
                  f"{str(e)[:600]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
