#!/usr/bin/env python3
"""The readings a limit is set from, on the chip, at the cell's own
size, many seeds in ONE process (set-up is long):

    python3 benchmark/tests/control_chip.py <workload> <seconds> <controls> <seed> ...

For every seed the program's numbers against the float32 reference
(sound runs); for the first ``controls`` seeds also the CONTROL's: the
reference computed with int8 matrix products, put in the program's
place.  A serving cell gets a short window of ``seconds`` at the cell's
own load; a training cell needs none.  Prints one line a seed and the
largest sound / smallest control reading of every number.
"""

from __future__ import annotations

import gc
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def train(cell, seeds, controls):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import harness, train_cell
    from paddle_tpu.models.llama_pretrain import (
        build_mesh, init_adafactor_state, make_train_step)
    devices = harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    job, fam = cell.traffic, cell.family
    cfg = fam.build_cfg(cell.conf, train=True, job=job)
    mesh = build_mesh(devices=devices)
    B, S = job["batch"], job["seq"]
    sound, low, compiled = [], [], None
    for k, seed in enumerate(seeds):
        key = fam.seed_key(seed)
        leaf0 = train_cell.leaf_maker(fam, cfg, key)
        batches = [np.stack([train_cell.token_row(seed, s * B + r, S,
                                                  cfg.vocab_size)
                             for r in range(B)])
                   for s in range(job["reference_steps"])]
        ref = train_cell.run_reference(cell, job, leaf0, batches)
        if k < controls:
            ctl = train_cell.run_reference(cell, job, leaf0, batches,
                                           "int8")
            low.append(train_cell.gap_numbers(ctl, ref))
            print(f"seed {seed} CONTROL int8: {low[-1]}", flush=True)
        with mesh:
            params = fam.make_params(cfg, seed, mesh)
            opt = init_adafactor_state(params)
            if compiled is None:
                compiled = make_train_step(
                    cfg, mesh, lr=job["lr"],
                    weight_decay=job["weight_decay"],
                    optimizer=job["optimizer"]).lower(
                        params, opt, jax.ShapeDtypeStruct(
                            (B, S + 1), jnp.int64)).compile()
            params, opt, prog = train_cell.follow_program(
                compiled, params, opt, batches, leaf0)
        del params, opt
        gc.collect()
        sound.append(train_cell.gap_numbers(prog, ref))
        print(f"seed {seed} program: {sound[-1]} losses {prog['loss']}",
              flush=True)
    for name in sound[0]:
        line = f"{name}: largest sound {max(s[name] for s in sound):.6g}"
        if low:
            line += f", smallest control {min(c[name] for c in low):.6g}"
        print(line, flush=True)


def serve(cell, seeds, controls, seconds):
    from benchmark import serve_cell
    for k, seed in enumerate(seeds):
        args = types.SimpleNamespace(workload=cell.name, seed=seed,
                                     seconds=seconds, trace=0)
        serve_cell.run(args, cell, control=k < controls)
        gc.collect()


def main(argv) -> int:
    from benchmark import harness
    cell = harness.find_cell(argv[1])
    seconds, controls = float(argv[2]), int(argv[3])
    seeds = [int(s) for s in argv[4:]]
    if cell.traffic["kind"] == "train_job":
        train(cell, seeds, controls)
    else:
        serve(cell, seeds, controls, seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
