"""A training cell: the program's jitted train step (``make_train_step``)
fed by ``paddle_tpu.io.DataLoader``, on the job a traffic file of
``kind: train_job`` states.

Set-up builds ONE compiled step with its state, drives it through its
first steps on the window's own feed, and hands that same object to the
window.  The reference (``reference.TrainReference`` around the blocks
of the cell's family) follows those first steps once the window has
closed, ``memory_peak_bytes`` has been read and the program's state is
freed: the peak reported is the program's, and the reference's time is
not set-up.
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from . import harness
from .harness import log
from .reference import TrainReference
from .stats import median
from .traffic import rng_for


def token_row(seed: int, i: int, seq: int, vocab: int):
    """Row ``i`` of the run's synthetic corpus: ``seq + 1`` token ids."""
    return rng_for(seed, 3, i).integers(0, vocab, size=seq + 1,
                                        dtype=np.int64)


class SyntheticTokens:
    """Seeded synthetic token rows, one per index, all different.
    Module-level so that the DataLoader's spawned workers can unpickle
    it; a worker must never initialise a JAX backend (the chip belongs
    to the parent)."""

    def __init__(self, n, seq, vocab, seed):
        self.n, self.seq, self.vocab, self.seed = n, seq, vocab, seed

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        from jax._src import xla_bridge
        if xla_bridge.backends_are_initialized():
            raise RuntimeError(
                "a DataLoader worker initialised a jax backend")
        return token_row(self.seed, i, self.seq, self.vocab)


def worst_leaf_gap(prog: dict, ref: dict) -> float:
    """The largest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    floor = median(list(ref.values()))
    return max(abs(prog[k] - ref[k]) / max(ref[k], floor) for k in ref)


def grad_norms_from_adafactor(opt_state, params) -> dict:
    """The norm of the first gradient as the optimizer got it, from its
    state after ONE step: at t = 1 beta2 is 0, so a factored leaf's
    ``vr`` is the row mean of g^2 (+1e-30) and an unfactored leaf's
    ``v`` is g^2 itself."""
    import jax
    import jax.numpy as jnp
    out = {}
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, p in flat:
        keys = tuple(k.key for k in path)
        st = opt_state["moments"]
        for k in keys:
            st = st[k]
        if "vr" in st:
            sq = jnp.sum(st["vr"]) * p.shape[-1]
        else:
            sq = jnp.sum(st["v"])
        out[keys] = math.sqrt(max(float(sq), 0.0))
    return out


def change_norms(params, leaf0) -> dict:
    import jax
    import jax.numpy as jnp
    out = {}
    for path, p in jax.tree_util.tree_flatten_with_path(params)[0]:
        keys = tuple(k.key for k in path)
        d = p.astype(jnp.float32) - leaf0(keys)
        out[keys] = math.sqrt(float(jnp.sum(jnp.square(d))))
    return out


class Feed:
    """The window's feed: batches from the DataLoader, with the first
    few held so that the reference can follow them first."""

    def __init__(self, loader):
        self.it = iter(loader)
        self.held = []

    def peek(self, n: int) -> list:
        while len(self.held) < n:
            self.held.append(self._fetch())
        return self.held[:n]

    def _fetch(self):
        b = next(self.it)
        return np.asarray(b.numpy() if hasattr(b, "numpy") else b)

    def __next__(self):
        return self.held.pop(0) if self.held else self._fetch()


def leaf_maker(fam, cfg, key):
    """``leaf0(path)``: one float32 leaf of the seed's initial
    parameters.  The key is an ARGUMENT of the jitted maker, not a
    constant in it, so that every seed runs the same programs."""
    import jax
    import jax.numpy as jnp
    make = jax.jit(lambda path, k: fam.make_leaf(cfg, k, path, jnp.float32),
                   static_argnums=0)
    return lambda path: make(path, key)


def run_reference(cell, job, leaf0, batches, precision="f32") -> dict:
    """Loss of each followed step, the first gradient's norm per leaf
    and the parameters' change per leaf, by the plain reference."""
    t = [time.monotonic()]
    ref = TrainReference(cell.block_reference, cell.conf, leaf0, job,
                         precision=precision)
    t.append(time.monotonic())
    losses = [ref.step(b) for b in batches]
    t.append(time.monotonic())
    out = {"loss": losses, "grad": ref.grad_norms,
           "change": ref.change_norms()}
    t.append(time.monotonic())
    log(f"reference ({precision}): state {t[1] - t[0]:.1f}s, "
        f"{len(batches)} step(s) {t[2] - t[1]:.1f}s, change norms "
        f"{t[3] - t[2]:.1f}s")
    del ref
    gc.collect()
    return out


def gap_numbers(prog: dict, ref: dict) -> dict:
    """The numbers compared, each against its limit of the same name
    (the losses share one)."""
    out = {f"loss_rel_gap.step{i}": abs(p - r) / abs(r)
           for i, (p, r) in enumerate(zip(prog["loss"], ref["loss"]))}
    out["grad_norm_worst_leaf_gap"] = worst_leaf_gap(prog["grad"],
                                                     ref["grad"])
    out["param_change_worst_leaf_gap"] = worst_leaf_gap(prog["change"],
                                                        ref["change"])
    return out


def compare(checks, job, prog: dict, ref: dict) -> None:
    for name, value in gap_numbers(prog, ref).items():
        checks.add(name, value, job["limits"][name.split(".")[0]])


def follow_program(compiled, params, opt_state, batches, leaf0):
    """Drive the compiled step through its first steps on ``batches``
    and read what the reference is compared with."""
    import jax.numpy as jnp
    prog = {"loss": []}
    for i, b in enumerate(batches):
        params, opt_state, loss = compiled(params, opt_state,
                                           jnp.asarray(b))
        prog["loss"].append(float(loss))
        if i == 0:
            prog["grad"] = grad_norms_from_adafactor(opt_state, params)
    prog["change"] = change_norms(params, leaf0)
    return params, opt_state, prog


def run(args, cell, step_override=None) -> int:
    """One run.  ``step_override`` (tests only) wraps the compiled step
    to break the timed path underneath."""
    devices = harness.require_chips(cell.chips)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.io import DataLoader
    from paddle_tpu.models.llama_pretrain import (
        build_mesh, init_adafactor_state, make_train_step)

    where = harness.enable_compile_cache()
    clock = harness.CompileClock()
    job, fam = cell.traffic, cell.family
    B, S = job["batch"], job["seq"]
    follow = job["reference_steps"]
    log(f"{cell.name}: device {devices[0].device_kind} x {len(devices)}; "
        f"compile cache {where}; depth {cell.conf['num_hidden_layers']} "
        f"b={B} s={S}")
    cfg = fam.build_cfg(cell.conf, train=True, job=job)
    key = fam.seed_key(args.seed)
    leaf0 = leaf_maker(fam, cfg, key)

    loader = DataLoader(
        SyntheticTokens(job["rows"], S, cfg.vocab_size, args.seed),
        batch_size=B, num_workers=job["loader_workers"],
        use_shared_memory=True)
    feed = Feed(loader)
    first = feed.peek(follow)
    if loader.transport != "shm":
        raise RuntimeError(f"DataLoader transport is {loader.transport!r}, "
                           "the job states shared memory")

    mesh = build_mesh(devices=devices)
    with mesh:
        params = fam.make_params(cfg, args.seed, mesh)
        opt_state = init_adafactor_state(params)
        step = make_train_step(
            cfg, mesh, lr=job["lr"], weight_decay=job["weight_decay"],
            optimizer=job["optimizer"])
        snap = clock.snap()
        compiled = step.lower(
            params, opt_state,
            jax.ShapeDtypeStruct((B, S + 1), jnp.int64)).compile()
        log(f"train step ready: {clock.since(snap)}")
        if step_override is not None:
            compiled = step_override(compiled)

        params, opt_state, prog = follow_program(
            compiled, params, opt_state,
            [next(feed) for _ in range(follow)], leaf0)

        tracer = harness.TraceSlice(
            harness.run_dir(cell) + "/trace")
        trace_from, trace_steps = job["trace_from_step"], job["trace_steps"]
        snap = clock.snap()
        losses, wait_s = [], 0.0
        t0 = time.monotonic()
        stamps = [t0]
        setup_s = t0 - harness.T_PROCESS_START
        elapsed = 0.0
        while elapsed < args.seconds:
            if args.trace and len(losses) == trace_from:
                tracer.start()
            w0 = time.monotonic()
            tokens = jnp.asarray(next(feed))
            wait_s += time.monotonic() - w0
            params, opt_state, loss = compiled(params, opt_state, tokens)
            losses.append(float(loss))          # the fence
            stamps.append(time.monotonic())
            elapsed = stamps[-1] - t0
            if tracer.on and len(losses) == trace_from + trace_steps:
                tracer.stop()
        tracer.stop()
        in_window = clock.since(snap)
        trace = tracer.result()
    # the program's peak, then its state freed, then the reference
    memory_peak = harness.memory_peak_bytes(devices)
    del params, opt_state, compiled, step
    gc.collect()
    log(f"peak HBM {memory_peak / 2**30:.2f} GiB (the program's: read "
        f"before the reference runs)")
    loader_it = feed.it
    feed.held.clear()
    if hasattr(loader_it, "close"):
        loader_it.close()

    steps = len(losses)
    tok_s = steps * B * S / elapsed / len(devices)
    step_ms = [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]
    log(f"window: {steps} steps in {elapsed:.3f}s, median step "
        f"{median(step_ms):.1f} ms (n={steps}); "
        f"train_tok_s_chip {tok_s:.1f}; setup_s {setup_s:.2f}; "
        f"compiles in window {in_window}")

    t_ref = time.monotonic()
    snap = clock.snap()
    ref = run_reference(cell, job, leaf0, first)
    log(f"reference followed {follow} step(s) in "
        f"{time.monotonic() - t_ref:.1f}s (not set-up): losses "
        f"{ref['loss']}; {clock.since(snap)}")
    checks = harness.Checks()
    compare(checks, job, prog, ref)
    finite = sum(1 for x in losses if math.isfinite(x))
    checks.add("window_losses_finite", finite, steps, at_least=True)

    e2e = {"train_tok_s_chip": {"value": tok_s, "unit": "tokens/s/chip"},
           "setup_s": {"value": setup_s, "unit": "s"}}
    if args.trace:
        log(f"end to end (traced run, not for comparison): {e2e}")
        counters = {"compiles_in_window": in_window["compiles"],
                    "steps": steps, "tokens_per_step": B * S,
                    "tok_s_chip": tok_s, "chips": len(devices),
                    "device_kind": devices[0].device_kind}
        spans = {"input_wait_s": wait_s, "window_s": elapsed}
        metrics = harness.read_layer_metrics(cell, trace, counters, spans)
    else:
        metrics = e2e
    harness.result_line(cell, devices, bool(args.trace), checks.ok,
                        steps, steps - finite, metrics, memory_peak, trace)
    return 0
