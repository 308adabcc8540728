"""Whole-program compiled training step for Layer models.

Reference role: the reference's static-graph Executor training path
(build program once, run per batch) and CINN whole-graph compilation.

Why it exists: the eager tape dispatches per op, and on a TPU
every dispatch pays host->device latency — a Layer/optimizer train
loop measures ~9 img/s for ResNet50-vs-966+ when the SAME model, loss
and optimizer rule are compiled into ONE jitted XLA program (PERF.md).
:func:`jit_train_step` does that generically: parameters/optimizer
states become functional pytrees, the optimizer's pure ``_update`` rule
(shared with the eager path — no duplicated math) runs inside the
program, and the updated device arrays are swapped back onto the
Parameter objects so the model stays authoritative.

Bounds (documented, loud):

* ``grad_clip`` other than None/ClipGradByGlobalNorm is rejected.
* Buffers (BatchNorm running stats) are passed in LIVE each step and
  their in-trace updates are written back after it (round-4: the
  compiled step now matches the eager loop's buffer semantics).
* EVERY trainable parameter handed to the optimizer is updated every
  step.  A parameter unreached by ``loss_fn`` gets zero gradients
  (still decayed by AdamW etc.) — exclude it from the optimizer's
  parameter list for eager-identical semantics (the eager loop skips
  grad-less parameters).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..autograd import tape
from ..nn.clip import ClipGradByGlobalNorm
from ..nn.layer.layers import Layer
from ..tensor.tensor import Tensor, wrap_array

__all__ = ["jit_train_step", "jit_eval_step"]

_EVAL_ROOT_SEQ = 0


def jit_train_step(model: Layer, loss_fn: Callable, optimizer,
                   amp_level: str = "O0", amp_dtype: str = "bfloat16",
                   return_outputs: bool = False):
    """Compile ``loss_fn(model(x), y)`` + backward + ``optimizer`` into
    one jitted step.  Returns ``step(x, y) -> loss Tensor``; parameters
    and optimizer state live on device between calls.  ``x`` / ``y``
    may be tuples: ``model(*x)`` and ``loss_fn(out, y_tuple)``.
    ``return_outputs=True`` makes the step return ``(loss, outputs)``
    (the forward outputs, for metric computation — hapi's fit loop).
    Buffer updates that happen inside the forward (BatchNorm running
    stats) are carried out of the trace and written back onto the
    Layer's buffers every step, matching the eager loop.

    ``amp_level``: "O0" (off) or "O1" — the eager autocast hook applies
    per-op inside the traced program (white/black lists identical to
    eager AMP), so the compiled step runs mixed bf16/fp16 with fp32
    master params and fp32 gradients.  No GradScaler is needed for
    bfloat16 (the TPU default).
    """
    clip = getattr(optimizer, "_grad_clip", None)
    if clip is not None and not isinstance(clip, ClipGradByGlobalNorm):
        raise NotImplementedError(
            "jit_train_step supports grad_clip=None or "
            "ClipGradByGlobalNorm; other clips need the eager path")

    # the model's full parameter set feeds the functional call; ONLY
    # the optimizer's own parameter list is updated (eager step()
    # touches optimizer._params() — a fine-tune that hands the
    # optimizer just the head must not decay the backbone)
    all_items = list(model.named_parameters())
    opt_ids = {id(p) for p in optimizer._params()}
    param_items = [(n, p) for n, p in all_items
                   if not p.stop_gradient and id(p) in opt_ids]
    # membership by id(): a `(n, p) not in list` test would fall through
    # to Tensor.__eq__ (elementwise) when two parameters share a name
    trained_ids = {id(p) for _, p in param_items}
    frozen_items = [(n, p) for n, p in all_items
                    if id(p) not in trained_ids]
    names = [n for n, _ in param_items]
    param_objs = {n: p for n, p in param_items}
    frozen_objs = {n: p for n, p in frozen_items}
    buf_objs = dict(model.named_buffers())

    if amp_level not in ("O0", "O1"):
        raise NotImplementedError(
            "jit_train_step amp_level must be O0 or O1 (O2 master-"
            "weight decoration belongs to amp.decorate + the eager "
            "loop)")
    if amp_level == "O1" and amp_dtype == "float16":
        raise NotImplementedError(
            "float16 autocast needs GradScaler loss scaling, which the "
            "compiled step does not integrate — use bfloat16 (the TPU "
            "default, no scaling needed) or the eager loop with "
            "amp.GradScaler")

    # RNG-consuming layers (Dropout etc.): a host-side key draw at trace
    # time would bake ONE mask into the program.  Instead each step
    # passes fresh uint32[2] key data (host-constructed, zero device
    # dispatches) and every RNG call site fold_ins a distinct counter —
    # see framework.random.traced_key_guard.  Reproducible via
    # paddle.seed() before building the step (the root is drawn from
    # the global chain here).
    from ..framework import random as framework_random
    rng_root = framework_random.draw_step_root()

    def loss_of(pvals, fvals, bvals, x, y, rng):
        from ..amp import auto_cast
        # x / y may be tuples of arrays (multi-input models: BERT takes
        # ids+token_types+mask; QA labels are (start, end))
        xs = tuple(wrap_array(a) for a in x) if isinstance(x, tuple) \
            else (wrap_array(x),)
        yt = tuple(wrap_array(a) for a in y) if isinstance(y, tuple) \
            else wrap_array(y)
        with tape.functional_trace_guard():
            with framework_random.traced_key_guard(rng):
                with auto_cast(enable=(amp_level == "O1"), level="O1",
                               dtype=amp_dtype):
                    out, new_bufs = model._functional_call(
                        {**pvals, **fvals}, *xs, buffers=bvals,
                        return_buffers=True)
                    loss = loss_fn(out, yt)
        loss_arr = loss._data if isinstance(loss, Tensor) else loss
        out_arrs = jax.tree_util.tree_map(
            lambda t: t._data if isinstance(t, Tensor) else t, out,
            is_leaf=lambda t: isinstance(t, Tensor))
        return loss_arr, (out_arrs, new_bufs)

    # optimizer states via _get_state: honors a prior set_state_dict
    # AND the multi_precision master-weight slot; leaves normalised to
    # arrays so step-2 state shapes/dtypes match step-1's (a Python
    # float leaf would force a full recompile on the second call)
    states = {
        n: jax.tree_util.tree_map(jnp.asarray, optimizer._get_state(p))
        for n, p in param_items}

    def update_all(pvals, svals, grads, lr):
        if clip is not None:
            # mirror ClipGradByGlobalNorm: params with need_clip=False
            # are excluded from both the norm and the scaling
            clipped = [n for n in names
                       if getattr(param_objs[n], "need_clip", True)]
            gnorm = jnp.sqrt(sum(
                jnp.sum(jnp.square(grads[n].astype(jnp.float32)))
                for n in clipped))
            scale = jnp.minimum(1.0, clip.clip_norm / (gnorm + 1e-12))
            grads = dict(grads)
            for n in clipped:
                grads[n] = grads[n] * scale.astype(grads[n].dtype)
        new_p, new_s = {}, {}
        for n in names:
            optimizer._current_param = param_objs[n]
            st = svals[n]
            g = grads[n]
            if "master" in st:      # multi-precision: fp32 compute copy
                compute_p = st["master"]
                g = g.astype(jnp.float32)
            else:
                compute_p = pvals[n]
            np_, ns = optimizer._update(compute_p, g, st, lr)
            ns = dict(st, **ns)
            if "master" in st:
                ns["master"] = np_
            new_p[n] = np_.astype(pvals[n].dtype)
            new_s[n] = ns
        optimizer._current_param = None
        return new_p, new_s

    # donate params + optimizer state: the old buffers are dead after
    # the step (replaced on the Parameter objects / state_box), and at
    # README-scale models an undonated copy is the difference between
    # fitting and OOM.  NOTE: external aliases of a Parameter's old
    # device buffer become invalid after a step (same as eager updates
    # replacing p._data).
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def compiled(pvals, svals, fvals, bvals, x, y, lr, rng):
        (loss, (outs, new_bufs)), grads = jax.value_and_grad(
            loss_of, has_aux=True)(pvals, fvals, bvals, x, y, rng)
        new_p, new_s = update_all(pvals, svals, grads, lr)
        return new_p, new_s, loss, outs, new_bufs

    state_box = {"s": states, "t": 0}

    def _arr(v):
        if isinstance(v, (tuple, list)):
            return tuple(_arr(e) for e in v)
        return v._data if isinstance(v, Tensor) else jnp.asarray(v)

    def step(x, y):
        xv = _arr(x)
        yv = _arr(y)
        pvals = {n: param_objs[n]._data for n in names}
        fvals = {n: p._data for n, p in frozen_objs.items()}
        bvals = {n: b._data for n, b in buf_objs.items()}  # live reads
        lr = jnp.asarray(float(optimizer.get_lr()), jnp.float32)
        rng = framework_random.make_step_key(rng_root, state_box["t"])
        state_box["t"] += 1
        new_p, new_s, loss, outs, new_bufs = compiled(
            pvals, state_box["s"], fvals, bvals, xv, yv, lr, rng)
        for n in names:
            param_objs[n]._data = new_p[n]
        state_box["s"] = new_s
        # keep the optimizer's own store in sync so state_dict()
        # checkpoints the jitted moments
        for n in names:
            optimizer._states[id(param_objs[n])] = new_s[n]
        # write buffer updates (BatchNorm running stats) back — the
        # eager loop refreshes them every forward, so must we
        for n, arr in new_bufs.items():
            buf_objs[n]._data = arr
        optimizer._step_count = getattr(optimizer, "_step_count", 0) + 1
        if return_outputs:
            return wrap_array(loss), jax.tree_util.tree_map(
                wrap_array, outs)
        return wrap_array(loss)

    return step


def jit_eval_step(model: Layer):
    """Compile ``model(*x)`` (eval mode, no grads) into one jitted
    program — the inference-side counterpart of :func:`jit_train_step`
    (hapi's evaluate/predict loops pay the same per-op dispatch cliff
    the fit loop did).  Returns ``fwd(x) -> outputs`` where ``x`` may
    be a Tensor or tuple of Tensors; parameters/buffers are read live
    each call, so it stays correct across training steps.  RNG ops in
    the forward (sampling heads, MC-dropout-style layers) get a fresh
    per-call key via the same traced-key threading as the train step —
    a host draw at trace time would bake ONE sample into the program."""
    from ..framework import random as framework_random

    p_objs = dict(model.named_parameters())
    buf_objs = dict(model.named_buffers())
    # root derived WITHOUT advancing the global chain: evaluate() must
    # not perturb the random stream of a seeded training script the way
    # a chain draw here would (deterministic under paddle.seed via
    # initial_seed; a per-build counter separates instances)
    global _EVAL_ROOT_SEQ
    _EVAL_ROOT_SEQ += 1
    rng_root = (framework_random.default_generator.initial_seed()
                ^ (0xA5EDC0DE + _EVAL_ROOT_SEQ)) & 0xFFFFFFFF
    counter = [0]
    # the forward's train/eval mode is BAKED at trace time; flipping it
    # later must be loud, not silently ignored
    mode_snapshot = model.training

    # _functional_call enters the functional-trace guard itself
    def fwd_of(pvals, bvals, x, rng):
        xs = tuple(wrap_array(a) for a in x) if isinstance(x, tuple) \
            else (wrap_array(x),)
        with framework_random.traced_key_guard(rng):
            out = model._functional_call(pvals, *xs, buffers=bvals)
        return jax.tree_util.tree_map(
            lambda t: t._data if isinstance(t, Tensor) else t, out,
            is_leaf=lambda t: isinstance(t, Tensor))

    compiled = jax.jit(fwd_of)

    def _arr(v):
        if isinstance(v, (tuple, list)):
            return tuple(_arr(e) for e in v)
        return v._data if isinstance(v, Tensor) else jnp.asarray(v)

    def fwd(x):
        if model.training != mode_snapshot:
            raise RuntimeError(
                "jit_eval_step compiled this model in "
                f"{'train' if mode_snapshot else 'eval'} mode but it "
                "is now in the other mode — rebuild the step after "
                "train()/eval() flips (the traced program bakes the "
                "mode)")
        pvals = {n: p._data for n, p in p_objs.items()}
        bvals = {n: b._data for n, b in buf_objs.items()}
        rng = framework_random.make_step_key(rng_root, counter[0])
        counter[0] += 1
        outs = compiled(pvals, bvals, _arr(x), rng)
        return jax.tree_util.tree_map(wrap_array, outs)

    return fwd
