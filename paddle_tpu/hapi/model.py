"""paddle.Model high-level API.

Reference: python/paddle/hapi/model.py:1052 (Model), :776
(DynamicGraphAdapter), :1750 (fit), :1999 (evaluate/predict).

The adapter runs eager by default; pass ``jit=True`` to ``prepare`` (or set
``model.use_jit = True``) to route train/eval batches through
``paddle_tpu.jit.to_static``-style whole-graph compilation.
"""

from __future__ import annotations

import os
from typing import Any, List, Optional, Sequence, Union

import numpy as np

from ..autograd import tape
from ..io import DataLoader, Dataset
from ..metric import Metric
from ..nn.layer.layers import Layer
from ..tensor.tensor import Tensor, to_tensor
from .callbacks import config_callbacks

__all__ = ["Model", "summary"]


def _to_list(x):
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _batch_len(ins, default):
    """Leading-dim size of the first input array, else ``default``."""
    first = ins[0] if isinstance(ins, (list, tuple)) and ins else ins
    shape = getattr(first, "shape", None)
    if shape is not None and len(shape) > 0:
        return int(shape[0])
    return default


class _DynamicGraphAdapter:
    """Reference: hapi/model.py:776."""

    def __init__(self, model: "Model"):
        self.model = model
        self._jit_step = None
        self._jit_unavailable = False
        self._jit_eval = None
        self._jit_eval_unavailable = False
        self._loss_arity = None

    def reset_jit_eligibility(self) -> None:
        """Called at the top of each fit()/evaluate run: an earlier
        accumulation run must not PERMANENTLY pin this Model to the
        eager loop (the compiled step is rebuilt lazily); a transient
        eval-side failure likewise must not pin evaluate/predict."""
        self._jit_unavailable = False
        self._jit_eval_unavailable = False

    def _compiled_eval(self):
        """Lazy jitted forward for evaluate/predict (same per-op
        dispatch cliff as training; see jit_eval_step)."""
        if self._jit_eval_unavailable:
            return None
        from ..jit import StaticFunction
        if isinstance(self.model.network, StaticFunction):
            # prepare(jit=True) already compiled the forward; nesting
            # jit_eval_step around it would re-trace the proxy's
            # machinery (and bake its per-call rng key as a constant)
            return None
        fwd = self._jit_eval
        if fwd is None:
            from ..incubate.jit_train import jit_eval_step
            fwd = self._jit_eval = jit_eval_step(self.model.network)
        return fwd

    def _eval_outputs(self, inputs):
        """Forward through the compiled path with warned fallback."""
        fwd = self._compiled_eval()
        if fwd is not None:
            try:
                return _to_list(fwd(tuple(inputs)))
            except Exception as e:
                self._jit_eval_unavailable = True
                self._jit_eval = None
                import warnings
                warnings.warn(
                    f"Model.evaluate/predict: compiled forward rejected "
                    f"this model ({type(e).__name__}: {str(e)[:120]}); "
                    f"running eagerly", stacklevel=3)
        return _to_list(self.model.network(*inputs))

    def _compiled_step(self):
        """Build (once) the whole-program compiled train step when the
        prepared configuration qualifies — this is what lifts Model.fit
        off the per-op eager dispatch cliff (9 -> 1,700 img/s for
        ResNet50 on the chip, PERF.md).  Ineligible setups
        (fp16 GradScaler, exotic grad clips, non-callable loss) fall
        back to the eager loop with one warning."""
        if self._jit_unavailable:
            return None
        if self._jit_step is not None:
            return self._jit_step
        m = self.model
        try:
            if m._loss is None or m._optimizer is None or \
                    m._scaler is not None or \
                    (m._amp_level == "O1" and
                     m._amp_dtype != "bfloat16") or \
                    m._amp_level not in ("O0", "O1"):
                raise NotImplementedError("configuration not eligible")
            from ..incubate.jit_train import jit_train_step

            def loss_fn(out, ys):
                outs = _to_list(out)
                ys = list(ys) if isinstance(ys, tuple) else [ys]
                losses = _to_list(m._loss(*(outs + ys)))
                total = losses[0]
                for l in losses[1:]:
                    total = total + l
                return total

            self._jit_step = jit_train_step(
                m.network, loss_fn, m._optimizer,
                amp_level=m._amp_level, amp_dtype=m._amp_dtype,
                return_outputs=True)
        except NotImplementedError as e:
            self._jit_unavailable = True
            import warnings
            warnings.warn(
                f"Model.fit: whole-program compiled training is not "
                f"available for this configuration ({e}); running the "
                f"eager loop (orders of magnitude slower on TPU)",
                stacklevel=3)
            return None
        return self._jit_step

    def train_batch(self, inputs, labels=None, update=True):
        m = self.model
        net = m.network
        net.train()
        inputs = _to_list(inputs)
        labels = _to_list(labels)
        inputs = [to_tensor(i) if not isinstance(i, Tensor) else i
                  for i in inputs]
        labels = [to_tensor(l) if not isinstance(l, Tensor) else l
                  for l in labels]
        if not update:
            # gradient accumulation interleaves update=False eager
            # backward passes — the compiled step would ignore those
            # accumulated grads, so disable it until the next fit()
            # (reset_jit_eligibility) and say so once
            if not self._jit_unavailable:
                import warnings
                warnings.warn(
                    "Model.fit: gradient accumulation runs the eager "
                    "loop (the compiled step cannot consume eager-"
                    "accumulated grads)", stacklevel=2)
            self._jit_unavailable = True
        if update:
            step = self._compiled_step()
            if step is not None:
                try:
                    loss, outs = step(tuple(inputs), tuple(labels))
                except Exception as e:
                    self._jit_unavailable = True
                    self._jit_step = None
                    import warnings
                    warnings.warn(
                        f"Model.fit: compiled step rejected this model "
                        f"({e}); falling back to the eager loop",
                        stacklevel=2)
                else:
                    outputs = _to_list(outs)
                    metrics = []
                    for metric in m._metrics:
                        res = metric.compute(*(outputs + labels))
                        metrics.append(metric.update(*_to_list(res)))
                    # multi-component losses: the step optimises the
                    # SUM (same as eager), but logging must keep the
                    # per-component shape — recompute components from
                    # the returned outputs (cheap: loss head only)
                    if self._loss_arity is None:
                        with tape.no_grad_guard():
                            self._loss_arity = len(_to_list(
                                m._loss(*(outputs + labels))))
                    if self._loss_arity > 1:
                        with tape.no_grad_guard():
                            comps = _to_list(
                                m._loss(*(outputs + labels)))
                        loss_vals = [
                            float(np.asarray(l.numpy()).ravel()[0])
                            for l in comps]
                    else:
                        loss_vals = [float(loss)]
                    if metrics:
                        return (loss_vals, metrics[0]
                                if len(metrics) == 1 else metrics)
                    return loss_vals
        if m._amp_level != "O0":
            from .. import amp as amp_mod
            ctx = amp_mod.auto_cast(level=m._amp_level,
                                    dtype=m._amp_dtype)
        else:
            import contextlib
            ctx = contextlib.nullcontext()
        with ctx:
            outputs = net(*inputs)
            outputs = _to_list(outputs)
            losses = m._loss(*(outputs + labels)) if m._loss else None
        losses_list = _to_list(losses)
        total = losses_list[0]
        for l in losses_list[1:]:
            total = total + l
        if m._scaler is not None:
            scaled = m._scaler.scale(total)
            scaled.backward()
            if update:
                m._scaler.step(m._optimizer)
                m._scaler.update()
                m._optimizer.clear_grad()
        else:
            total.backward()
            if update:
                m._optimizer.step()
                m._optimizer.clear_grad()
        metrics = []
        for metric in m._metrics:
            res = metric.compute(*(outputs + labels))
            metrics.append(metric.update(*_to_list(res)))
        loss_vals = [float(np.asarray(l.numpy()).ravel()[0])
                     for l in losses_list]
        if metrics:
            return (loss_vals, metrics[0] if len(metrics) == 1 else metrics)
        return loss_vals

    @tape.no_grad_guard()
    def eval_batch(self, inputs, labels=None):
        m = self.model
        net = m.network
        net.eval()
        inputs = [to_tensor(i) if not isinstance(i, Tensor) else i
                  for i in _to_list(inputs)]
        labels = [to_tensor(l) if not isinstance(l, Tensor) else l
                  for l in _to_list(labels)]
        outputs = self._eval_outputs(inputs)
        metrics = []
        loss_vals = None
        if m._loss:
            losses = _to_list(m._loss(*(outputs + labels)))
            loss_vals = [float(np.asarray(l.numpy()).ravel()[0])
                         for l in losses]
        for metric in m._metrics:
            res = metric.compute(*(outputs + labels))
            metrics.append(metric.update(*_to_list(res)))
        if metrics:
            return (loss_vals, metrics[0] if len(metrics) == 1 else metrics)
        return loss_vals

    @tape.no_grad_guard()
    def predict_batch(self, inputs):
        m = self.model
        net = m.network
        net.eval()
        inputs = [to_tensor(i) if not isinstance(i, Tensor) else i
                  for i in _to_list(inputs)]
        outputs = self._eval_outputs(inputs)
        return [o.numpy() for o in outputs]


class Model:
    """Reference: hapi/model.py:1052."""

    def __init__(self, network: Layer, inputs=None, labels=None):
        self.network = network
        self._inputs = inputs
        self._labels = labels
        self._loss = None
        self._optimizer = None
        self._metrics: List[Metric] = []
        self._scaler = None
        self._amp_level = "O0"
        self._amp_dtype = "bfloat16"
        self.stop_training = False
        self._adapter = _DynamicGraphAdapter(self)

    # -- setup --------------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None, jit=False):
        self._optimizer = optimizer
        if loss is not None and not (isinstance(loss, Layer) or
                                     callable(loss)):
            raise TypeError("loss must be a Layer or callable")
        self._loss = loss
        self._metrics = _to_list(metrics)
        for m in self._metrics:
            if not isinstance(m, Metric):
                raise TypeError(f"metric {m} is not a paddle Metric")
        if amp_configs is not None:
            if isinstance(amp_configs, str):
                self._amp_level = amp_configs
            else:
                self._amp_level = amp_configs.get("level", "O1")
                self._amp_dtype = amp_configs.get("dtype", "bfloat16")
            if self._amp_dtype == "float16" and self._amp_level != "O0":
                from ..amp import GradScaler
                self._scaler = GradScaler()
        if jit:
            from ..jit import to_static
            self.network = to_static(self.network)

    # -- batch-level --------------------------------------------------------
    def train_batch(self, inputs, labels=None, update=True):
        return self._adapter.train_batch(inputs, labels, update)

    def eval_batch(self, inputs, labels=None):
        return self._adapter.eval_batch(inputs, labels)

    def predict_batch(self, inputs):
        return self._adapter.predict_batch(inputs)

    # -- loops --------------------------------------------------------------
    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1,
            verbose=2, drop_last=False, shuffle=True, num_workers=0,
            callbacks=None, accumulate_grad_batches=1, num_iters=None):
        """Reference: model.py:1750."""
        self._adapter.reset_jit_eligibility()
        if isinstance(train_data, Dataset):
            train_loader = DataLoader(train_data, batch_size=batch_size,
                                      shuffle=shuffle, drop_last=drop_last,
                                      num_workers=num_workers)
        else:
            train_loader = train_data
        if eval_data is not None and isinstance(eval_data, Dataset):
            eval_loader = DataLoader(eval_data, batch_size=batch_size,
                                     num_workers=num_workers)
        else:
            eval_loader = eval_data

        try:
            steps = len(train_loader)
        except Exception:
            steps = None
        cbks = config_callbacks(callbacks, model=self,
                                batch_size=batch_size, epochs=epochs,
                                steps=steps, log_freq=log_freq,
                                verbose=verbose, save_freq=save_freq,
                                save_dir=save_dir,
                                metrics=self._metrics_name())
        self.stop_training = False
        cbks.on_train_begin()
        from ..profiler.timer import benchmark
        bench = benchmark()
        bench.begin('train')
        it_count = 0
        try:
            for epoch in range(epochs):
                cbks.on_epoch_begin(epoch)
                for m in self._metrics:
                    m.reset()
                logs = {}
                accum = 0
                it = iter(train_loader)
                step = 0
                bench.reset_step_timer()
                while True:
                    bench.before_reader()
                    try:
                        data = next(it)
                    except StopIteration:
                        break
                    bench.after_reader()
                    cbks.on_train_batch_begin(step)
                    ins, labels = self._split_data(data)
                    accum += 1
                    update = accum % accumulate_grad_batches == 0
                    from ..utils.logging import step_statistics
                    with step_statistics.timer("train_batch"):
                        out = self.train_batch(ins, labels,
                                               update=update)
                    step_statistics.bump("train_batches")
                    logs = self._make_logs(out)
                    # actual per-batch sample count (last batch may be short;
                    # a user-supplied DataLoader ignores the batch_size arg)
                    n_samples = _batch_len(ins, batch_size)
                    logs["batch_size"] = n_samples
                    bench.after_step(n_samples)
                    logs["ips"] = bench.current_event.speed_average() \
                        if bench.current_event else 0.0
                    cbks.on_train_batch_end(step, logs)
                    it_count += 1
                    step += 1
                    if num_iters is not None and it_count >= num_iters:
                        self.stop_training = True
                        break
                cbks.on_epoch_end(epoch, logs)
                if eval_loader is not None and (epoch + 1) % eval_freq == 0:
                    eval_logs = self._run_eval(eval_loader, cbks)
                bench.reset_step_timer()
                if self.stop_training:
                    break
        finally:
            bench.end()
        cbks.on_train_end(logs)

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_iters=None):
        self._adapter.reset_jit_eligibility()
        if isinstance(eval_data, Dataset):
            loader = DataLoader(eval_data, batch_size=batch_size,
                                num_workers=num_workers)
        else:
            loader = eval_data
        cbks = config_callbacks(callbacks, model=self, verbose=verbose,
                                metrics=self._metrics_name())
        logs = self._run_eval(loader, cbks, num_iters=num_iters)
        return logs

    def _run_eval(self, loader, cbks, num_iters=None):
        for m in self._metrics:
            m.reset()
        cbks.on_eval_begin()
        logs = {}
        for step, data in enumerate(loader):
            cbks.on_eval_batch_begin(step)
            ins, labels = self._split_data(data)
            out = self.eval_batch(ins, labels)
            logs = self._make_logs(out, prefix="eval_" if False else "")
            cbks.on_eval_batch_end(step, logs)
            if num_iters is not None and step + 1 >= num_iters:
                break
        # final metric values
        for m in self._metrics:
            res = m.accumulate()
            names = m.name() if isinstance(m.name(), list) else [m.name()]
            vals = res if isinstance(res, list) else [res]
            for n, v in zip(names, vals):
                logs[n] = v
        cbks.on_eval_end(logs)
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, verbose=1, callbacks=None):
        self._adapter.reset_jit_eligibility()
        if isinstance(test_data, Dataset):
            loader = DataLoader(test_data, batch_size=batch_size,
                                num_workers=num_workers)
        else:
            loader = test_data
        cbks = config_callbacks(callbacks, model=self, verbose=verbose)
        cbks.on_predict_begin()
        outputs = []
        for step, data in enumerate(loader):
            cbks.on_predict_batch_begin(step)
            ins, _ = self._split_data(data, has_labels=False)
            out = self.predict_batch(ins)
            outputs.append(out)
            cbks.on_predict_batch_end(step)
        cbks.on_predict_end()
        # transpose: list of per-batch lists -> list per output
        n_out = len(outputs[0]) if outputs else 0
        result = [[b[i] for b in outputs] for i in range(n_out)]
        if stack_outputs:
            result = [np.concatenate(r, axis=0) for r in result]
        return result

    # -- save/load ----------------------------------------------------------
    def save(self, path, training=True):
        from ..framework.io import save as fsave
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        if training:
            fsave(self.network.state_dict(), path + ".pdparams")
            if self._optimizer is not None:
                fsave(self._optimizer.state_dict(), path + ".pdopt")
        else:
            from ..jit import save as jsave
            jsave(self.network, path)

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from ..framework.io import load as fload
        state = fload(path + ".pdparams")
        self.network.set_state_dict(state)
        opt_path = path + ".pdopt"
        if not reset_optimizer and self._optimizer is not None and \
                os.path.exists(opt_path):
            self._optimizer.set_state_dict(fload(opt_path))

    def parameters(self, *args, **kwargs):
        return self.network.parameters()

    def summary(self, input_size=None, dtype=None):
        return summary(self.network, input_size, dtypes=dtype)

    # -- helpers ------------------------------------------------------------
    def _metrics_name(self):
        names = ["loss"]
        for m in self._metrics:
            n = m.name()
            names.extend(n if isinstance(n, list) else [n])
        return names

    def _split_data(self, data, has_labels=True):
        if isinstance(data, (list, tuple)):
            if not has_labels:
                # dataset items may still carry labels (predict over a
                # labelled dataset): drop the trailing label field
                if len(data) >= 2 and self._loss is not None:
                    return list(data[:-1]), None
                return list(data), None
            if len(data) >= 2:
                *ins, label = data
                # common case: (x, y)
                if len(data) == 2:
                    return [data[0]], [data[1]]
                return ins, [label]
            return list(data), None
        return [data], None

    def _make_logs(self, out, prefix=""):
        logs = {}
        if out is None:
            return logs
        if isinstance(out, tuple) and len(out) == 2 and isinstance(
                out[0], list):
            losses, met = out
            logs[prefix + "loss"] = losses
            names = []
            for m in self._metrics:
                n = m.name()
                names.extend(n if isinstance(n, list) else [n])
            mets = met if isinstance(met, list) else [met]
            for n, v in zip(names, mets):
                logs[prefix + n] = v
        else:
            logs[prefix + "loss"] = out
        return logs


def summary(net: Layer, input_size=None, dtypes=None, input=None):
    """Reference: hapi/summary.py — layer table + parameter counts."""
    rows = []
    total_params = 0
    trainable = 0
    for name, layer in net.named_sublayers(include_self=True):
        n_params = sum(p.size for p in layer._parameters.values()
                       if p is not None)
        rows.append((name or type(net).__name__, type(layer).__name__,
                     n_params))
    for p in net.parameters():
        total_params += p.size
        if not p.stop_gradient:
            trainable += p.size
    line = "-" * 72
    print(line)
    print(f"{'Layer (type)':<40}{'Params':>12}")
    print(line)
    for name, tname, n in rows:
        print(f"{name + ' (' + tname + ')':<40}{n:>12,}")
    print(line)
    print(f"Total params: {total_params:,}")
    print(f"Trainable params: {trainable:,}")
    print(f"Non-trainable params: {total_params - trainable:,}")
    print(line)
    return {"total_params": total_params, "trainable_params": trainable}
